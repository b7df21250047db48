"""The benchmark: one cell of BENCHMARK.json, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py ... --rehearse    # tiny sizes on the CPU
    python3 bench/run.py ... --control     # the system's control in the
                                           # program's place (must come
                                           # out not correct)

A run builds the cell's system (``systems/<system>.py``, named by its
configuration) from ``--seed`` on the cell's chips: the system draws its
own input (a day of client events, a day's sessions as requests) and
puts the program on the chips. It warms every shape it will use, then
measures for ``--seconds`` under the cell's traffic
(``traffic/<traffic>.json``, driven by ``loops.py``). After the window
the system finishes what is in flight and compares what the program
produced with its own plain reference (``System.compared``: each number
with its limit; ``check.py`` gives the verdict). With ``--trace 0`` the
result's metrics are the cell's end-to-end metrics; with ``--trace 1``
the window runs under the profiler and they are its per-layer metrics,
each read by ``metrics/<name>.py`` from ``ctx``.

A system module holds ``System`` with: ``from_seed(cfg, traffic, seed, mesh)``;
``span`` and ``sub_spans`` (its host spans); ``unit`` and
``step(n) -> units done`` (events or tokens); ``warm()``; ``finish()``;
``compared(window_compiles, control)``; ``tally(window) -> (attempted,
failed)``; and ``ctx()``, the keys it adds for the metric readers.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last the numbers compared, each with its limit.
Standard error ends with the same numbers. Without a TPU, or with fewer
chips than the cell asks for, the run prints no result and exits 2;
``--rehearse`` runs on the CPU, and its result names the CPU. JAX keeps
compiled programs in ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else
in ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import importlib.util                                        # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
import tempfile                                              # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: checks control flow, "
                         "never a chip number")
    ap.add_argument("--control", action="store_true",
                    help="judge the system's control (the log tier's "
                         "reference with dedup off, the served model's "
                         "reference in a lower precision) in place of the "
                         "program: must come out not correct")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a whole number >= 0")
    return args


def _load_module(path: str):
    name = "bench._loaded." + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class CompileCounter:
    """Counts XLA compiles while it is open (a persistent-cache hit is no
    compile)."""

    def __init__(self):
        import jax
        self.n = 0
        self._monitoring = jax.monitoring
        self._monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.n += 1

    def close(self):
        self._monitoring.unregister_event_duration_listener(self._on)


def _devices(chips: int, rehearse: bool):
    import jax
    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def _enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _device_facts(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices),
                memory_peak_bytes=max(peaks) if None not in peaks else None)


def run(args, t_start: float) -> dict:
    """One run of ``args.workload``; returns the result object."""
    from bench import check, loops, manifest, peaks, trace_reduce

    man = manifest.load(ROOT)
    cell = manifest.cell(man, args.workload)
    cfg = _read_json(os.path.join(
        ROOT, manifest.config_entry(man, cell["config"])["file"]))
    traffic = _read_json(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json"))
    if args.rehearse:
        cfg.update(cfg["rehearse"])
        traffic.update(traffic.get("rehearse", {}))
    chips = cell["chips"]
    if args.rehearse and chips > 1 and "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   "--xla_force_host_platform_device_count="
                                   f"{chips}").strip()

    import jax
    from jax.profiler import TraceAnnotation
    devices = _devices(chips, args.rehearse)
    if not args.rehearse:
        _enable_compile_cache()
    counter = CompileCounter()
    try:
        mesh = jax.sharding.Mesh(devices, ("data",))
        system = _load_module(os.path.join(
            BENCH, "systems", cfg["system"] + ".py")).System.from_seed(
                cfg, traffic, args.seed, mesh)
        system.warm()

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") \
            if args.trace else None
        setup_s = time.perf_counter() - t_start
        compiles_before = counter.n
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with TraceAnnotation("window"):
                window = loops.LOOPS[traffic["loop"]](
                    system, args.seconds, traffic, TraceAnnotation)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        window_compiles = counter.n - compiles_before
    finally:
        counter.close()
    device = _device_facts(devices)

    summary = planes = None
    if trace_dir:
        try:
            planes = None if args.rehearse else trace_reduce.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if planes is not None:
        spans = trace_reduce.host_spans(planes, ["window"])
        if len(spans) != 1:
            raise RuntimeError(f"found {len(spans)} window spans in the "
                               "trace, not one")
        summary = trace_reduce.reduce(
            planes, spans[0][1:], (system.span,) + system.sub_spans)
        device.update(busy_s=summary["busy_s"],
                      window_s=summary["window_s"])
        print("device seconds and runs by program:", json.dumps([
            [k, v, summary["module_n"][k]] for k, v in sorted(
                summary["module_s"].items(), key=lambda kv: -kv[1])[:12]]),
            file=sys.stderr)

    system.finish()
    compared = system.compared(window_compiles, args.control)

    ctx = dict(setup_s=setup_s, window=window, span=system.span,
               trace=summary, chips=chips,
               peaks=None if args.rehearse else peaks.peaks(
                   devices[0].device_kind), **system.ctx())
    wanted = (manifest.per_layer(man, cell["name"]) if args.trace
              else manifest.end_to_end(man, cell["name"]))
    metrics = {}
    for m in wanted:
        value = _load_module(os.path.join(
            BENCH, "metrics", m["name"] + ".py")).read(ctx)
        if value is not None:
            metrics[m["name"]] = dict(value=value, unit=m["unit"])
    attempted, failed = system.tally(window)
    result = dict(correct=check.passed(compared), attempted=attempted,
                  failed=failed, metrics=metrics, device=device)
    if summary is not None:
        result["breakdown"] = trace_reduce.breakdown(summary)
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the benchmark imports as the package ``bench``, the program as ``repro``
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    try:
        result = run(args, T_START)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    from bench import check
    check.report(result["compared"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
