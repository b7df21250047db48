"""Record one traced step of a log-tier benchmark cell, small enough to
keep as a test fixture.

    python3 scripts/record_log_tier_trace.py --workload day-batch \
        --seed 7 --out tests/data/v5e_day_batch.planes.json.gz

Builds the cell's system as ``bench/run.py`` does (``BENCHMARK.json``,
``bench/configs``), warms it up, and runs one step under the profiler,
inside the host spans ``window`` and the cell's step span (``tick`` or
``day``). The file keeps, as planes in ``bench/trace_reduce.py``'s form:

* each device's ``XLA Ops`` events, the op's text cut to its name, output
  shape and opcode, with a parallel ``op_names`` list of the op's
  ``op_name`` (the ``jax.named_scope`` path), found in XLA's dump of the
  optimized module that the op ran in (a fusion without one takes its
  fused root's);
* the host's benchmark spans and the program's spans
  (``repro.core.spans``), with a parallel ``counts`` list of their stats.

Needs a TPU; with none it exits 2.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import json
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

BENCH_SPANS = ("window", "tick", "day", "day.pipeline", "day.store",
               "day.fold")
PROGRAM_SPANS = ("streampipe.", "distpipe.", "store.")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) [^=]*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")


def dumped_op_names(dump_dir: str) -> dict[str, dict[str, str]]:
    """Module name -> instruction name -> ``op_name``, from XLA's text
    dumps of optimized modules. A fusion without an ``op_name`` takes
    its fused computation's root's (or its first op's that has one)."""
    out: dict[str, dict[str, str]] = {}
    for path in sorted(glob.glob(
            f"{dump_dir}/*after_optimizations.txt")):
        with open(path) as f:
            text = f.read()
        module = re.search(r"^HloModule ([^ ,]+)", text, re.M)
        if module is None:
            continue
        own, calls, first, root = {}, {}, {}, {}
        comp = None
        for line in text.splitlines():
            head = _COMPUTATION.match(line)
            if head:
                comp = head.group(1)
                continue
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            name = m.group(1)
            meta = re.search(r'op_name="([^"]*)"', line)
            called = re.search(r"calls=%?([\w.\-]+)", line)
            if meta:
                own[name] = meta.group(1)
                first.setdefault(comp, meta.group(1))
                if line.lstrip().startswith("ROOT "):
                    root[comp] = meta.group(1)
            elif called:
                calls[name] = called.group(1)
        for name, comp in calls.items():
            own[name] = root.get(comp) or first.get(comp, "")
        out.setdefault(module.group(1), {}).update(own)
    return out


def _short(name: str) -> str:
    """``%fusion.7 = u32[8]{0:T(1024)} fusion(...), kind=...`` ->
    ``%fusion.7 = u32[8] fusion()``: what ``trace_reduce`` reads of it."""
    from bench import trace_reduce as tr
    if " = " not in name:
        return name
    head, _, shape = tr.op_label(name).partition(" ")
    return f"%{head} = {shape or '()'} {tr.opcode(name)}()"


def _module_at(modules, t: float) -> str:
    """The name of the module event (``jit_tick(123)`` -> ``jit_tick``)
    running at ``t``; ``modules`` sorted by start."""
    j = bisect.bisect_right([s for _, s, _ in modules], t) - 1
    if j < 0 or t > modules[j][1] + modules[j][2]:
        return ""
    return modules[j][0].split("(", 1)[0]


def _op_name(op_names, module: str, instr: str) -> str:
    """The instruction's ``op_name`` in ``module``; where no module event
    covers the op, in the one module that has the instruction."""
    if instr in op_names.get(module, {}):
        return op_names[module][instr]
    found = [m[instr] for m in op_names.values() if instr in m]
    return found[0] if len(found) == 1 else ""


def compact(data, op_names: dict[str, dict[str, str]]) -> list[dict]:
    planes = []
    for p in data.planes:
        device = p.name.startswith("/device:")
        modules = sorted(((e.name, e.start_ns, e.duration_ns)
                          for ln in p.lines if ln.name == MODULES_LINE
                          for e in ln.events), key=lambda m: m[1])
        lines = []
        for ln in p.lines:
            if device and ln.name != OPS_LINE:
                continue
            events, extra = [], []
            for e in ln.events:
                if not device and not (e.name in BENCH_SPANS
                                       or e.name.startswith(PROGRAM_SPANS)):
                    continue
                if device:
                    events.append((_short(e.name), e.start_ns,
                                   e.duration_ns))
                    extra.append(_op_name(
                        op_names, _module_at(modules, e.start_ns),
                        e.name.split(" = ", 1)[0].lstrip("%")))
                else:
                    events.append((e.name, e.start_ns, e.duration_ns))
                    extra.append({k: v for k, v in dict(e.stats).items()
                                  if isinstance(v, int)})
            if events:
                lines.append(dict(name=ln.name, events=events, **{
                    "op_names" if device else "counts": extra}))
        if lines:
            planes.append(dict(name=p.name, lines=lines))
    return planes


def record(workload: str, seed: int, dump_dir: str) -> list[dict]:
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from bench import loggen, manifest, run

    man = manifest.load(ROOT)
    cell = manifest.cell(man, workload)
    cfg = run._read_json(os.path.join(
        ROOT, manifest.config_entry(man, cell["config"])["file"]))
    devices = run._devices(cell["chips"], rehearse=False)
    # every program compiles here, so XLA dumps each one
    jax.config.update("jax_enable_compilation_cache", False)
    day = loggen.generate(cfg, seed)
    codes = loggen.assign_codes(day["name_id"], len(loggen.name_table()))
    stages = loggen.stage_codes(cfg["funnel"], codes)
    system = run._load_module(os.path.join(
        run.BENCH, "systems", cfg["system"] + ".py")).System(
            cfg, day, stages, jax.sharding.Mesh(devices, ("data",)))
    for _ in range(cfg["warm_steps"]):
        system.step(system.unit)
    with tempfile.TemporaryDirectory() as trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with TraceAnnotation("window"), TraceAnnotation(system.span):
                system.step(system.unit)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        return compact(ProfileData.from_file(path),
                       dumped_op_names(dump_dir))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from bench.run import NoChip
    dump_dir = tempfile.mkdtemp(prefix="xla-dump-")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               f"--xla_dump_to={dump_dir} "
                               "--xla_dump_hlo_as_text").strip()
    try:
        planes = record(args.workload, args.seed, dump_dir)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    finally:
        shutil.rmtree(dump_dir, ignore_errors=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(planes, f, separators=(",", ":"))
    names = [n for p in planes for ln in p["lines"]
             for n in ln.get("op_names", [])]
    print(json.dumps(dict(workload=args.workload, out=args.out,
                          ops=len(names), named=sum(map(bool, names)),
                          bytes=os.path.getsize(args.out))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
