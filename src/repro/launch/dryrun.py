import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape x
mesh) cell, prove it fits (memory_analysis), and extract roofline inputs
(cost_analysis + HLO collective bytes).

Two modes per cell:

* ``full``  — the REAL config (scanned layers, production microbatching),
  compiled on the production mesh. Proves sharding coherence + per-device
  memory. XLA's HloCostAnalysis counts while-loop bodies ONCE, so this
  compile is NOT used for FLOPs.
* ``cost``  — reduced-depth UNROLLED variants (layers + microbatches as
  python loops) compiled on the single-pod mesh; costs are exactly linear
  (train: bilinear in (L, microbatches)), so two/three points extrapolate
  to the full depth. Collective bytes come from the unrolled optimized HLO
  (no while loops -> every collective instruction is counted once, true).

Results are cached as JSON per (arch, shape, mesh, mode) under
``results/dryrun/``; the sweep driver runs each cell in a subprocess.

Besides the model cells there are pipeline cells: the distributed log
pipeline (data/distpipe.py) lowered at hour-of-events shapes on the
production mesh, for all_to_all/psum collective sizing — and stream cells:
one streaming micro-batch tick (data/streampipe.py) lowered at
events-per-tick shapes (ring merge + repartition + delta psums).

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
      --mesh single --mode full
  python -m repro.launch.dryrun --pipeline hour_1m --mesh single
  python -m repro.launch.dryrun --stream tick_64k --mesh single
  python -m repro.launch.dryrun --store compact_1m
  python -m repro.launch.dryrun --all            # full sweep (both meshes)
"""
import argparse
import json
import re
import subprocess
import sys
import time
import traceback

import jax
import numpy as np

RESULTS_DIR = os.environ.get("DRYRUN_RESULTS",
                             os.path.join(os.path.dirname(__file__),
                                          "../../../results/dryrun"))

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|([a-z0-9]+)\[([0-9,]*)\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_TUPLE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device payload bytes of collective ops in optimized HLO.

    Convention: all-reduce counts 2x its output bytes (ring = reduce-scatter
    + all-gather); others count 1x output bytes. Tuple-shaped outputs
    (e.g. fused start ops) sum their parts. '-done' ops are skipped (the
    '-start' carries the shape).
    """
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    counts = dict(out)
    for line in hlo_text.splitlines():
        if "-done" in line and ("collective" in line or "all-" in line):
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(4)
        if m.group(2):  # plain shape
            nbytes = _shape_bytes(m.group(2), m.group(3))
        else:           # tuple shape: sum the component shapes
            nbytes = sum(_shape_bytes(d, s)
                         for d, s in _TUPLE_RE.findall(m.group(1)))
        mult = 2 if kind == "all-reduce" else 1
        out[kind] += mult * nbytes
        counts[kind] += 1
    out["total"] = sum(v for k, v in out.items())
    out["instruction_counts"] = counts
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, mode: str,
             overrides: dict | None = None, tag: str = "") -> dict:
    from ..dist.compat import cost_analysis, use_mesh
    from ..dist.mesh import make_production_mesh
    from .shapes import make_cell, cell_supported, SHAPES, Shape

    ok, reason = cell_supported(arch, shape_name)
    if not ok:
        return dict(arch=arch, shape=shape_name, mesh=mesh_kind, mode=mode,
                    skipped=True, reason=reason)

    overrides = dict(overrides or {})
    # Mesh refactorization lever (same 256 chips): {"mesh_data": 32,
    # "mesh_model": 8} etc. Consumed here, not by ModelConfig.
    data = overrides.pop("mesh_data", 16)
    model = overrides.pop("mesh_model", 256 // data)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"),
                                data=data, model=model)
    t0 = time.time()
    cell = make_cell(arch, shape_name, mesh, overrides)
    fn = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                 donate_argnums=cell.donate_argnums)
    with use_mesh(mesh):  # with_sharding_constraint(P) binds here
        lowered = fn.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = cost_analysis(compiled)
    result = dict(
        arch=arch, shape=shape_name, mesh=mesh_kind, mode=mode, tag=tag,
        skipped=False, overrides=overrides or {},
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None),
        ),
        flops=cost.get("flops"),
        bytes_accessed=cost.get("bytes accessed"),
        utilization=cost.get("utilization", None),
    )
    if mode == "cost":
        result["collectives"] = collective_bytes(compiled.as_text())
    return result


PIPELINE_SHAPES = {
    "hour_256k": 1 << 18,
    "hour_1m": 1 << 20,
    "hour_16m": 1 << 24,
}


def make_pipeline_cell(n_events: int, mesh, *, alphabet: int = 1024,
                       max_len: int = 256, n_stages: int = 4,
                       capacity_factor: float = 2.0):
    """(fn, args, in_shardings) for the distributed log pipeline.

    Event columns are ShapeDtypeStructs sharded over the mesh ``data`` axis
    (the log mover's arbitrary partitioning); the funnel stage table is
    replicated. Lowering must run under ``jax.experimental.enable_x64`` —
    the columns are int64.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..data.distpipe import DistPipelineConfig, build_pipeline_fn

    n_shards = mesh.shape["data"]
    cfg = DistPipelineConfig(
        alphabet_size=alphabet,
        max_sessions_per_shard=-(-n_events // n_shards),
        max_len=max_len, capacity_factor=capacity_factor)
    fn = build_pipeline_fn(mesh, cfg, n_stages)
    sds = jax.ShapeDtypeStruct
    args = (sds((n_events,), np.int64), sds((n_events,), np.int64),
            sds((n_events,), np.int64), sds((n_events,), np.int32),
            sds((n_events,), np.int64), sds((n_events,), bool),
            sds((n_stages, alphabet), bool))
    col = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    return fn, args, (col,) * 6 + (rep,)


def run_pipeline_cell(shape_name: str, mesh_kind: str,
                      overrides: dict | None = None, tag: str = "") -> dict:
    """Lower + compile the distributed log pipeline on the production mesh
    and extract the same memory/cost/collective-bytes roofline inputs as the
    model cells. The pipeline has no while loops, so collective bytes from
    the optimized HLO are exact (the keyed all_to_all dominates)."""
    from ..dist.compat import enable_x64
    from ..dist.compat import cost_analysis, use_mesh
    from ..dist.mesh import make_production_mesh

    overrides = dict(overrides or {})
    data = overrides.pop("mesh_data", 16)
    model = overrides.pop("mesh_model", 256 // data)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"),
                                data=data, model=model)
    n_events = PIPELINE_SHAPES[shape_name]
    t0 = time.time()
    fn, args, in_sh = make_pipeline_cell(n_events, mesh, **overrides)
    jitted = jax.jit(fn, in_shardings=in_sh)
    with enable_x64():
        with use_mesh(mesh):
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = cost_analysis(compiled)
    return dict(
        arch="pipeline", shape=shape_name, mesh=mesh_kind, mode="cost",
        tag=tag, skipped=False, n_events=n_events,
        overrides=overrides or {},
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None),
        ),
        flops=cost.get("flops"),
        bytes_accessed=cost.get("bytes accessed"),
        utilization=cost.get("utilization", None),
        collectives=collective_bytes(compiled.as_text()),
    )


STREAM_SHAPES = {
    "tick_64k": 1 << 16,
    "tick_256k": 1 << 18,
}


def make_stream_cell(tick_events: int, mesh, *, alphabet: int = 1024,
                     max_len: int = 256, max_open: int = 4096,
                     n_stages: int = 4, capacity_factor: float = 2.0):
    """(fn, args, in_shardings) for one streaming micro-batch tick.

    The ring state and event columns are ShapeDtypeStructs sharded over the
    mesh ``data`` axis; the two watermarks and the stage table are
    replicated. Like the batch pipeline cell, lowering runs under
    ``enable_x64`` (int64 ids/timestamps end-to-end).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..data.streampipe import (StreamConfig, build_stream_tick_fn,
                                   stream_state_structs)

    n_shards = mesh.shape["data"]
    cfg = StreamConfig(
        alphabet_size=alphabet, max_open=max_open, max_len=max_len,
        tick_capacity=tick_events, capacity_factor=capacity_factor)
    fn = build_stream_tick_fn(mesh, cfg, n_stages)
    sds = jax.ShapeDtypeStruct
    ring = stream_state_structs(cfg, n_shards)
    args = (ring,
            sds((tick_events,), np.int64), sds((tick_events,), np.int64),
            sds((tick_events,), np.int64), sds((tick_events,), np.int32),
            sds((tick_events,), np.int64), sds((tick_events,), bool),
            sds((), np.int64), sds((), np.int64),
            sds((n_stages, alphabet), bool))
    col = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    ring_sh = {k: col for k in ring}
    return fn, args, (ring_sh,) + (col,) * 6 + (rep,) * 3


def run_stream_cell(shape_name: str, mesh_kind: str,
                    overrides: dict | None = None, tag: str = "") -> dict:
    """Lower + compile one streaming tick on the production mesh; same
    roofline extraction as the batch pipeline cell. The tick's collectives
    are the keyed all_to_all repartition plus the rollup-delta psums."""
    from ..dist.compat import enable_x64
    from ..dist.compat import cost_analysis, use_mesh
    from ..dist.mesh import make_production_mesh

    overrides = dict(overrides or {})
    data = overrides.pop("mesh_data", 16)
    model = overrides.pop("mesh_model", 256 // data)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"),
                                data=data, model=model)
    tick_events = STREAM_SHAPES[shape_name]
    t0 = time.time()
    fn, args, in_sh = make_stream_cell(tick_events, mesh, **overrides)
    jitted = jax.jit(fn, in_shardings=in_sh)
    with enable_x64():
        with use_mesh(mesh):
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = cost_analysis(compiled)
    return dict(
        arch="stream", shape=shape_name, mesh=mesh_kind, mode="cost",
        tag=tag, skipped=False, tick_events=tick_events,
        overrides=overrides or {},
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None),
        ),
        flops=cost.get("flops"),
        bytes_accessed=cost.get("bytes accessed"),
        utilization=cost.get("utilization", None),
        collectives=collective_bytes(compiled.as_text()),
    )


STORE_SHAPES = {
    "compact_256k": 1 << 18,
    "compact_1m": 1 << 20,
}


def make_store_cell(n_events: int, *, max_len: int = 256,
                    gap_ms: int = 30 * 60 * 1000):
    """(fn, args) for the segment store's compaction kernel
    (data/store.py): the fused sort + segment sessionizer over the closed
    events of the folded segments, at worst-case caps (every event its own
    session). No mesh — compaction runs on the host that owns the store;
    the cell exists for the memory roofline (the (max_sessions, max_len)
    scatter grid dominates) and the sort/segment FLOPs.
    """
    import functools
    from ..core.sessionize import _sessionize

    fn = functools.partial(_sessionize, gap_ms=gap_ms,
                           max_sessions=n_events, max_len=max_len)
    sds = jax.ShapeDtypeStruct
    args = (sds((n_events,), np.int64), sds((n_events,), np.int64),
            sds((n_events,), np.int64), sds((n_events,), np.int32),
            sds((n_events,), np.int64), sds((n_events,), bool))
    return fn, args


def run_store_cell(shape_name: str, mesh_kind: str,
                   overrides: dict | None = None, tag: str = "") -> dict:
    """Lower + compile the store compaction kernel; same roofline
    extraction as the other cells (collective bytes are zero — the pass is
    single-host by design, the segments were already user-sharded)."""
    from ..dist.compat import enable_x64
    from ..dist.compat import cost_analysis

    n_events = STORE_SHAPES[shape_name]
    t0 = time.time()
    fn, args = make_store_cell(n_events, **(overrides or {}))
    jitted = jax.jit(fn)
    with enable_x64():
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = cost_analysis(compiled)
    return dict(
        arch="store", shape=shape_name, mesh=mesh_kind, mode="cost",
        tag=tag, skipped=False, n_events=n_events,
        overrides=overrides or {},
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None),
        ),
        flops=cost.get("flops"),
        bytes_accessed=cost.get("bytes accessed"),
        utilization=cost.get("utilization", None),
        collectives=collective_bytes(compiled.as_text()),
    )


def result_path(arch, shape, mesh, mode, tag=""):
    name = f"{arch}__{shape}__{mesh}__{mode}{('__' + tag) if tag else ''}.json"
    return os.path.join(RESULTS_DIR, name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--mode", default="full", choices=["full", "cost"])
    ap.add_argument("--overrides", default="{}")
    ap.add_argument("--tag", default="")
    ap.add_argument("--pipeline", choices=sorted(PIPELINE_SHAPES),
                    help="lower+compile the distributed log pipeline at this "
                         "shape instead of a model cell")
    ap.add_argument("--stream", choices=sorted(STREAM_SHAPES),
                    help="lower+compile one streaming micro-batch tick "
                         "(data/streampipe.py) at this tick shape instead "
                         "of a model cell")
    ap.add_argument("--store", choices=sorted(STORE_SHAPES),
                    help="lower+compile the segment-store compaction "
                         "kernel (data/store.py) at this closed-event "
                         "count instead of a model cell")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(RESULTS_DIR, exist_ok=True)

    if args.pipeline or args.stream or args.store:
        if args.arch or args.shape or args.mode != "full" or args.all \
                or sum(map(bool, (args.pipeline, args.stream,
                                  args.store))) > 1:
            ap.error("--pipeline/--stream/--store are their own cell kinds; "
                     "they cannot be combined with each other or with "
                     "--arch/--shape/--mode/--all (collective bytes are "
                     "always extracted, i.e. cost mode)")
        kind = ("pipeline" if args.pipeline
                else "stream" if args.stream else "store")
        shape = args.pipeline or args.stream or args.store
        runner = {"pipeline": run_pipeline_cell, "stream": run_stream_cell,
                  "store": run_store_cell}[kind]
        try:
            res = runner(shape, args.mesh, json.loads(args.overrides),
                         args.tag)
        except Exception:
            res = dict(arch=kind, shape=shape, mesh=args.mesh,
                       mode="cost", tag=args.tag, error=True,
                       traceback=traceback.format_exc())
        path = result_path(kind, shape, args.mesh, "cost", args.tag)
        with open(path, "w") as f:
            json.dump(res, f, indent=2)
        if res.get("error"):
            print(res["traceback"], file=sys.stderr)
            sys.exit(1)
        print(json.dumps({k: v for k, v in res.items()
                          if k != "overrides"}, indent=2))
        return

    if args.all:
        from ..configs import ASSIGNED
        from .shapes import SHAPES
        cells = [(a, s, m) for a in ASSIGNED for s in SHAPES
                 for m in ("single", "multi")]
        failures = 0
        for arch, shape, mesh in cells:
            path = result_path(arch, shape, mesh, "full")
            if os.path.exists(path) and not args.force:
                print(f"[cached] {arch} {shape} {mesh}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh,
                   "--mode", "full"]
            print(f"[run] {arch} {shape} {mesh}", flush=True)
            r = subprocess.run(cmd, cwd=os.getcwd())
            failures += (r.returncode != 0)
        sys.exit(1 if failures else 0)

    try:
        res = run_cell(args.arch, args.shape, args.mesh, args.mode,
                       json.loads(args.overrides), args.tag)
    except Exception:
        res = dict(arch=args.arch, shape=args.shape, mesh=args.mesh,
                   mode=args.mode, tag=args.tag, error=True,
                   traceback=traceback.format_exc())
    path = result_path(args.arch, args.shape, args.mesh, args.mode, args.tag)
    with open(path, "w") as f:
        json.dump(res, f, indent=2)
    if res.get("error"):
        print(res["traceback"], file=sys.stderr)
        sys.exit(1)
    if res.get("skipped"):
        print(f"SKIP {args.arch} {args.shape}: {res['reason']}")
        return
    print(json.dumps({k: v for k, v in res.items()
                      if k not in ("overrides",)}, indent=2))


if __name__ == "__main__":
    main()
