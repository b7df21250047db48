"""A profiler trace reduced to what the benchmark's metrics read.

``load`` turns a JAX profiler ``.xplane.pb`` into plain lists: planes, their
lines, and events with a name, a start and a duration in ns. ``reduce``
takes those and a window (the benchmark's own ``window`` host span):

* ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), inside the
  window, averaged over the devices;
* ``op_kind_s``: device seconds per HLO opcode (``sort``, ``fusion``,
  ``scatter``, ...), summed over the devices;
* ``op_name_s``: device seconds per op, named by its HLO name and output
  shape (``fusion.636 pred[69632]``), summed over the devices and steps;
* ``module_s`` and ``module_n``: device seconds and runs per compiled
  program (the ``XLA Modules`` line, named with its id, as in
  ``jit_step_fn(3)``), summed over the devices;
* ``collective_s`` and ``collective_exposed_s``: time of collective ops
  (all-to-all, all-reduce, all-gather, ...), and the part of it in which
  no other op ran on that device, averaged over the devices;
* ``gaps``: every idle stretch of a device inside the window, each with
  the innermost benchmark host span open at its middle (``none`` if none).

Host and device events share the trace's clock: both count from the start
of the trace, which the profiler takes for every plane.
"""
from __future__ import annotations

import glob
import re

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "none"
# ops whose events span the ops of their bodies, which the line also holds
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")


def load(trace_dir: str) -> list[dict]:
    """The planes of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    data = ProfileData.from_file(paths[0])
    return [dict(name=p.name, lines=[
        dict(name=ln.name, events=[(e.name, float(e.start_ns),
                                    float(e.duration_ns)) for e in ln.events])
        for ln in p.lines]) for p in data.planes]


def _short_name(name: str) -> str:
    """``%sort.3 = (s32[..]) sort(..)`` -> ``sort``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def op_label(name: str) -> str:
    """``%fusion.636 = pred[69632]{0:T(1024)} fusion(..)`` ->
    ``fusion.636 pred[69632]``: the op and its output shape without
    layout (a tuple's first element)."""
    head, _, rest = name.partition(" = ")
    shape = re.match(r"\(?\s*([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (" " + shape.group(1) if shape else "")


def opcode(name: str) -> str:
    """The HLO opcode in an op's text (``%x = <shape> opcode(...)``); the
    short name where the text holds no shape."""
    if " = " not in name:
        return _short_name(name)
    rest = name.split(" = ", 1)[1]
    if rest.startswith("("):                       # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    m = re.match(r"\s*([A-Za-z][\w\-]*)\(", rest)
    return m.group(1) if m else _short_name(name)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint, ordered cover of the intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(cover) -> float:
    return sum(b - a for a, b in cover)


def _minus(cover, other) -> float:
    """Length of ``cover`` outside ``other`` (both disjoint and ordered)."""
    return _length(cover) - _length(union(
        [(max(a, c), min(b, d)) for a, b in cover for c, d in other
         if min(b, d) > max(a, c)]))


def host_spans(planes: list[dict], names) -> list[tuple[str, float, float]]:
    """Every host event whose name is one of ``names``."""
    names = set(names)
    return [(n, s, s + d) for p in planes if not p["name"].startswith(
                DEVICE_PREFIX)
            for ln in p["lines"] for n, s, d in ln["events"] if n in names]


def _span_at(spans, t: float) -> str:
    inner = [(e - s, n) for n, s, e in spans if s <= t < e]
    return min(inner)[1] if inner else NO_SPAN


def reduce(planes: list[dict], window: tuple[float, float],
           span_names=()) -> dict:
    """Busy time, op times and idle gaps inside ``window`` (trace ns)."""
    lo, hi = window
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)
               and any(ln["name"] == OPS_LINE for ln in p["lines"])]
    if not devices:
        raise RuntimeError("the trace holds no device with an "
                           f"'{OPS_LINE}' line")
    spans = host_spans(planes, span_names)
    busy_ns = coll_ns = exposed_ns = 0.0
    kind_ns: dict[str, float] = {}
    name_ns: dict[str, float] = {}
    module_ns: dict[str, float] = {}
    module_n: dict[str, int] = {}
    gaps: list[tuple[str, float]] = []
    for dev in devices:
        clipped, coll, compute = [], [], []
        for ln in dev["lines"]:
            if ln["name"] == MODULES_LINE:
                for name, s, d in ln["events"]:
                    a, b = max(s, lo), min(s + d, hi)
                    if b > a:
                        module_ns[name] = module_ns.get(name, 0.0) + (b - a)
                        module_n[name] = module_n.get(name, 0) + 1
            if ln["name"] != OPS_LINE:
                continue
            for name, s, d in ln["events"]:
                a, b = max(s, lo), min(s + d, hi)
                if b <= a:
                    continue
                clipped.append((a, b))
                k = opcode(name)
                if k in CONTAINERS:
                    continue
                (coll if k.startswith(COLLECTIVES) else compute).append(
                    (a, b))
                kind_ns[k] = kind_ns.get(k, 0.0) + (b - a)
                n = op_label(name)
                name_ns[n] = name_ns.get(n, 0.0) + (b - a)
        cover = union(clipped)
        busy_ns += _length(cover)
        coll_cover = union(coll)
        coll_ns += _length(coll_cover)
        exposed_ns += _minus(coll_cover, union(compute))
        edges = [lo] + [x for ab in cover for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_span_at(spans, (a + b) / 2), (b - a) * 1e-9))
    return dict(busy_s=busy_ns * 1e-9 / len(devices),
                window_s=(hi - lo) * 1e-9, devices=len(devices),
                collective_s=coll_ns * 1e-9 / len(devices),
                collective_exposed_s=exposed_ns * 1e-9 / len(devices),
                op_kind_s={k: v * 1e-9 for k, v in kind_ns.items()},
                op_name_s={k: v * 1e-9 for k, v in name_ns.items()},
                module_s={k: v * 1e-9 for k, v in module_ns.items()},
                module_n=module_n,
                gaps=gaps)


def breakdown(summary: dict, top: int = 10) -> dict:
    """The device ops that took most time, and the idle time by the host
    span open in it, each a list of ``[name, seconds]``."""
    ops = sorted(summary["op_name_s"].items(), key=lambda kv: -kv[1])
    idle: dict[str, float] = {}
    for name, secs in summary["gaps"]:
        idle[name] = idle.get(name, 0.0) + secs
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])
    return dict(device_ops=[[k, v] for k, v in ops[:top]],
                idle_gaps=[[k, v] for k, v in gaps[:top]])
