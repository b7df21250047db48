"""The program's own host spans (``repro.core.spans``) in the measured
window, for the readers of ``program_span`` metrics.

The window's spans are those of its last ``K`` step spans
(``streampipe.tick`` or ``distpipe.call``), ``K`` being the window's step
count, and every span that starts after the first of them; spans inside
a flush tick (after the window, before the check) are left out. A
program without the span module, or a buffer that no longer holds the
whole window, gives None.
"""
from __future__ import annotations

STEP_SPANS = ("streampipe.tick", "distpipe.call")


def window_spans(ctx):
    """The window's records from ``repro.core.spans.recent()``, or None."""
    try:
        from repro.core.spans import recent
    except ImportError:
        return None
    recorded = recent()
    k = len(ctx["window"]["step_s"])
    flushes = [(r.start_ns, r.end_ns) for r in recorded
               if r.name == "streampipe.tick" and r.counts.get("flush")]
    steps = sorted(r.start_ns for r in recorded
                   if r.name in STEP_SPANS and not r.counts.get("flush"))
    if k == 0 or len(steps) < k:
        return None
    first = steps[-k]
    return [r for r in recorded if r.start_ns >= first
            and not any(a <= r.start_ns <= b for a, b in flushes)]


def ns_per_event(ctx, names) -> float | None:
    """Summed duration of the window's spans named in ``names``, in ns,
    per event of the window; None without a trace or without spans."""
    if ctx["trace"] is None:
        return None
    spans = window_spans(ctx)
    if spans is None:
        return None
    ns = sum(r.end_ns - r.start_ns for r in spans if r.name in names)
    return ns / ctx["window"]["events"]
