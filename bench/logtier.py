"""What the log tier's two systems (``systems/stream.py``,
``systems/day.py``) share: the day of client events drawn from the seed,
the funnel's stage codes, the least-bytes yardstick, and the comparison
with the reference over the same events.

Every number compared is exact (limit 0): a count of differences, of
drops, or of compiles. With ``control`` the reference with dedup off is
judged in place of what the program stored and folded; it must fail.
"""
from __future__ import annotations

import numpy as np

from bench import least_bytes, loggen
from bench.reference import fast

LIMITS = dict(sessions_mismatched=0, bigram_abs_diff=0, funnel_abs_diff=0,
              events_dropped=0, sessions_truncated=0, window_compiles=0)


def compare(out: dict, ref: dict, window_compiles: int) -> dict:
    """``{name: {"value": v, "limit": l}}`` for each number compared."""
    values = dict(
        sessions_mismatched=fast.multiset_difference(
            fast.session_hashes(out["sessions"]),
            fast.session_hashes(ref["sessions"])),
        bigram_abs_diff=int(np.abs(out["bigrams"] - ref["bigrams"]).sum()),
        funnel_abs_diff=int(np.abs(out["reach"] - ref["reach"]).sum()),
        events_dropped=int(out["dropped"]),
        sessions_truncated=int(out["truncated"]),
        window_compiles=int(window_compiles))
    return {k: dict(value=v, limit=LIMITS[k]) for k, v in values.items()}


class LogSystem:
    """The part of a log system that is not its program: ``from_seed``
    draws the day and the funnel's stages, and the subclass's ``build``
    puts the program on the mesh; a step is ``unit`` events; ``tally``
    counts the window's events as attempted and the events the program
    dropped after the warm steps as failed."""

    span = ""
    sub_spans: tuple[str, ...] = ()

    def __init__(self, cfg: dict, day: dict, stages, mesh):
        self.cfg, self.day, self.stages = cfg, day, stages
        self.build(mesh)

    @classmethod
    def from_seed(cls, cfg: dict, traffic: dict, seed: int,
                  mesh) -> "LogSystem":
        day = loggen.generate(cfg, seed)
        code_of_name = loggen.assign_codes(day["name_id"],
                                           len(loggen.name_table()))
        return cls(cfg, day, loggen.stage_codes(cfg["funnel"], code_of_name),
                   mesh)

    def build(self, mesh) -> None:
        raise NotImplementedError

    def warm(self) -> None:
        for _ in range(self.cfg["warm_steps"]):
            self.step(self.unit)
        self.dropped_before = self.dropped()

    def compared(self, window_compiles: int, control: bool) -> dict:
        ref = self.reference(dedup=self.cfg["dedup"])
        out = self.output()
        if control:
            out = dict(self.reference(dedup=False), dropped=0, truncated=0)
        return compare(out, ref, window_compiles)

    def tally(self, window: dict) -> tuple[int, int]:
        return window["events"], self.dropped() - self.dropped_before

    def ctx(self) -> dict:
        return dict(bytes_per_event=getattr(
            least_bytes, self.cfg["least_bytes"])(self.cfg))
