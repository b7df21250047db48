"""The measured window, as the traffic file says: ``closed`` runs full
steps back to back and counts what each step returns (the events a log
step took, the tokens a serving step emitted); ``open`` offers events on
a fixed schedule.

Open loop: event ``q`` of the window is due at ``q / events_per_s``
seconds. Each step takes every event due when it starts, up to the
system's capacity, and sleeps only while nothing is due. An event's lag
is the completion of the step that took it minus its due time, so a
step's queue wait counts. The window ends when every event due in its
``seconds`` is stored and folded.
"""
from __future__ import annotations

import math
import time

import numpy as np


def closed(system, seconds: float, traffic: dict, span) -> dict:
    clock = time.perf_counter
    steps = []
    done = 0
    t0 = clock()
    while True:
        a = clock()
        with span(system.span):
            done += system.step(system.unit)
        b = clock()
        steps.append(b - a)
        if b - t0 >= seconds:
            break
    return dict(loop="closed", events=done, seconds=b - t0, step_s=steps)


def open_(system, seconds: float, traffic: dict, span) -> dict:
    rate = float(traffic["events_per_s"])
    cap = system.unit
    clock = time.perf_counter
    due_total = math.floor(seconds * rate) + 1
    ticks = []                        # (first event, events, start, done)
    taken = 0
    t0 = clock()
    while taken < due_total:
        now = clock() - t0
        n = min(math.floor(now * rate) + 1, due_total) - taken
        if n <= 0:
            time.sleep(max(taken / rate - now, 0.0))
            continue
        n = min(n, cap)
        with span(system.span):
            system.step(n)
        ticks.append((taken, n, now, clock() - t0))
        taken += n
    lags = np.concatenate([done - (q + np.arange(n)) / rate
                           for q, n, _, done in ticks])
    started = sum(n for _, n, start, _ in ticks if start <= seconds)
    return dict(loop="open", events=taken, seconds=ticks[-1][3],
                step_s=[done - start for _, _, start, done in ticks],
                lags_s=lags, backlog_end=due_total - started)


LOOPS = {"closed": closed, "open": open_}
