"""Shared helpers of the benchmark's CPU tests: an in-process rehearsal
run of one cell (tiny sizes, the CPU, no persistent compile cache)."""
from __future__ import annotations

import time

import pytest


@pytest.fixture
def rehearse():
    from bench import run

    def go(workload, *extra, seconds=1.5, seed=2 ** 31 + 11):
        args = run.parse_args(["--workload", workload, "--seed", str(seed),
                               "--seconds", str(seconds), "--trace", "0",
                               "--rehearse", *extra])
        return run.run(args, time.perf_counter())
    return go
