"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships as kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd public wrapper with impl dispatch), ref.py (pure-jnp oracle).
Kernels are validated against their oracles in interpret mode on CPU
(tests/test_kernels.py), compiled for a described TPU v5e at real widths
(tests/test_tpu_compile.py), and run against their oracles on the chip by
``chip_smoke.py``'s kernel phase. ``impl="pallas"`` never falls back to the
oracle: a case the kernel cannot take raises.
"""
from .flash_attention.ops import flash_attention
from .funnel_match.ops import deepest_stage, reach_counts
from .event_count.ops import histogram as event_histogram, count_codes

__all__ = ["flash_attention", "deepest_stage", "reach_counts",
           "event_histogram", "count_codes"]
