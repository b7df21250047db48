"""The model operations a served token needs, from the configuration's
shapes alone: a yardstick independent of how the program computes them
(``serve_mfu`` divides it by the peak).

A token at position ``p`` (counting from 0) passes every layer once: the
query, key, value and output projections (``2 D hd (2 H + 2 KV)``
operations: a multiply and an add per weight), the gated MLP's three
matrices (``6 D F``), and attention over its ``p + 1`` positions of
context (``4 H hd (p + 1)``: the scores and the weighted sum of values);
then the tied head (``2 D V``). Every prompt token and every emitted
token counts once, at its own position: prompt positions served from a
cached prefix are not subtracted, and norms, rotary phases and softmax
are left out.
"""
from __future__ import annotations

import numpy as np


def per_token(m: dict) -> int:
    """Operations of one token, all but attention over its context."""
    D, hd, H, KV, F = (m["d_model"], m["head_dim"], m["num_heads"],
                       m["num_kv_heads"], m["d_ff"])
    layer = 2 * D * hd * (2 * H + 2 * KV) + 6 * D * F
    return m["num_layers"] * layer + 2 * D * m["vocab_size"]


def per_context(m: dict) -> int:
    """Operations of attention per position of context, over the layers."""
    return m["num_layers"] * 4 * m["num_heads"] * m["head_dim"]


def positions(m: dict, first, last) -> float:
    """Operations of the tokens at positions ``first .. last - 1`` of one
    sequence each (arrays, one entry per sequence)."""
    first = np.asarray(first, np.float64)
    last = np.asarray(last, np.float64)
    n = np.maximum(last - first, 0)
    # the sum of (p + 1) over first <= p < last
    context = n * (first + last + 1) / 2
    return float(per_token(m) * n.sum() + per_context(m) * context.sum())
