"""Compile the Pallas kernels for a described TPU v5e, at the widths the
chip runs them, with no chip attached.

Interpret mode on the CPU validates what the kernels compute; only the
TPU compiler shows what it refuses (tilings, layouts, unsupported
lowerings). Each test lowers one kernel for one v5e chip and checks that
the compiled program holds the Mosaic kernel (``tpu_custom_call``). The
widths are ``behavior-lm-100m``'s (12 heads of 64, bf16) and the log
tier's (alphabet 1024, sessions of 256 events).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test runner's workers each
import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.event_count.kernel import histogram_pallas
from repro.kernels.flash_attention.kernel import (flash_attention_fwd,
                                                  paged_decode_attention_fwd)
from repro.kernels.funnel_match.kernel import deepest_stage_pallas

HEADS, HEAD_DIM = 12, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    L = 512
    qkv = ((1, HEADS, L, HEAD_DIM), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
        qkv, qkv, qkv, sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block_size,max_blocks", [(16, 16), (4, 64)])
def test_paged_decode_attention_compiles_for_v5e(one_chip, block_size,
                                                 max_blocks):
    B, n_pool = 16, 16 * max_blocks + 1
    pool = ((n_pool, HEADS, block_size, HEAD_DIM), jnp.bfloat16)
    text = _compiled_text(
        lambda q, kp, vp, tbl, kvl: paged_decode_attention_fwd(
            q, kp, vp, tbl, kvl),
        ((B, HEADS, 1, HEAD_DIM), jnp.bfloat16), pool, pool,
        ((B, max_blocks), jnp.int32), ((B,), jnp.int32), sharding=one_chip)
    assert "tpu_custom_call" in text


def test_histogram_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda s: histogram_pallas(s, alphabet_size=1024),
        ((1 << 20,), jnp.int32), sharding=one_chip)
    assert "tpu_custom_call" in text


def test_deepest_stage_compiles_for_v5e(one_chip):
    text = _compiled_text(deepest_stage_pallas, ((4096, 256), jnp.int32),
                          sharding=one_chip)
    assert "tpu_custom_call" in text
