"""The plain reference of the served behaviour LM: a decoder-only
transformer written out in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``, with no cache, no batching of
requests, no paging and no kernels. It imports nothing of the program.

The architecture is the one ``src/repro/configs/paper.py`` names
(``family="dense"``): token embedding; per layer RMSNorm, multi-head
self-attention with rotary positions (half-split rotation, theta
``rope_theta``) and a causal mask, residual, RMSNorm, SwiGLU MLP
(``silu(x W_gate) * (x W_up) W_down``), residual; a final RMSNorm; logits
against the tied embedding. Every weight is drawn here, from the seed
(``init``), in the layout the program takes.

Where it departs from the program (``models/transformer.py`` and
``models/layers.py``):

* float32 activations, K/V and matmuls at the highest precision, where
  the program casts weights to bfloat16 at use, keeps bfloat16
  activations and a bfloat16 K/V cache, and reduces softmax and norms in
  float32;
* one causal pass over the whole prompt and the served tokens
  (teacher forcing), where the program prefills a bucket, with a prefix
  gathered from the block pool, and then decodes one token a step through
  its paged cache;
* logits at every position, where the program takes them only where it
  samples.

``quantize`` gives the control: every matmul operand (activations,
weights, queries, keys, attention weights, values) rounded through
float8 e4m3 with one scale per tensor that maps its largest magnitude to
the format's largest value, then multiplied in float32: the reference
computed in the precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def key_of(seed: int):
    """A PRNG key from the whole of ``seed`` (above 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def init(key, m: dict) -> dict:
    """Random weights in float32: fan-in truncated normals for the
    projections, N(0, 0.02) for the embedding, norm scales U(0.75, 1.25).
    The pytree is the program's (``transformer.init_params``): layers
    stacked on a leading axis."""
    L, D, H, hd, F, V = (m["num_layers"], m["d_model"], m["num_heads"],
                         m["head_dim"], m["d_ff"], m["vocab_size"])
    KV = m["num_kv_heads"]
    ks = iter(jax.random.split(key, 11))

    def dense(shape, fan_in):
        return fan_in ** -0.5 * jax.random.truncated_normal(
            next(ks), -3, 3, (L,) + shape, jnp.float32)

    def scale(shape):
        return jax.random.uniform(next(ks), shape, jnp.float32, 0.75, 1.25)

    return dict(
        embed=0.02 * jax.random.normal(next(ks), (V, D), jnp.float32),
        blocks=dict(
            ln1=dict(scale=scale((L, D))),
            attn=dict(wq=dense((D, H, hd), D), wk=dense((D, KV, hd), D),
                      wv=dense((D, KV, hd), D), wo=dense((H, hd, D), H * hd)),
            ln2=dict(scale=scale((L, D))),
            mlp=dict(w_gate=dense((D, F), D), w_up=dense((D, F), D),
                     w_down=dense((F, D), F))),
        ln_f=dict(scale=scale((D,))))


def _q(x, quantize: bool):
    if not quantize:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, hd) at positions 0..S-1."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, tokens, m: dict, quantize: bool = False):
    """(B, S, V) float32 logits of every position of ``tokens`` (B, S)."""
    q = functools.partial(_q, quantize=quantize)
    eps, theta = m["norm_eps"], m["rope_theta"]
    H, KV = m["num_heads"], m["num_kv_heads"]
    S = tokens.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]

        def layer(x, p):
            h = _rms(x, p["ln1"]["scale"], eps)
            a = p["attn"]
            qh = _rope(jnp.einsum("bsd,dhk->bshk", q(h), q(a["wq"])), theta)
            kh = _rope(jnp.einsum("bsd,dhk->bshk", q(h), q(a["wk"])), theta)
            vh = jnp.einsum("bsd,dhk->bshk", q(h), q(a["wv"]))
            kh = jnp.repeat(kh, H // KV, axis=2)
            vh = jnp.repeat(vh, H // KV, axis=2)
            s = jnp.einsum("bqhk,bshk->bhqs", q(qh), q(kh))
            s = jnp.where(causal, s * qh.shape[-1] ** -0.5, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqs,bshk->bqhk", q(w), q(vh))
            x = x + jnp.einsum("bqhk,hkd->bqd", q(o), q(a["wo"]))
            h = _rms(x, p["ln2"]["scale"], eps)
            f = p["mlp"]
            g = jax.nn.silu(jnp.einsum("bsd,df->bsf", q(h), q(f["w_gate"])))
            u = jnp.einsum("bsd,df->bsf", q(h), q(f["w_up"]))
            x = x + jnp.einsum("bsf,fd->bsd", q(g * u), q(f["w_down"]))
            return x, None

        x, _ = jax.lax.scan(layer, x, params["blocks"])
        x = _rms(x, params["ln_f"]["scale"], eps)
        return jnp.einsum("bsd,vd->bsv", q(x), q(params["embed"]))


@functools.partial(jax.jit, static_argnames=("m", "quantize"))
def _block_shortfall(params, tokens, target, m, quantize):
    """Per row, the widest gap between the reference's best logit and its
    logit of the scored token, over the positions ``target`` scores
    (>= 0). The scored token is ``target`` itself, or with ``quantize``
    the token the float8 reference puts first."""
    m = dict(m)
    ref = logits(params, tokens, m)
    best = ref.max(-1)
    pick = (jnp.argmax(logits(params, tokens, m, quantize=True), -1)
            if quantize else jnp.maximum(target, 0))
    got = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return jnp.where(target >= 0, best - got, -jnp.inf).max(-1)


def shortfall(params, sequences, m: dict, *, length: int, rows: int,
              quantize: bool = False) -> np.ndarray:
    """For each ``(prompt, served)`` pair the widest gap, over the served
    tokens, between the reference's best logit and its logit of the
    served token (teacher-forced on the prompt and the served tokens
    before it). With ``quantize`` the served token is replaced, at each
    of the same positions, by the float8 reference's first choice.
    Sequences are padded to ``length`` and run ``rows`` at a time, so one
    program serves every block."""
    frozen = tuple(sorted(m.items()))
    out = []
    for a in range(0, len(sequences), rows):
        block = sequences[a:a + rows]
        tokens = np.zeros((rows, length), np.int32)
        target = np.full((rows, length), -1, np.int32)
        for r, (prompt, served) in enumerate(block):
            n, k = len(prompt), len(served)
            if n + k - 1 > length or k == 0:
                raise ValueError(f"a sequence of {n} + {k} tokens does "
                                 f"not fit {length} positions")
            tokens[r, :n] = prompt
            tokens[r, n:n + k - 1] = served[:-1]
            target[r, n - 1:n + k - 1] = served
        got = _block_shortfall(params, jnp.asarray(tokens),
                               jnp.asarray(target), frozen, quantize)
        out.append(np.asarray(got)[:len(block)])
    return np.concatenate(out) if out else np.zeros(0, np.float32)
