"""Plain float32 forward of a dense GQA decoder (optionally with qk-norm),
written from the published description.

It shares no code with ``src/``. The weights are regenerated from the seed
with the same recipe the served model is initialised by (normal / sqrt(fan
in), stored in bfloat16), so the reference and the program hold the same
model without passing arrays between them.

``quant="fp8"`` computes the control: every weight matmul runs on weights
and activations rounded to fp8 e4m3 (three mantissa bits, scaled so the
largest magnitude of each row of activations and each output column of
weights is 448), with float32 accumulation. That is the lower-precision
path a later change to a bfloat16 model could be tempted by.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512          # query rows per attention block
SEQ_PAD = 1024         # sequences are padded to a multiple of this


@dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    qk_norm: bool
    eps: float
    theta: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if c.get("num_local_experts"):
            raise ValueError("the reference has no mixture-of-experts layer")
        return cls(d=c["hidden_size"], layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tied=bool(c["tie_word_embeddings"]),
                   qk_norm=bool(c["reference"]["qk_norm"]),
                   eps=float(c["rms_norm_eps"]),
                   theta=float(c["rope_theta"]))


def _normal_bf16(key, shape, fan_in):
    return jax.jit(lambda k: (jax.random.normal(k, shape, jnp.float32)
                              * (1.0 / math.sqrt(fan_in))).astype(
                                  jnp.bfloat16))(key)


def make_weights(m: Dims, key) -> dict:
    """The model's weights from ``key``: key splits, shapes and scales of
    the served model's initialisation, one jitted call per tensor."""
    ks = jax.random.split(key, 8)
    L, d, H, K, hd = m.layers, m.d, m.heads, m.kv_heads, m.head_dim
    ke = jax.random.split(ks[0], 2)
    w = {"embed": _normal_bf16(ke[0], (m.vocab, d), d)}
    if not m.tied:
        w["lm_head"] = _normal_bf16(ke[1], (d, m.vocab), d)
    ka = jax.random.split(ks[1], 6)
    w["wq"] = _normal_bf16(ka[0], (L, d, H * hd), d)
    w["wk"] = _normal_bf16(ka[1], (L, d, K * hd), d)
    w["wv"] = _normal_bf16(ka[2], (L, d, K * hd), d)
    w["wo"] = _normal_bf16(ka[3], (L, H * hd, d), H * hd)
    km = jax.random.split(ks[2], 3)
    w["w_gate"] = _normal_bf16(km[0], (L, d, m.ff), d)
    w["w_up"] = _normal_bf16(km[1], (L, d, m.ff), d)
    w["w_down"] = _normal_bf16(km[2], (L, m.ff, d), m.ff)
    # every norm scale of the served model starts at one
    return w


# ------------------------------------------------------------------ pieces
def _quant(x, axis, quant):
    """``x`` rounded to fp8 e4m3 with one scale per slice along ``axis``
    (the absmax of the slice maps to 448), returned in float32."""
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = amax / 448.0
    m, e = jnp.frexp(x / s)                  # m in [0.5, 1)
    y = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    # e4m3 subnormals: below 2**-6 the spacing stays 2**-9
    y = jnp.where(jnp.abs(x / s) < 2.0 ** -6,
                  jnp.round(x / s * 2.0 ** 9) / 2.0 ** 9, y)
    return y * s


def _mm(x, wt, quant: Optional[str]):
    """x [n, a] @ w [a, b] in float32; the control rounds both sides to
    ``quant`` first (activations per row, weights per output column)."""
    w = wt.astype(jnp.float32)
    if quant is not None:
        x = _quant(x, -1, quant)
        w = _quant(w, 0, quant)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """Rotate-half RoPE. x [S, h, hd], pos [S]."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, group):
    """Causal attention, one sequence: q [S, H, hd], k/v [S, K, hd], one
    block of ``Q_BLOCK`` query rows at a time (S is a multiple of it).
    Head h reads kv head h // group."""
    S, H, hd = q.shape
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, kk,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        s = jnp.where(qpos >= jnp.arange(S)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vv,
                          precision=jax.lax.Precision.HIGHEST)

    return jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _attn_block(m: Dims, x, wq, wk, wv, wo, quant):
    S = x.shape[0]
    H, K, hd = m.heads, m.kv_heads, m.head_dim
    pos = jnp.arange(S)
    h = _rms(x, m.eps)
    q = _mm(h, wq, quant).reshape(S, H, hd)
    k = _mm(h, wk, quant).reshape(S, K, hd)
    v = _mm(h, wv, quant).reshape(S, K, hd)
    if m.qk_norm:
        q, k = _rms(q, m.eps), _rms(k, m.eps)
    q, k = _rope(q, pos, m.theta), _rope(k, pos, m.theta)
    o = _attention(q, k, v, H // K).reshape(S, H * hd)
    return x + _mm(o, wo, quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _ffn(x, g, u, dn, quant):
    return _mm(jax.nn.silu(_mm(x, g, quant)) * _mm(x, u, quant), dn, quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(m: Dims, x, head, quant):
    return _mm(_rms(x, m.eps), head, quant)


def logits_at(m: Dims, w: dict, tokens: np.ndarray, at: np.ndarray,
              quant: Optional[str] = None) -> np.ndarray:
    """Float32 logits [len(at), vocab] of one sequence ``tokens`` at the
    positions ``at``, computed layer by layer. The sequence is right-padded
    to a multiple of ``SEQ_PAD``; causal attention keeps the padding out of
    every real position."""
    S = len(tokens)
    n = -(-S // SEQ_PAD) * SEQ_PAD
    toks = np.zeros(n, np.int32)
    toks[:S] = tokens
    x = w["embed"][jnp.asarray(toks)].astype(jnp.float32)
    for l in range(m.layers):
        x = _attn_block(m, x, w["wq"][l], w["wk"][l], w["wv"][l],
                        w["wo"][l], quant)
        x = x + _ffn(_rms(x, m.eps), w["w_gate"][l], w["w_up"][l],
                     w["w_down"][l], quant)
    head = w["embed"].T if m.tied else w["lm_head"]
    return np.asarray(_head(m, x[jnp.asarray(at)], head, quant))
