"""Plain float32 reference of the ``lm`` family, for the check of what the
timed path served.

A decoder-only transformer as the program runs it: token embedding with no
scale; per layer a pre-norm block of RMSNorm (gain applied as ``1 + g``),
multi-head attention with grouped key/value heads (query head ``h`` reads
key/value head ``h // group``), rotary embedding over the whole head in the
split-half form (``theta ** (-i / (D/2))``), causal softmax scaled by
``1/sqrt(D)``, an output projection and a residual add; then RMSNorm, a
gated FFN ``W2 (silu(W1 x) * W3 x)`` and a residual add; a final RMSNorm and
an untied output head.  No bias anywhere.

Straightforward ``jax.numpy`` in float32 with every product at ``HIGHEST``
precision: no kernel, no cache, no batching, no paging.  It reads the
bench's own bf16 weights, as dicts keyed like the program's parameters, and
imports nothing of the program.  Layers run one by one in a scan, each
upcast to float32 inside it, and attention runs in blocks of query rows, so
the largest configuration fits beside its weights.

``fp8=True`` computes the same function in fp8 (e4m3): every product's
operands are rounded to e4m3 under a per-tensor scale for weights and a
per-row scale for activations, and accumulate in float32 — the precision
below bf16 that a later change could be tempted to serve in.  It is the
control that the check has to refuse.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERY_BLOCK = 512
E4M3_MAX = 448.0


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, fp8: bool):
    w = w.astype(F32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, g, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(F32))


def _rope(x, pos, theta: float):
    """x (T, H, D); split-half rotation by absolute position."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None, None].astype(F32) * freqs[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, fp8: bool):
    """Causal attention, q (T, H, D), k/v (T, Hkv, D)."""
    T, H, D = q.shape
    group = H // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    qb = min(QUERY_BLOCK, T)
    kpos = jnp.arange(T)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / math.sqrt(D)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _fp8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    return jax.lax.map(block, jnp.arange(T // qb)).reshape(T, H, D)


def logits_at(params: Dict[str, Any], tokens, read, a: Dict[str, Any],
              fp8: bool = False):
    """Logits (len(read), V) in float32 at positions ``read`` of one
    sequence ``tokens`` (T,), T a multiple of 512 or below it.

    ``a``: num_hidden_layers, num_attention_heads, num_key_value_heads,
    head_dim, rope_theta, norm_eps."""
    T = tokens.shape[0]
    H, Hkv, D = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    eps, theta = a["norm_eps"], float(a["rope_theta"])
    emb = params["embed"]
    x = emb[tokens].astype(F32)
    if fp8:
        x = _fp8(x, None)
    pos = jnp.arange(T)
    blocks = jax.tree.map(lambda b: b.reshape((-1,) + b.shape[2:]),
                          params["blocks"])

    def layer(x, p):
        at, ml = p["attn"], p["mlp"]
        h = _rms(x, p["ln_attn"], eps)
        q = _rope(_mm(h, at["wq"], fp8).reshape(T, H, D), pos, theta)
        k = _rope(_mm(h, at["wk"], fp8).reshape(T, Hkv, D), pos, theta)
        v = _mm(h, at["wv"], fp8).reshape(T, Hkv, D)
        o = _attention(q, k, v, fp8)
        x = x + _mm(o.reshape(T, H * D), at["wo"], fp8)
        y = _rms(x, p["ln_mlp"], eps)
        f = jax.nn.silu(_mm(y, ml["w1"], fp8)) * _mm(y, ml["w3"], fp8)
        return x + _mm(f, ml["w2"], fp8), None

    x, _ = jax.lax.scan(layer, x, blocks)
    h = _rms(x[read], params["ln_final"], eps)
    return _mm(h, params["lm_head"], fp8)
