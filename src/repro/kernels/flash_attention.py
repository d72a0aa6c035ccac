"""Pallas TPU flash attention (forward) with GQA, sliding window, softcap.

Covers the host-side attention hot-spot of the split-brain design and the
prefill path of every assigned transformer arch (gemma2's logit softcap and
local/global alternation included).

Grid: (B, Hq, Tq/bq, Tk/bk) with the KV dimension innermost ("arbitrary"
semantics); online-softmax running max/denominator/accumulator live in VMEM
scratch and are revisited across KV steps.  GQA is expressed in the K/V
BlockSpec index maps (q head h reads kv head h // group) — no repeat/copy of
KV in HBM.  Fully-masked causal blocks are skipped with ``pl.when``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ, DEFAULT_BK = 512, 512

def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], n_kv: int,
            bq: int, bk: int, tk_valid: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos0 = qi * bq + off_ref[b]
    block_needed = True
    if causal:
        block_needed = ki * bk <= qpos0 + bq - 1
    if window is not None:
        block_needed = jnp.logical_and(
            block_needed, (ki + 1) * bk - 1 > qpos0 - window)

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        qpos = qpos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < tk_valid
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale",
                     "bq", "bk", "interpret"),
)
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None, kv_offset=0,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B,Hq,Tq,D); k,v (B,Hkv,Tk,D); Hq % Hkv == 0.

    Query row ``i`` of batch row ``b`` sits at key position
    ``i + kv_offset[b]``.  ``kv_offset`` (scalar or (B,), may be traced)
    rides in as a scalar-prefetch operand, so one compiled program serves
    every offset — the chunked-prefill case, where a chunk at a traced
    start attends causally over the whole linear cache.  ``interpret=True``
    runs the Pallas interpreter (CPU validation only)."""
    B, Hq, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    group = Hq // Hkv
    s = scale if scale is not None else D ** -0.5
    bq_, bk_ = min(bq, Tq), min(bk, Tk)
    pad_q, pad_k = (-Tq) % bq_, (-Tk) % bk_
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq, nk = q.shape[2] // bq_, k.shape[2] // bk_

    offsets = jnp.broadcast_to(jnp.asarray(kv_offset, jnp.int32), (B,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                    # per-row kv_offset
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq_, D), lambda b, h, i, j, _: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk_, D),
                         lambda b, h, i, j, _: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk_, D),
                         lambda b, h, i, j, _: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, D),
                               lambda b, h, i, j, _: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=s, causal=causal, window=window, softcap=softcap,
            n_kv=nk, bq=bq_, bk=bk_, tk_valid=Tk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_prefill",
    )(offsets, q, k, v)
    return out[:, :, :Tq]
