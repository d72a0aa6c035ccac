"""Pallas TPU paged decode attention: flash-decode over the page pool.

The gather-free counterpart of ``serve/pages.py::gather_view`` +
``ref.decode_attention``: one decode query per slot attends to its KV
directly THROUGH the page table, so the ``(B, max_len, ...)`` dense-view
transient of the reference paged decode step never exists and steady-state
HBM reads drop from O(max_len) to O(live tokens) per slot.

Grid: ``(B, P)`` — slots × page-blocks, with the page axis innermost
("arbitrary" semantics) as the split-K axis of a flash-decode online
softmax: running max / denominator / accumulator live in VMEM scratch and
are revisited across page steps.  The page table and the per-slot lengths
ride in as **scalar-prefetch** operands (``pltpu.PrefetchScalarGridSpec``),
so the K/V BlockSpec index maps resolve ``table[b, p]`` BEFORE the kernel
body runs and the DMA engine fetches exactly one physical page per grid
step.  The kernel reads the WHOLE stacked pool ``(L, N, ps, Hkv/f, f·D)``
as it is stored — the decode step never slices a layer out of it, which on
the TPU is a copy of that layer's pool (DESIGN.md §6): the pool's leading
axes merge into one run of ``L·N`` pages (a bitcast of the row-major pool)
and the layer becomes an offset of the page ids, ``layer·N + table[b, p]``.
The block is the whole page, every kv head at once:
``(1, ps, Hkv/f, f·D)``, whose last two dimensions equal the array's — the
only page block of this layout the TPU compiler accepts (a per-head block
is refused).

The stored layout is LANE-DENSE (``layers.kv_fold``): when ``D < 128``
divides 128 and the kv heads divide into groups of ``f = 128 // D``, ``f``
adjacent heads share one 128-lane row, head ``r·f + i`` in lanes
``[i·D, (i+1)·D)`` of row ``r`` (``f = 1`` is the plain ``(Hkv, D)``
layout).  Per-head quantities (scores, running max and denominator) are
kept lane-expanded — each head's value repeated over its ``D`` lanes — so
the probability-times-value product stays elementwise on full vregs; the
per-head score reduction is a masked lane sum per lane group.  Scores are
lane reductions over ``D`` (decode is bandwidth-bound: one query row per
kv head leaves the MXU nothing to fill), and GQA groups loop statically
inside the body, so every page is read once per slot, never per q head.

Pages past a slot's ``cache_len`` (and pages wholly below its sliding
window) skip their compute with ``pl.when``.  For the dead TAIL the
allocator's table entries additionally point at the scratch page, so even
the prefetch touches only a single hot page; wholly-below-window pages
are real allocated pages, so their grid steps still fetch one page each
(compute-free — in the serving stack this case never arises, since
window-capped cache leaves stay dense ring buffers and never page).
Sliding-window and softcap semantics match ``flash_attention.py`` /
``ref.decode_attention`` exactly.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _lane_groups(fold: int, head_dim: int):
    """(1, 1, f·D) int32: the head slot ``i`` of each lane of a folded row."""
    return jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, fold * head_dim), 2) // head_dim


def _head_sum(x, fold: int, lanes):
    """Per-head sums over the ``D`` lanes of each head in ``x (…, R, f·D)``.
    Unfolded (``f = 1``): ``(…, R, 1)``.  Folded: lane-expanded
    ``(…, R, f·D)``, each head's sum repeated over its own lanes."""
    if fold == 1:
        return jnp.sum(x, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for i in range(fold):
        sel = lanes == i
        out = jnp.where(sel, jnp.sum(jnp.where(sel, x, 0.0), axis=-1,
                                     keepdims=True), out)
    return out


def _expand_heads(s, fold: int, lanes):
    """Per-kv-head values ``(R, f)`` -> the layout of ``_head_sum``'s output:
    ``(R, 1)`` unfolded, ``(R, f·D)`` lane-expanded when folded."""
    if fold == 1:
        return s
    out = jnp.zeros(s.shape[:-1] + (lanes.shape[-1],), s.dtype)
    for i in range(fold):
        out = jnp.where(lanes[0] == i, s[:, i:i + 1], out)
    return out


def _kernel(*refs, scale: float, window: Optional[int],
            softcap: Optional[float], ps: int, n_pages: int, group: int,
            fold: int, head_dim: int, quant: bool = False,
            with_lse: bool = False):
    _, len_ref, q_ref, k_ref, v_ref, *rest = refs
    if quant:
        # per-page, per-kv-head dequant scales ride as (1, Hkv/f, f) blocks
        # fetched through the same table index as the page (DESIGN.md §13)
        ks_ref, vs_ref, *rest = rest
    o_ref, *rest = rest
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        (m_ref, l_ref, acc_ref), lse_ref = rest, None
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    start = p * ps
    # a page is needed iff it overlaps [max(0, length - window), length);
    # the overlap is never empty, so a computed block always has >= 1 valid
    # position (no all-masked softmax corner)
    needed = start < length
    if window is not None:
        needed = jnp.logical_and(needed, start + ps > length - window)

    @pl.when(needed)
    def _compute():
        lanes = _lane_groups(fold, head_dim) if fold > 1 else None
        k = k_ref[0].astype(jnp.float32)              # (ps, R, f·D)
        v = v_ref[0].astype(jnp.float32)              # (ps, R, f·D)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (ps, 1, 1), 0)
        mask = pos < length
        if window is not None:
            mask &= pos > length - 1 - window
        if quant:
            ks = _expand_heads(ks_ref[0], fold, lanes)   # (R, W)
            vs = _expand_heads(vs_ref[0], fold, lanes)
        for g in range(group):
            q = q_ref[0, g].astype(jnp.float32)       # (R, f·D)
            logits = _head_sum(k * q[None], fold, lanes) * scale
            if quant:
                # fused dequant: scores of int8/fp8 codes times this
                # page's per-kv-head scale == scores of dequantized keys
                logits = logits * ks[None]
            if softcap is not None:
                logits = softcap * jnp.tanh(logits / softcap)
            logits = jnp.where(mask, logits, NEG_INF)  # (ps, R, W)

            m_prev, l_prev = m_ref[g], l_ref[g]        # (R, W)
            m_new = jnp.maximum(m_prev, logits.max(axis=0))
            pexp = jnp.exp(logits - m_new[None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[g] = l_prev * corr + pexp.sum(axis=0)
            m_ref[g] = m_new
            pv = jnp.sum(pexp * v, axis=0)             # (R, f·D)
            if quant:
                pv = pv * vs
            acc_ref[g] = acc_ref[g] * corr + pv

    @pl.when(p == n_pages - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)             # (group, R, W)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp of this invocation's logits, for cross-shard
            # combination (a slot with no local pages reports ~ -inf and
            # drops out of the merge)
            lse_ref[0] = m_ref[...] + jnp.log(l)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "scale", "interpret", "return_lse"),
)
def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, page_table: jnp.ndarray,
                           cache_len: jnp.ndarray, *,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None,
                           layer,
                           interpret: bool = False,
                           return_lse: bool = False):
    """q (B, Hq, 1, D); pools: the whole stacked pool
    ``(L, num_pages, page_size, Hkv/f, f·D)`` in its stored layout (``f``
    from ``layers.kv_fold``; ``f = 1`` is the plain ``(.., Hkv, D)``
    layout), of which layer ``layer`` (an int32 scalar, traced or not) is
    read in place; page_table (B, P) int32 physical page ids of that layer
    (the dead tail points at its scratch page 0); cache_len
    (B,) valid lengths.  Hq % Hkv == 0.  Token position t of slot b lives
    at ``(page_table[b, t // page_size], t % page_size)``.  The fold is
    read off the shapes: ``f = (f·D) / D``.

    ``k_scale``/``v_scale`` (L, num_pages, Hkv) f32 dequantize QUANTIZED
    pools (int8/fp8 codes) at page-fetch time: each page's scales are fetched through the
    same table index as the page itself and multiply its scores and value
    sum inside the online softmax — the split-K grid is unchanged and the
    pages stream at 1 byte per element (DESIGN.md §13).

    ``return_lse=True`` additionally returns the per-head log-sum-exp
    (B, Hkv, group) f32 of the computed logits, so partial results over a
    SPLIT page axis can be exactly combined across TP shards
    (``distributed.collectives.tp_paged_decode_attention_merge``).

    ``interpret=True`` runs the Pallas interpreter (CPU validation only).
    """
    B, Hq, _, D = q.shape
    L, N, ps, R, FD = k_pool.shape
    fold = FD // D
    Hkv = R * fold
    P = page_table.shape[1]
    group = Hq // Hkv
    W = 1 if fold == 1 else FD             # width of the per-head stats
    s = scale if scale is not None else D ** -0.5
    quant = k_scale is not None
    # GQA layout (B, group, Hkv/f, f·D): the group dim rides inside the
    # q/out block, so each KV page is fetched once per slot (not once per q
    # head), and q's heads fold into lanes exactly as the pool's do
    qg = (q[:, :, 0, :].reshape(B, Hkv, group, D).transpose(0, 2, 1, 3)
          .reshape(B, group, R, FD))
    # the stacked pool as one run of L·N pages (its leading axes merged: a
    # bitcast of the row-major pool), and the layer as an offset of the
    # page ids, so each grid step fetches page ``layer·N + table[b, p]``
    flat = lambda a: a.reshape((L * N,) + a.shape[2:])
    table = page_table.astype(jnp.int32) + jnp.asarray(layer, jnp.int32) * N

    # index maps take the two scalar-prefetch refs as trailing args
    def per_slot(*block):
        return pl.BlockSpec(block, lambda b, p, *_: (b,) + (0,) * (len(block) - 1))

    def per_page(*block):
        return pl.BlockSpec(
            block, lambda b, p, tbl, _: (tbl[b, p],) + (0,) * (len(block) - 1))

    in_specs = [per_slot(1, group, R, FD), per_page(1, ps, R, FD),
                per_page(1, ps, R, FD)]
    operands = [qg, flat(k_pool), flat(v_pool)]
    if quant:
        # per-page scales, heads split as the page's rows are
        in_specs += [per_page(1, R, fold), per_page(1, R, fold)]
        operands += [flat(sc).astype(jnp.float32).reshape(L * N, R, fold)
                     for sc in (k_scale, v_scale)]
    out_specs = per_slot(1, group, R, FD)
    out_shape = jax.ShapeDtypeStruct((B, group, R, FD), q.dtype)
    if return_lse:
        out_specs = [out_specs, per_slot(1, group, R, W)]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, group, R, W), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # page_table, cache_len
        grid=(B, P),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((group, R, W), jnp.float32),
            pltpu.VMEM((group, R, W), jnp.float32),
            pltpu.VMEM((group, R, FD), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=s, window=window, softcap=softcap, ps=ps,
            n_pages=P, group=group, fold=fold, head_dim=D, quant=quant,
            with_lse=return_lse),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_flash_decode",
    )(table, jnp.asarray(cache_len, jnp.int32), *operands)
    # (B, group, Hkv, D) -> (B, Hq, 1, D), head h·group + g
    to_heads = lambda a: (a.reshape(B, group, Hkv, -1).transpose(0, 2, 1, 3)
                          .reshape(B, Hq, 1, -1))
    if return_lse:
        out, lse = out
        lse = lse[..., ::D] if fold > 1 else lse       # one lane per head
        return to_heads(out), to_heads(lse).reshape(B, Hkv, group)
    return to_heads(out)
