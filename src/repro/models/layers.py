"""Shared pure-JAX building blocks: norms, rope, linear (raw or LAQ-quantized),
embeddings, GQA attention.  No flax — params are plain pytrees of arrays.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels import ops

Init = jax.nn.initializers


def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return jax.random.uniform(key, (in_dim, out_dim), dtype, -scale, scale)


def linear(x: jnp.ndarray, w, kernels: str) -> jnp.ndarray:
    """Apply a linear map; ``w`` is a raw (in,out) array or a QuantizedLinear.

    The quantized branch is the ITA device datapath: INT8 activations times
    hardwired INT4 codes (see core/quant.py, kernels/w4a8_matmul.py).
    ``kernels`` (kernels/ops.py) selects the W4A8 kernel or its oracle for
    the quantized branch.
    """
    if isinstance(w, quant.QuantizedLinear):
        shape = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        qx, xs = quant.quantize_activations_int8(x2)
        y = ops.w4a8_matmul(qx, xs, w.codes, w.scales, out_dtype=x.dtype,
                            kernels=kernels)
        return y.reshape(*shape, w.codes.shape[-1])
    return x @ w.astype(x.dtype)


def rmsnorm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0) -> jnp.ndarray:
    """x: (B, H, T, D) with even D; positions: (T,) or (B, T)."""
    D = x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]   # (T, half)
        ang = ang[None, None]
    else:
        ang = positions[:, None, :, None].astype(jnp.float32) * freqs[None, None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def swiglu(x: jnp.ndarray, w1, w3, w2,
           kernels: str, pin_fn=None) -> jnp.ndarray:
    """FFN(x) = W2 . (silu(W1 x) * (W3 x)) — eq. (4)/(5) of the paper.

    ``pin_fn`` (serve TP exactness, DESIGN.md §11) is applied to the hidden
    activation before the W2 contraction — sharding.pin_tp_exact gathers a
    d_ff-sharded hidden so the down-projection is never split."""
    h = jax.nn.silu(linear(x, w1, kernels)) * linear(x, w3, kernels)
    if pin_fn is not None:
        h = pin_fn(h)
    return linear(h, w2, kernels)


# ----------------------------------------------------------------------------
# GQA attention block
# ----------------------------------------------------------------------------
def attn_init(key, d_model: int, num_heads: int, num_kv_heads: int,
              head_dim: int, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], num_heads * head_dim, d_model, dtype),
    }


def qkv_project(p: dict, x: jnp.ndarray, num_heads: int, num_kv_heads: int,
                head_dim: int, kernels: str):
    """The ITA device phase of attention: static linear maps only."""
    B, T, _ = x.shape
    q = linear(x, p["wq"], kernels).reshape(B, T, num_heads, head_dim).transpose(0, 2, 1, 3)
    k = linear(x, p["wk"], kernels).reshape(B, T, num_kv_heads, head_dim).transpose(0, 2, 1, 3)
    v = linear(x, p["wv"], kernels).reshape(B, T, num_kv_heads, head_dim).transpose(0, 2, 1, 3)
    return q, k, v


def attn_apply(p: dict, x: jnp.ndarray, *, num_heads: int, num_kv_heads: int,
               head_dim: int, positions: jnp.ndarray, rope_theta: float,
               window: Optional[int] = None, softcap: Optional[float] = None,
               causal: bool = True, kernels: str,
               kv: Optional[tuple] = None, pin_fn=None,
               model_axis: Optional[str] = None,
               batch_axes: tuple = ()) -> jnp.ndarray:
    """Full attention block (prefill/training path). ``kv`` overrides K/V
    (cross-attention: keys/values from another sequence, no rope).
    ``pin_fn`` gathers the head-sharded attention output before the wo
    contraction (serve TP exactness, DESIGN.md §11); ``model_axis`` and
    ``batch_axes`` route a Pallas kernel per shard on a TP mesh."""
    B, T, _ = x.shape
    q, k, v = qkv_project(p, x, num_heads, num_kv_heads, head_dim, kernels)
    if kv is None:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    else:
        k, v = kv
        causal, window = False, None
    o = ops.attention(q, k, v, causal=causal, window=window, softcap=softcap,
                      kernels=kernels, model_axis=model_axis,
                      batch_axes=batch_axes)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, num_heads * head_dim)
    if pin_fn is not None:
        o = pin_fn(o)
    return linear(o, p["wo"], kernels)


def cache_write(cache: jnp.ndarray, new: jnp.ndarray, pos: jnp.ndarray,
                aligned: bool = True) -> jnp.ndarray:
    """Write one token's K or V into the cache at per-sequence positions.

    cache: (B, Hkv, S, D); new: (B, Hkv, 1, D); pos: (B,).

    ``aligned=True`` (lockstep decode, the dry-run serving shapes): a single
    dynamic_update_slice at the scalar position — SPMD-partitions cleanly
    with the cache sharded on batch and sequence.  The batched-index vmap
    form lowers to ``scatter``, which XLA's partitioner can only handle by
    all-gathering the cache every layer (measured 77 GB/chip/step on
    granite-8b decode_32k — §Perf H2 log).  ``aligned=False`` keeps ragged
    positions via a one-hot masked select (shardable, full-cache traffic).
    """
    if aligned:
        return jax.lax.dynamic_update_slice(
            cache, new, (0, 0, pos[0], 0))
    S = cache.shape[2]
    onehot = (jnp.arange(S)[None, :] == pos[:, None])[:, None, :, None]
    return jnp.where(onehot, new, cache)


# Physical page 0 of every page pool is the reserved scratch page: writes
# for inactive slots are routed there so the jitted step keeps fixed shapes
# (serve/pages.py re-exports this as the allocator's contract).
SCRATCH_PAGE = 0

# re-exported KV page-quantization vocabulary (core/quant.py owns it so the
# kernel dispatcher can see QuantizedLeaf without an import cycle)
QuantizedLeaf = quant.QuantizedLeaf
KV_DTYPES = quant.KV_DTYPES
KV_QMAX = quant.KV_QMAX


def kv_pow2_scale(amax: jnp.ndarray, kv_dtype: str) -> jnp.ndarray:
    """Smallest power-of-two scale s with ``amax/s <= qmax``.

    Power-of-two scales make the page quantizer IDEMPOTENT: requantizing
    already-roundtripped content lands on the same codes (int8: any page
    whose ratio amax/s exceeded qmax/2 before rounding still exceeds it
    after, so the exponent never drops), which is what lets shared prefix
    pages quantize once and the prefix on/off token-identity survive
    quantization (DESIGN.md §13)."""
    qmax = KV_QMAX[kv_dtype]
    amax = jnp.maximum(amax.astype(jnp.float32), 1e-30)
    return jnp.exp2(jnp.ceil(jnp.log2(amax / qmax)))


def kv_quantize(x: jnp.ndarray, scale: jnp.ndarray,
                kv_dtype: str) -> jnp.ndarray:
    """Encode f32 values into page codes under a (broadcastable) scale."""
    y = x.astype(jnp.float32) / scale
    if kv_dtype == "int8":
        return jnp.clip(jnp.round(y), -127, 127).astype(jnp.int8)
    return y.astype(KV_DTYPES[kv_dtype])


def kv_dequantize(codes: jnp.ndarray, scale: jnp.ndarray,
                  out_dtype=jnp.float32) -> jnp.ndarray:
    """codes × scale.  Exact for both formats: |code| · 2^e products carry
    at most 8 significant bits, so even a bfloat16 ``out_dtype`` holds them
    without rounding — dequantized views are bit-stable."""
    return (codes.astype(jnp.float32) * scale).astype(out_dtype)


def page_offsets(table: jnp.ndarray, pos: jnp.ndarray, write: jnp.ndarray,
                 page_size: int):
    """Resolve per-slot write coordinates through the page table: position
    ``pos[b]`` of slot ``b`` lives at ``(table[b, pos // ps], pos % ps)``;
    slots with ``write=False`` are routed to the scratch page so jitted
    programs keep fixed shapes whatever the active set.  The ONE place the
    table-indexing/scratch contract lives — shared by the in-place append
    (``paged_cache_write``) and the gather discipline's writeback
    (``serve/pages.py::scatter_token``)."""
    page = jnp.take_along_axis(table, (pos // page_size)[:, None],
                               axis=1)[:, 0]
    return jnp.where(write, page, SCRATCH_PAGE), pos % page_size


LANES = 128


def kv_fold(num_kv_heads: int, head_dim: int, tp: int = 1) -> int:
    """How many kv heads share one 128-lane row of a page-pool leaf.

    The TPU tiles an array's last dimension over 128 lanes, so a pool
    stored ``(.., Hkv, D)`` with ``D < 128`` pads every row — the compiler
    then picks another axis (the page axis) as the lane dimension, and each
    program that hands a page block to the paged kernel first re-lays the
    whole pool out.  Folding ``f = 128 // D`` adjacent heads into the minor
    dimension, ``(.., Hkv/f, f·D)``, makes the stored row lane-dense, so the
    row-major layout is the one the compiler keeps.  Head ``r·f + i`` lives
    in lanes ``[i·D, (i+1)·D)`` of row ``r``: the fold is a plain row-major
    reshape of the trailing ``(Hkv, D)`` pair (DESIGN.md §5).

    ``f = 1`` (``D >= 128``, a ``D`` that does not divide 128, or an Hkv
    that does not split into groups of ``f``) leaves the plain layout.  So
    does a fold that would take away the head cut of the TP serving mesh
    (DESIGN.md §11): where ``tp`` divides Hkv, the pool's head rows
    ``Hkv/f`` must divide by ``tp`` too, or the pool would replicate."""
    if head_dim < LANES and LANES % head_dim == 0:
        f = LANES // head_dim
        if num_kv_heads % f == 0 and (
                num_kv_heads % tp or (num_kv_heads // f) % tp == 0):
            return f
    return 1


def unfold_heads(x: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """``(.., Hkv/f, f·D)`` -> ``(.., Hkv, D)``: undo ``kv_fold``."""
    return x.reshape(x.shape[:-2] + (num_kv_heads, -1))


def paged_cache_write(pool, new: jnp.ndarray,
                      table: jnp.ndarray, pos: jnp.ndarray,
                      write: jnp.ndarray, layer):
    """Append one token's K or V per slot directly into the page pool.

    pool: the whole stacked ``(L, num_pages, page_size, Hkv/f, f·D)`` pool
    in its stored, possibly lane-folded layout (``kv_fold``), written at
    layer ``layer`` (a traced scalar) in place; a :class:`QuantizedLeaf`
    routes to the quantize-on-write append.  new: (B, Hkv, 1, D); table:
    (B, P) physical page ids; pos: (B,) write positions (== ``len``);
    write: (B,) bool — inactive slots land on the scratch page so the
    program shape never depends on the active set.  O(B x token bytes) pool
    traffic: the in-place counterpart of ``cache_write`` with no dense view
    in sight.
    """
    codes = pool.codes if isinstance(pool, QuantizedLeaf) else pool
    ps = codes.shape[-3]
    page, off = page_offsets(table, pos, write, ps)
    tok = new[:, :, 0, :].reshape((new.shape[0],) + codes.shape[-2:])
    if isinstance(pool, QuantizedLeaf):
        codes, scales = quant_page_append(pool.codes, pool.scales, tok,
                                          layer, page, off, pool.kv_dtype)
        return QuantizedLeaf(codes, scales, pool.kv_dtype, pool.out_dtype)
    return pool.at[layer, page, off].set(tok.astype(pool.dtype))


def quant_page_append(codes: jnp.ndarray, scales: jnp.ndarray,
                      tok: jnp.ndarray, layer, page: jnp.ndarray,
                      off: jnp.ndarray, kv_dtype: str):
    """The quantize-on-write page append core (DESIGN.md §13).

    codes: (L, N, ps, *rest) stacked pool codes in pages-leading layout,
    the trailing pair of ``rest`` possibly lane-folded ``(Hkv/f, f·D)``;
    scales: (L, N, *rest[:-2], Hkv) matching per-page, per-kv-head scales
    (the fold is read off their last axis).  tok: (B, *rest) the new
    tokens in the codes' layout; layer/page/off: resolved write
    coordinates — page/off (B,) from ``page_offsets``, ``layer`` a scalar
    or a (B,) array, one layer per token.  Decode-append must REQUANTIZE the
    page — the incoming token can exceed the page's current range — so
    the page is dequantized, the token inserted at ``off``, and the whole
    page re-encoded under ``max(old_scale, needed)``:

      * ``off == 0`` means a FRESH (or reused) page: the stale codes and
        scale are dead, so the effective old scale is zeroed and positions
        past ``off`` are masked out of the re-encode — a recycled page can
        never leak a stale amax into the new sequence's scale;
      * the scale is monotone within a page lifetime (never shrinks), so
        already-written positions only ever requantize under an equal or
        coarser power-of-two scale.

    Returns ``(codes, scales)`` with the touched pages rewritten.  Both
    scatters may hit duplicate indices only on the scratch page (inactive
    slots), whose content is garbage by contract.
    """
    at = (layer, page)
    hkv = scales.shape[-1]
    B = tok.shape[0]
    stored = codes[at]                                 # (B, ps, *rest)
    cp = unfold_heads(stored, hkv)                     # (B, ps, .., Hkv, D)
    nd = cp.ndim
    ps = cp.shape[1]

    def _x(s):  # (B, .., Hkv) -> broadcast over (B, ps, .., Hkv, D)
        return jnp.expand_dims(s, (1, nd - 1))

    sp = scales[at]                                    # (B, .., Hkv)
    fresh = (off > 0).reshape((B,) + (1,) * (sp.ndim - 1))
    sp_eff = jnp.where(fresh, sp, 0.0)
    old = cp.astype(jnp.float32) * _x(sp_eff)
    idx = jnp.arange(ps)[None, :]
    keep = (idx < off[:, None]).reshape((B, ps) + (1,) * (nd - 2))
    ins = (idx == off[:, None]).reshape((B, ps) + (1,) * (nd - 2))
    merged = jnp.where(keep, old, 0.0)
    merged = jnp.where(ins, unfold_heads(tok, hkv)[:, None].astype(
        jnp.float32), merged)
    amax = jnp.max(jnp.abs(merged), axis=(1, nd - 1))  # (B, .., Hkv)
    new_sc = jnp.maximum(sp_eff, kv_pow2_scale(amax, kv_dtype))
    q = kv_quantize(merged, _x(new_sc), kv_dtype).reshape(stored.shape)
    return codes.at[at].set(q), scales.at[at].set(new_sc)


def fake_quant_pages(leaf: jnp.ndarray, s_ax: int, n_tokens,
                     page_size: int, kv_dtype: str) -> jnp.ndarray:
    """Round-trip the COMPLETED pages of a dense request-cache leaf through
    the page quantizer (quantize→dequantize in place, dense dtype kept).

    The prefix-cache identity glue (DESIGN.md §13): under a quantized pool,
    a page's content is frozen at quantized precision the moment the page
    completes during prefill, so the chunk stream attends to exactly the
    values a later consumer will dequantize out of the shared page — the
    prefix on/off token identity survives quantization.  ``n_tokens``
    (traced) marks the filled length; only pages wholly below it round-trip
    (the partial tail page stays dense until insertion).  Per-page scales
    reduce over the within-page and trailing head_dim axes, matching the
    pool layout's per-page × per-kv-head scale exactly, and the insert
    quantizer reproduces the same codes from the roundtripped content
    (power-of-two idempotence), so shared pages quantize ONCE.
    """
    S = leaf.shape[s_ax]
    P = S // page_size
    x = jnp.moveaxis(leaf, s_ax, 0)                    # (S, *rest)
    xp = x.reshape((P, page_size) + x.shape[1:]).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xp), axis=(1, xp.ndim - 1), keepdims=True)
    sc = kv_pow2_scale(amax, kv_dtype)
    rt = kv_dequantize(kv_quantize(xp, sc, kv_dtype), sc)
    done = (jnp.arange(P) < jnp.asarray(n_tokens, jnp.int32) // page_size)
    rt = jnp.where(done.reshape((P,) + (1,) * (xp.ndim - 1)), rt, xp)
    out = rt.reshape(x.shape).astype(leaf.dtype)
    return jnp.moveaxis(out, 0, s_ax)
