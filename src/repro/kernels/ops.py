"""Jit'd public wrappers around the Pallas kernels and their jnp oracles.

Every op takes ``kernels``, one of :data:`KERNELS`, chosen ONCE, by whoever
builds the program, and passed down explicitly (``ModelConfig.kernels``):

* ``"pallas"`` — the compiled TPU kernels; what a serving engine picks when
  its mesh sits on TPU devices (:func:`for_platform`);
* ``"jnp"`` — the pure-jnp references in ``kernels/ref.py`` (lowering-safe,
  chunked); what an engine picks on any other platform;
* ``"interpret"`` — the Pallas kernels run by the Pallas interpreter, for
  CPU tests that validate a kernel against its oracle.

Nothing here probes the device or reads a process-wide switch, and
``kernels`` has no default: a caller that does not pass it fails.  Ops
without a kernel (dense-cache decode, the selective scan, chunked WKV)
always run their reference; an op that does have a kernel never swaps it
for the reference behind the caller's back.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import ref
from repro.kernels import rwkv_scan as _rwkv
from repro.kernels import w4a8_matmul as _w4a8

KERNELS = ("jnp", "pallas", "interpret")


def for_platform(platform: str) -> str:
    """The kernel set a device platform serves with: the compiled Pallas
    kernels on ``"tpu"``, the jnp oracles everywhere else."""
    return "pallas" if platform == "tpu" else "jnp"


def _pallas(kernels: str) -> bool:
    if kernels not in KERNELS:
        raise ValueError(f"kernels must be one of {KERNELS}, got {kernels!r}")
    return kernels != "jnp"


def w4a8_matmul(qx, x_scale, codes, w_scale, *, out_dtype=jnp.bfloat16,
                kernels: str):
    if _pallas(kernels):
        return _w4a8.w4a8_matmul(qx, x_scale, codes, w_scale,
                                 out_dtype=out_dtype,
                                 interpret=kernels == "interpret")
    return ref.w4a8_matmul(qx, x_scale, codes, w_scale, out_dtype)


def _tp_mesh(model_axis: Optional[str]):
    """(ambient mesh, TP degree) for a traced op; degree 1 off a TP mesh."""
    if model_axis is None:
        return None, 1
    from repro.distributed import runtime
    mesh = runtime.ambient_mesh()
    if mesh is None or model_axis not in mesh.axis_names:
        return None, 1
    return mesh, int(mesh.shape[model_axis])


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None, scale: Optional[float] = None,
              kv_offset=0, kernels: str,
              model_axis: Optional[str] = None, batch_axes: tuple = ()):
    """Prefill / training attention.  q (B,Hq,Tq,D); k, v (B,Hkv,Tk,D);
    query row i sits at key position ``i + kv_offset`` (scalar or (B,),
    may be traced).  On a TP mesh (``model_axis`` names a >1-sized axis of
    the ambient mesh) the kernel runs per shard inside ``shard_map``."""
    if not _pallas(kernels):
        return ref.mha_chunked(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               kv_offset=kv_offset)
    kw = dict(causal=causal, window=window, softcap=softcap, scale=scale,
              interpret=kernels == "interpret")
    mesh, tp = _tp_mesh(model_axis)
    if tp > 1:
        from repro.distributed import collectives
        fn = collectives.tp_flash_attention(
            mesh, model_axis, batch_axes=batch_axes,
            cut_heads=q.shape[1] % tp == 0 and k.shape[1] % tp == 0, **kw)
        off = jnp.broadcast_to(jnp.asarray(kv_offset, jnp.int32),
                               (q.shape[0],))
        return fn(q, k, v, off)
    return _fa.flash_attention(q, k, v, kv_offset=kv_offset, **kw)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     scale: Optional[float] = None,
                     dist_axis: Optional[str] = None,
                     batch_axes: tuple = ()):
    """Decode attention over a DENSE per-slot cache (the dense slot layout
    and fused generate; the served paged path uses
    :func:`paged_decode_attention`).  Reference only."""
    if dist_axis is not None and window is None:
        # §Perf H2: LSE-combined flash decode over the seq-sharded cache.
        from repro.distributed import collectives, runtime
        mesh = runtime.ambient_mesh()
        if mesh is not None and dist_axis in mesh.axis_names:
            S = k_cache.shape[2]
            valid = jnp.arange(S)[None, :] < cache_len[:, None]
            fn = collectives.distributed_decode_attention(
                mesh, dist_axis, softcap=softcap, scale=scale,
                batch_axes=batch_axes)
            return fn(q, k_cache, v_cache, valid)
    return ref.decode_attention(q, k_cache, v_cache, cache_len,
                                window=window, softcap=softcap, scale=scale)


def paged_decode_attention(q, k_pool, v_pool, page_table, cache_len, *,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           kernels: str,
                           model_axis: Optional[str] = None,
                           batch_axes: tuple = (),
                           layer):
    """Gather-free decode attention THROUGH the page table: no dense-view
    transient (serve/pages.py::gather_view) is ever materialized.  The
    Pallas kernel walks ``pool[table]`` page-block-wise (flash-decode over
    the split-K page axis, DESIGN.md §6); the reference is a ``lax.scan``
    over pages with the same online-softmax accumulation.

    Pools arrive whole and stacked, ``(L, N, ps, Hkv/f, f·D)`` in their
    stored, possibly lane-folded layout (``layers.kv_fold``), and are read
    at layer ``layer`` (a traced scalar) in place — no layer slice of the
    pool is ever taken.

    On a TP serving mesh (``model_axis`` names a >1-sized axis of the
    ambient mesh) the Pallas branch dispatches per shard (DESIGN.md §11):
    divisible head counts run the unchanged grid on each shard's head-cut
    pool slice with no collective; an indivisible Hkv replicates heads and
    splits the page axis instead, merging partials in log-sum-exp space.
    The jnp reference needs no routing — XLA partitions it under GSPMD.

    QUANTIZED pools arrive as ``QuantizedLeaf`` (int8/fp8 codes + per-page,
    per-kv-head scales, DESIGN.md §13): every path dequantizes at page-
    fetch time, the per-shard kernels on the scales' own head cut."""
    from repro.core.quant import QuantizedLeaf
    k_scale = v_scale = None
    if isinstance(k_pool, QuantizedLeaf):
        k_pool, k_scale = k_pool.codes, k_pool.scales
        v_pool, v_scale = v_pool.codes, v_pool.scales
    if not _pallas(kernels):
        return ref.paged_decode_attention(q, k_pool, v_pool, page_table,
                                          cache_len, window=window,
                                          softcap=softcap, scale=scale,
                                          k_scale=k_scale, v_scale=v_scale,
                                          layer=layer)
    interpret = kernels == "interpret"
    mesh, tp = _tp_mesh(model_axis)
    if tp > 1:
        from repro.distributed import collectives
        # the pool is cut on its stored head rows (Hkv/f of them)
        Hq, rows = q.shape[1], k_pool.shape[-2]
        args = (q, k_pool, v_pool, page_table, cache_len,
                jnp.asarray(layer, jnp.int32)) + (
            () if k_scale is None else (k_scale, v_scale))
        if Hq % tp == 0:
            if rows % tp == 0:
                return collectives.tp_paged_decode_attention(
                    mesh, model_axis, window=window, softcap=softcap,
                    scale=scale, batch_axes=batch_axes, interpret=interpret,
                    quant=k_scale is not None)(*args)
            if window is None and page_table.shape[1] % tp == 0:
                return collectives.tp_paged_decode_attention_merge(
                    mesh, model_axis, softcap=softcap, scale=scale,
                    batch_axes=batch_axes, interpret=interpret,
                    quant=k_scale is not None)(*args)
    return _pa.paged_decode_attention(q, k_pool, v_pool, page_table,
                                      cache_len, window=window,
                                      softcap=softcap, scale=scale,
                                      k_scale=k_scale, v_scale=v_scale,
                                      layer=layer, interpret=interpret)


def chunk_attention(q, k_cache, v_cache, q_pos, *,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    kernels: str,
                    model_axis: Optional[str] = None,
                    batch_axes: tuple = ()):
    """Chunked-prefill attention: a chunk written in place into a linear KV
    cache attends causally over absolute positions (serve-path paged/chunked
    prefill).  ``q_pos`` (B, W) must be consecutive per row (the chunk
    starts at ``q_pos[:, 0]``), so the Pallas branch is flash attention
    over the whole cache with that traced start as its key offset."""
    if _pallas(kernels):
        return attention(q, k_cache, v_cache, causal=True, window=window,
                         softcap=softcap, scale=scale,
                         kv_offset=q_pos[:, 0], kernels=kernels,
                         model_axis=model_axis, batch_axes=batch_axes)
    return ref.chunk_attention(q, k_cache, v_cache, q_pos, window=window,
                               softcap=softcap, scale=scale)


def rwkv6_chunked(r, k, v, w, u, state=None, *, chunk: int = 64):
    return ref.rwkv6_scan_chunked(r, k, v, w, u, state, chunk=chunk)


def rwkv6(r, k, v, w, u, state=None, *, kernels: str):
    """The WKV recurrence.  The kernel starts from a zero state, so a
    carried ``state`` (decode, chunk continuation) runs the reference."""
    if _pallas(kernels) and state is None:
        return _rwkv.rwkv6_scan(r, k, v, w, u,
                                interpret=kernels == "interpret")
    return ref.rwkv6_scan(r, k, v, w, u, state)


def selective_scan(x, delta, A, B, C, state=None, *,
                   algorithm: str = "sequential"):
    if algorithm == "associative" and x.shape[1] > 1:
        return ref.selective_scan_assoc(x, delta, A, B, C, state)
    return ref.selective_scan(x, delta, A, B, C, state)
