"""Unified decoder-only transformer LM covering the dense, MoE, softcap,
sliding-window, and cross-attention (VLM) members of the assigned pool.

Depth is executed as ``lax.scan`` over layer *groups* so the HLO stays O(1)
in depth (94-layer qwen3 compiles in seconds at 512 devices).  A group is
``group_size`` consecutive layers (+ an optional cross-attention block for
VLM archs); heterogeneity inside a group (gemma2 local/global alternation)
is unrolled statically from ``cfg.layer_pattern``.

Params are plain pytrees; every stacked array has leading dims
(n_groups, group_size, ...), which is also what the sharding-rules engine
keys on.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import sharding as _shd
from repro.kernels import ops
from repro.models import layers as L
from repro.models import moe as moe_mod


def _pin(cfg: ModelConfig):
    """Serve-TP exactness hook for down-projection inputs (no-op unless
    cfg.parallel.exact_tp and a mesh is ambient — see shd.pin_tp_exact)."""
    if not cfg.parallel.exact_tp:
        return None
    return lambda a: _shd.pin_tp_exact(a, cfg)


def group_layout(cfg: ModelConfig) -> Tuple[int, int]:
    P = len(cfg.layer_pattern)
    group_size = cfg.cross_attn_every if cfg.cross_attn_every else P
    assert cfg.num_layers % group_size == 0, (cfg.num_layers, group_size)
    assert group_size % P == 0, (group_size, P)
    return cfg.num_layers // group_size, group_size


def _stack(key, n: int, init_fn):
    """Initialize ``n`` independent copies stacked on a new leading axis."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def block_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "ln_attn": jnp.zeros((cfg.d_model,), dtype),
        "ln_mlp": jnp.zeros((cfg.d_model,), dtype),
        "attn": L.attn_init(ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, hd, dtype),
    }
    if cfg.moe:
        p["moe"] = moe_mod.moe_init(ks[1], cfg.d_model, cfg.d_ff, cfg.moe, dtype)
    else:
        p["mlp"] = {
            "w1": L.dense_init(ks[2], cfg.d_model, cfg.d_ff, dtype),
            "w3": L.dense_init(ks[3], cfg.d_model, cfg.d_ff, dtype),
            "w2": L.dense_init(ks[4], cfg.d_ff, cfg.d_model, dtype),
        }
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dtype = jnp.float32
    n_groups, group_size = group_layout(cfg)
    k_emb, k_blocks, k_cross, k_head = jax.random.split(key, 4)

    def group_init(k):
        return _stack(k, group_size, lambda kk: block_init(kk, cfg, dtype))

    params: Dict[str, Any] = {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model), dtype) * 0.02,
        "blocks": _stack(k_blocks, n_groups, group_init),
        "ln_final": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
    if cfg.cross_attn_every:
        hd = cfg.resolved_head_dim

        def cross_init(k):
            kk = jax.random.split(k, 2)
            return {
                "ln": jnp.zeros((cfg.d_model,), dtype),
                "attn": L.attn_init(kk[0], cfg.d_model, cfg.num_heads,
                                    cfg.num_kv_heads, hd, dtype),
                "gate": jnp.zeros((), dtype),
            }
        params["cross"] = _stack(k_cross, n_groups, cross_init)
    return params


# ----------------------------------------------------------------------------
# Forward (training / prefill): full sequence
# ----------------------------------------------------------------------------
def _block_apply(p, x, spec, cfg: ModelConfig, positions):
    h = L.attn_apply(
        p["attn"],
        L.rmsnorm(x, p["ln_attn"], cfg.norm_eps),
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim, positions=positions,
        rope_theta=cfg.rope_theta, window=spec.window, softcap=cfg.softcap,
        kernels=cfg.kernels,
        model_axis=cfg.parallel.model_axis,
        batch_axes=cfg.parallel.batch_axes, pin_fn=_pin(cfg))
    x = x + h
    y = L.rmsnorm(x, p["ln_mlp"], cfg.norm_eps)
    if cfg.moe:
        out, aux = moe_mod.moe_apply(p["moe"], y, cfg.moe)
    else:
        out, aux = L.swiglu(y, p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"],
                            cfg.kernels, pin_fn=_pin(cfg)), 0.0
    return x + out, aux


def _cross_apply(p, x, cross_kv, cfg: ModelConfig):
    hd = cfg.resolved_head_dim
    h = L.attn_apply(
        p["attn"], L.rmsnorm(x, p["ln"], cfg.norm_eps),
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads, head_dim=hd,
        positions=jnp.zeros((1,), jnp.int32), rope_theta=cfg.rope_theta,
        kv=cross_kv, kernels=cfg.kernels,
        model_axis=cfg.parallel.model_axis,
        batch_axes=cfg.parallel.batch_axes, pin_fn=_pin(cfg))
    return x + jnp.tanh(p["gate"]).astype(x.dtype) * h


def _cross_kv(p, frontend: jnp.ndarray, cfg: ModelConfig):
    """Project stub modality embeddings to cross K/V (device-phase op)."""
    Bx, Tx, _ = frontend.shape
    hd = cfg.resolved_head_dim
    k = L.linear(frontend, p["attn"]["wk"], cfg.kernels).reshape(Bx, Tx, cfg.num_kv_heads, hd)
    v = L.linear(frontend, p["attn"]["wv"], cfg.kernels).reshape(Bx, Tx, cfg.num_kv_heads, hd)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.parallel.remat == "none":
        return fn
    if cfg.parallel.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(params, tokens: jnp.ndarray, cfg: ModelConfig,
            frontend: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None):
    """tokens (B, T) -> (logits (B, T, V), aux_loss)."""
    n_groups, group_size = group_layout(cfg)
    P = len(cfg.layer_pattern)
    B, T = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    x = params["embed"][tokens].astype(dtype)
    if cfg.tie_embeddings:
        x = x * math.sqrt(cfg.d_model)  # gemma-style scaling with tied embed
    if positions is None:
        positions = jnp.arange(T)

    def group_fn(x, group_in):
        gp = group_in["blocks"]
        if cfg.parallel.gather_fsdp_weights:
            from repro.distributed import sharding as _shd
            gp = _shd.gather_fsdp(gp, cfg)
            x = _shd.pin_batch(x, cfg)
        aux_total = 0.0
        for j in range(group_size):
            pj = jax.tree.map(lambda a: a[j], gp)
            x, aux = _block_apply(pj, x, cfg.layer_pattern[j % P], cfg, positions)
            aux_total = aux_total + aux
        if cfg.cross_attn_every:
            kv = _cross_kv(group_in["cross"], frontend.astype(dtype), cfg)
            x = _cross_apply(group_in["cross"], x, kv, cfg)
        return x, jnp.asarray(aux_total, jnp.float32)

    group_fn = _maybe_remat(group_fn, cfg)
    xs = {"blocks": params["blocks"]}
    if cfg.cross_attn_every:
        xs["cross"] = params["cross"]
    if cfg.parallel.scan_layers:
        x, auxs = jax.lax.scan(lambda c, g: group_fn(c, g), x, xs)
        aux = jnp.sum(auxs) if cfg.moe else 0.0
    else:
        aux = 0.0
        for g in range(n_groups):
            x, a = group_fn(x, jax.tree.map(lambda t: t[g], xs))
            aux += a

    logits = _logits_head(params, x, cfg)
    return logits, aux


# ----------------------------------------------------------------------------
# KV cache + decode
# ----------------------------------------------------------------------------
def _block_qkv(pj, x, positions, cfg: ModelConfig):
    """Shared block head for prefill/decode: pre-norm, QKV projection, rope."""
    xn = L.rmsnorm(x, pj["ln_attn"], cfg.norm_eps)
    q, k, v = L.qkv_project(pj["attn"], xn, cfg.num_heads, cfg.num_kv_heads,
                            cfg.resolved_head_dim, cfg.kernels)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    return q, k, v


def _block_tail(pj, x, o, cfg: ModelConfig):
    """Shared block tail for prefill/decode: attention-output projection,
    FFN (dense or MoE), both residual adds.  o: (B, H, T, hd)."""
    B, T = x.shape[:2]
    o = o.transpose(0, 2, 1, 3).reshape(B, T, cfg.num_heads * cfg.resolved_head_dim)
    pin = _pin(cfg)
    if pin is not None:
        o = pin(o)
    x = x + L.linear(o, pj["attn"]["wo"], cfg.kernels)
    y = L.rmsnorm(x, pj["ln_mlp"], cfg.norm_eps)
    if cfg.moe:
        out, _ = moe_mod.moe_apply(pj["moe"], y, cfg.moe)
    else:
        out = L.swiglu(y, pj["mlp"]["w1"], pj["mlp"]["w3"], pj["mlp"]["w2"],
                       cfg.kernels, pin_fn=pin)
    return x + out


def _embed_decode(params, tokens: jnp.ndarray, cfg: ModelConfig):
    """Shared decode preamble: embed one token per row -> (B, 1, d)."""
    x = params["embed"][tokens][:, None, :].astype(jnp.dtype(cfg.dtype))
    if cfg.tie_embeddings:
        x = x * math.sqrt(cfg.d_model)
    return x


def _logits_head(params, x: jnp.ndarray, cfg: ModelConfig):
    """Shared logits tail: final norm, (tied) LM head, final softcap.
    ONE copy, so the dense and paged decode paths cannot drift apart on
    the head math their token-identity contract depends on."""
    x = L.rmsnorm(x, params["ln_final"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = L.linear(x, head, cfg.kernels).astype(jnp.float32)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits



def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               frontend: Optional[jnp.ndarray] = None, params=None) -> Dict[str, Any]:
    n_groups, group_size = group_layout(cfg)
    hd = cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.dtype)
    # per-pattern-slot window: cache only needs the window size for local slots
    sizes = tuple(min(max_len, s.window) if s.window else max_len
                  for s in cfg.layer_pattern)
    P = len(cfg.layer_pattern)
    cache: Dict[str, Any] = {
        "k": [jnp.zeros((n_groups, group_size // P, batch, cfg.num_kv_heads,
                         sizes[j], hd), dtype) for j in range(P)],
        "v": [jnp.zeros((n_groups, group_size // P, batch, cfg.num_kv_heads,
                         sizes[j], hd), dtype) for j in range(P)],
        "len": jnp.zeros((batch,), jnp.int32),
    }
    if cfg.cross_attn_every and frontend is not None and params is not None:
        kv = jax.vmap(lambda cp: _cross_kv(cp, frontend.astype(dtype), cfg))(
            params["cross"])
        cache["cross_k"], cache["cross_v"] = kv
    return cache


def prefill(params, cache, tokens: jnp.ndarray, cfg: ModelConfig,
            true_len: Optional[jnp.ndarray] = None):
    """Fill a FRESH KV cache with a whole prompt in one forward-style pass.

    tokens (B, T) -> (last-position logits (B, V), cache with len = T).
    One fused program instead of T sequential decode steps: QKV for the full
    prompt, block-write into the cache, causal self-attention over the
    prompt.  Requires every cache slot to hold T tokens (``api.prefill``
    falls back to a scanned decode otherwise) and an empty cache.

    ``true_len`` (traced scalar, serve-path shape bucketing): tokens is
    right-padded to a bucket width and only the first ``true_len`` positions
    are real.  Logits are taken at position ``true_len - 1`` and ``len`` is
    advanced by ``true_len``.  The K/V written past ``true_len`` are garbage
    but unreachable: causal attention masks them during prefill, decode
    attends only to ``len`` positions, and each subsequent decode step
    overwrites slot ``len`` before attending to it (DESIGN.md §4).
    """
    n_groups, group_size = group_layout(cfg)
    P = len(cfg.layer_pattern)
    T = tokens.shape[1]
    dtype = jnp.dtype(cfg.dtype)
    x = params["embed"][tokens].astype(dtype)
    if cfg.tie_embeddings:
        x = x * math.sqrt(cfg.d_model)
    positions = jnp.arange(T)

    def group_fn(x, group_in):
        gp = group_in["blocks"]
        new_k, new_v = [], []
        for j in range(group_size):
            slot = j % P
            spec = cfg.layer_pattern[slot]
            pj = jax.tree.map(lambda a: a[j], gp)
            kc = group_in["k"][slot][j // P]
            vc = group_in["v"][slot][j // P]
            q, k, v = _block_qkv(pj, x, positions, cfg)
            kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))
            o = ops.attention(q, k, v, causal=True, window=spec.window,
                              softcap=cfg.softcap, kernels=cfg.kernels,
                              model_axis=cfg.parallel.model_axis,
                              batch_axes=cfg.parallel.batch_axes)
            x = _block_tail(pj, x, o, cfg)
            new_k.append(kc)
            new_v.append(vc)
        if cfg.cross_attn_every:
            kv = (group_in["cross_k"], group_in["cross_v"])
            x = _cross_apply(group_in["cross"], x, kv, cfg)
        upd = {
            "k": [jnp.stack(new_k[s::P]) for s in range(P)],
            "v": [jnp.stack(new_v[s::P]) for s in range(P)],
        }
        return x, upd

    xs = {"blocks": params["blocks"], "k": cache["k"], "v": cache["v"]}
    if cfg.cross_attn_every:
        xs["cross"] = params["cross"]
        xs["cross_k"] = cache["cross_k"]
        xs["cross_v"] = cache["cross_v"]
    x, upd = jax.lax.scan(group_fn, x, xs)

    x = L.rmsnorm(x, params["ln_final"], cfg.norm_eps)
    if true_len is None:
        x_last = x[:, -1]
        advance = T
    else:
        B = tokens.shape[0]
        idx = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32) - 1, (B,))
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        advance = jnp.asarray(true_len, jnp.int32)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = L.linear(x_last, head, cfg.kernels).astype(jnp.float32)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = upd["k"], upd["v"]
    new_cache["len"] = cache["len"] + advance
    return logits, new_cache


def prefill_chunk(params, cache, tokens: jnp.ndarray, true_len, cfg: ModelConfig):
    """Advance a (possibly non-empty) KV cache by one right-padded chunk.

    The chunked-prefill block path: tokens (B, W) are the next ``true_len``
    prompt positions (bucket-padded to W), written at offset ``cache["len"]``
    and attended causally against the whole cache via
    ``ops.chunk_attention`` — positions past each row are masked, so the
    padding rows' K/V are garbage that the next chunk overwrites (or that
    sits beyond ``len``, unreachable by decode).  Requires every cache slot
    to be a LINEAR (non-ring) buffer of the full ``max_len``; windowed ring
    layouts take the masked scan-of-decode fallback in ``api.prefill_chunk``.

    PRECONDITION (enforced by the caller, not checkable on a traced
    ``len``): ``cache["len"]`` must be a multiple of W and W must divide
    the cache size, i.e. chunks are fed full-width back to back with only
    the LAST one padded — the scheduler's feeding order.  A misaligned
    start would make ``dynamic_update_slice`` clamp ``start + W`` back
    into bounds and silently overwrite earlier positions.  The start need
    NOT be zero: the shared-prefix serve path seeds ``len = cached`` from
    the page pool and streams only the prompt tail through here — rope and
    the causal mask are absolute-position, so the math is unchanged; the
    pager rounds the cached length to a multiple of lcm(page, W) exactly
    so this alignment precondition keeps holding (DESIGN.md §7).

    Returns the cache with ``len += true_len`` (no logits: chunked prefill
    feeds the last prompt token to the decode step, which produces them).
    """
    n_groups, group_size = group_layout(cfg)
    P = len(cfg.layer_pattern)
    B, W = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    x = params["embed"][tokens].astype(dtype)
    if cfg.tie_embeddings:
        x = x * math.sqrt(cfg.d_model)
    start = cache["len"]                                   # (B,)
    positions = start[:, None] + jnp.arange(W)[None, :]    # (B, W)

    def group_fn(x, group_in):
        gp = group_in["blocks"]
        new_k, new_v = [], []
        for j in range(group_size):
            slot = j % P
            spec = cfg.layer_pattern[slot]
            pj = jax.tree.map(lambda a: a[j], gp)
            kc = group_in["k"][slot][j // P]
            vc = group_in["v"][slot][j // P]
            q, k, v = _block_qkv(pj, x, positions, cfg)
            kc = jax.vmap(lambda c, kk, i: jax.lax.dynamic_update_slice(
                c, kk, (0, i, 0)))(kc, k, start)
            vc = jax.vmap(lambda c, vv, i: jax.lax.dynamic_update_slice(
                c, vv, (0, i, 0)))(vc, v, start)
            o = ops.chunk_attention(q, kc, vc, positions, window=spec.window,
                                    softcap=cfg.softcap,
                                    kernels=cfg.kernels,
                                    model_axis=cfg.parallel.model_axis,
                                    batch_axes=cfg.parallel.batch_axes)
            x = _block_tail(pj, x, o, cfg)
            new_k.append(kc)
            new_v.append(vc)
        upd = {
            "k": [jnp.stack(new_k[s::P]) for s in range(P)],
            "v": [jnp.stack(new_v[s::P]) for s in range(P)],
        }
        return x, upd

    xs = {"blocks": params["blocks"], "k": cache["k"], "v": cache["v"]}
    _, upd = jax.lax.scan(group_fn, x, xs)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = upd["k"], upd["v"]
    new_cache["len"] = cache["len"] + jnp.asarray(true_len, jnp.int32)
    return new_cache


def _stack_layers(leaf, n: int):
    """(n_groups, gs//P, ...) -> (n_groups * gs//P, ...): one layer axis.
    A bitcast for the row-major pool (the merged axes are its major ones);
    QuantizedLeaf codes and scales reshape together."""
    return jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), leaf)


def paged_decode_step(params, cache, table, tokens: jnp.ndarray,
                      cfg: ModelConfig, *, write=None, seq_axes=None):
    """One decode step straight through the page pool — no dense view.

    The gather-free serve path (DESIGN.md §6): full-attention pattern slots
    hold their K/V as pool leaves ``(n_groups, gs//P, num_pages, page_size,
    Hkv/f, f·hd)`` in the stored, possibly lane-folded layout
    (``layers.kv_fold``); the step appends the new token to its page
    (``layers.paged_cache_write``) and attends via
    ``ops.paged_decode_attention`` with pages as the split-K axis.  Ring
    (window < max_len) slots and ``len`` keep the dense layout and the
    exact ``decode_step`` math.

    The pool is UPDATED IN PLACE: the layer scan carries the whole stacked
    pool instead of slicing it as ``xs`` and restacking it as ``ys`` (which
    on the TPU copies every layer's pool out and back in, every step).
    Each layer scatters its token at ``(layer, page, offset)`` and the
    kernel reads that layer's pages of the stacked pool as stored
    (``ops.paged_decode_attention(..., layer=)``), so no program holds a
    pool-sized temporary.

    cache: paged slot-cache pytree; table: (B, P) physical page ids;
    tokens: (B,); write: (B,) bool — slots with ``write=False`` are frozen
    (their pool append routes to the scratch page, dense leaves and ``len``
    keep their old values; their logits are garbage and must be ignored).
    seq_axes: the per-leaf sequence-axis pytree from
    ``serve/pages.py::seq_axes`` discovery — entries >= 0 mark pool leaves.
    Token-identical to gathering the dense view and running ``decode_step``
    (tests/test_paged_attention.py), with O(live tokens) KV reads.
    """
    n_groups, group_size = group_layout(cfg)
    P = len(cfg.layer_pattern)
    per = group_size // P                    # layers of one slot per group
    B = tokens.shape[0]
    if write is None:
        write = jnp.ones((B,), bool)
    sa_k = [seq_axes["k"][j] for j in range(P)]
    x = _embed_decode(params, tokens, cfg)
    pos = cache["len"]                        # (B,)
    positions = pos[:, None]                  # (B, 1)
    wmask = write[:, None, None, None]
    kv = {n: [_stack_layers(a, n_groups * per) for a in cache[n]]
          for n in ("k", "v")}

    def group_fn(carry, group_in):
        x, kv = carry
        kv = {n: list(leaves) for n, leaves in kv.items()}
        gp = group_in["blocks"]
        for j in range(group_size):
            slot = j % P
            spec = cfg.layer_pattern[slot]
            pj = jax.tree.map(lambda a: a[j], gp)
            lyr = group_in["g"] * per + j // P
            kc, vc = kv["k"][slot], kv["v"][slot]
            q, k, v = _block_qkv(pj, x, positions, cfg)
            if sa_k[slot] >= 0:
                # pool leaf: in-place page append + gather-free attention.
                # NOTE: no seq-sharded (decode_attn="shard_map") variant —
                # the page pool is not sequence-sharded; configs needing it
                # must serve via the dense or gather disciplines.
                kc = L.paged_cache_write(kc, k, table, pos, write, layer=lyr)
                vc = L.paged_cache_write(vc, v, table, pos, write, layer=lyr)
                o = ops.paged_decode_attention(
                    q, kc, vc, table, pos + 1, window=spec.window,
                    softcap=cfg.softcap, kernels=cfg.kernels,
                    model_axis=cfg.parallel.model_axis,
                    batch_axes=cfg.parallel.batch_axes, layer=lyr)
            else:
                # ring buffer (window < max_len): dense path, frozen where
                # the slot is inactive
                kl, vl = kc[lyr], vc[lyr]
                S = kl.shape[2]
                idx = pos % S
                kl_new = L.cache_write(kl, k[:, :, 0:1], idx,
                                       cfg.parallel.aligned_decode)
                vl_new = L.cache_write(vl, v[:, :, 0:1], idx,
                                       cfg.parallel.aligned_decode)
                kc = kc.at[lyr].set(jnp.where(wmask, kl_new, kl))
                vc = vc.at[lyr].set(jnp.where(wmask, vl_new, vl))
                eff_len = jnp.minimum(pos + 1, S)
                # no dist_axis: the engine refuses inplace paging under
                # decode_attn="shard_map" (serve/engine.py), so the seq-
                # sharded decode variant is unreachable from this step
                o = ops.decode_attention(q, kl_new, vl_new, eff_len,
                                         softcap=cfg.softcap)
            x = _block_tail(pj, x, o, cfg)
            kv["k"][slot], kv["v"][slot] = kc, vc
        return (x, kv), None

    xs = {"blocks": params["blocks"], "g": jnp.arange(n_groups)}
    (x, kv), _ = jax.lax.scan(group_fn, (x, kv), xs)

    logits = _logits_head(params, x[:, 0], cfg)
    new_cache = dict(cache)
    for n in ("k", "v"):
        new_cache[n] = [jax.tree.map(lambda a, o: a.reshape(o.shape), new, old)
                        for new, old in zip(kv[n], cache[n])]
    new_cache["len"] = cache["len"] + write.astype(jnp.int32)
    return logits, new_cache


def decode_step(params, cache, tokens: jnp.ndarray, cfg: ModelConfig):
    """One decode step. tokens (B,) -> (logits (B, V), new_cache)."""
    n_groups, group_size = group_layout(cfg)
    P = len(cfg.layer_pattern)
    x = _embed_decode(params, tokens, cfg)
    pos = cache["len"]                        # (B,)
    positions = pos[:, None]                  # (B, 1)

    def group_fn(x, group_in):
        gp = group_in["blocks"]
        new_k, new_v = [], []
        for j in range(group_size):
            slot = j % P
            spec = cfg.layer_pattern[slot]
            pj = jax.tree.map(lambda a: a[j], gp)
            kc = group_in["k"][slot][j // P]
            vc = group_in["v"][slot][j // P]
            q, k, v = _block_qkv(pj, x, positions, cfg)
            S = kc.shape[2]
            if spec.window and spec.window <= S:
                idx = pos % S                 # ring buffer for local layers
            else:
                idx = jnp.minimum(pos, S - 1)
            kc = L.cache_write(kc, k[:, :, 0:1], idx,
                               cfg.parallel.aligned_decode)
            vc = L.cache_write(vc, v[:, :, 0:1], idx,
                               cfg.parallel.aligned_decode)
            dist_axis = (cfg.parallel.seq_axis
                         if cfg.parallel.decode_attn == "shard_map" else None)
            if spec.window and spec.window <= S:
                # ring buffer: all S slots valid once len >= S; attention mask
                # handles the general case via effective length
                eff_len = jnp.minimum(pos + 1, S)
                o = ops.decode_attention(q, kc, vc, eff_len, softcap=cfg.softcap,
                                         dist_axis=dist_axis,
                                         batch_axes=cfg.parallel.batch_axes)
            else:
                o = ops.decode_attention(q, kc, vc, pos + 1, window=spec.window,
                                         softcap=cfg.softcap,
                                         dist_axis=dist_axis,
                                         batch_axes=cfg.parallel.batch_axes)
            x = _block_tail(pj, x, o, cfg)
            new_k.append(kc)
            new_v.append(vc)
        if cfg.cross_attn_every:
            kv = (group_in["cross_k"], group_in["cross_v"])
            x = _cross_apply(group_in["cross"], x, kv, cfg)
        upd = {
            "k": [jnp.stack(new_k[s::P]) for s in range(P)],
            "v": [jnp.stack(new_v[s::P]) for s in range(P)],
        }
        return x, upd

    xs = {"blocks": params["blocks"], "k": cache["k"], "v": cache["v"]}
    if cfg.cross_attn_every:
        xs["cross"] = params["cross"]
        xs["cross_k"] = cache["cross_k"]
        xs["cross_v"] = cache["cross_v"]
    x, upd = jax.lax.scan(group_fn, x, xs)

    logits = _logits_head(params, x[:, 0], cfg)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = upd["k"], upd["v"]
    new_cache["len"] = cache["len"] + 1
    return logits, new_cache
