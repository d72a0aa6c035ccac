"""Hymba (arXiv:2411.13676): hybrid-head LM — every layer runs attention
heads and Mamba-style SSM heads **in parallel** on the same input, then
fuses the two branches.

Faithful skeleton: GQA sliding-window attention branch + selective-scan SSM
branch, per-branch RMS normalization, averaged fusion, SwiGLU FFN.  (The
paper's meta-tokens and cross-layer KV sharing are omitted; noted in
DESIGN.md.)  The SSM branch gives O(1) decode state, which is what makes the
long_500k cell runnable: attention uses a bounded ring-buffer window while
the SSM carries unbounded context.

Split-brain: all projections (QKV/O, in/out/Δ/B/C, FFN) are static ->
device; selective-scan state update + attention over the window -> host.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSMConfig
from repro.distributed import sharding as _shd
from repro.kernels import ops
from repro.models import layers as L


def _pin(cfg: ModelConfig):
    """Serve-TP exactness hook for down-projection inputs (no-op unless
    cfg.parallel.exact_tp and a mesh is ambient — see shd.pin_tp_exact)."""
    if not cfg.parallel.exact_tp:
        return None
    return lambda a: _shd.pin_tp_exact(a, cfg)


def block_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    d = cfg.d_model
    ssm = cfg.ssm or SSMConfig()
    N, R = ssm.state_dim, ssm.dt_rank
    ks = jax.random.split(key, 10)
    hd = cfg.resolved_head_dim
    return {
        "ln_in": jnp.zeros((d,), dtype),
        "ln_mlp": jnp.zeros((d,), dtype),
        "ln_attn_out": jnp.zeros((d,), dtype),
        "ln_ssm_out": jnp.zeros((d,), dtype),
        "attn": L.attn_init(ks[0], d, cfg.num_heads, cfg.num_kv_heads, hd, dtype),
        "ssm": {
            "w_in": L.dense_init(ks[1], d, d, dtype),
            "w_delta": L.dense_init(ks[2], d, R, dtype),
            "w_delta_up": L.dense_init(ks[3], R, d, dtype),
            "A_log": jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32), (d, 1))),
            "w_B": L.dense_init(ks[4], d, N, dtype),
            "w_C": L.dense_init(ks[5], d, N, dtype),
            "D": jnp.ones((d,), dtype),
            "w_out": L.dense_init(ks[6], d, d, dtype),
        },
        "mlp": {
            "w1": L.dense_init(ks[7], d, cfg.d_ff, dtype),
            "w3": L.dense_init(ks[8], d, cfg.d_ff, dtype),
            "w2": L.dense_init(ks[9], cfg.d_ff, d, dtype),
        },
    }


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    keys = jax.random.split(k_blocks, cfg.num_layers)
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02,
        "blocks": jax.vmap(lambda k: block_init(k, cfg))(keys),
        "ln_final": jnp.zeros((cfg.d_model,)),
        "lm_head": L.dense_init(k_head, cfg.d_model, cfg.vocab_size),
    }


def _ssm_branch(p, x, cfg: ModelConfig, state=None):
    """x: (B, T, d) -> (out, new_state (B, d, N))."""
    ssm_p = p["ssm"]
    pin = _pin(cfg) or (lambda a: a)
    h = jax.nn.silu(L.linear(x, ssm_p["w_in"], cfg.kernels))
    delta = jax.nn.softplus(
        L.linear(pin(L.linear(x, ssm_p["w_delta"], cfg.kernels)),
                 ssm_p["w_delta_up"], cfg.kernels)
    ).astype(jnp.float32)
    A = -jnp.exp(ssm_p["A_log"].astype(jnp.float32))
    Bm = L.linear(x, ssm_p["w_B"], cfg.kernels).astype(jnp.float32)
    Cm = L.linear(x, ssm_p["w_C"], cfg.kernels).astype(jnp.float32)
    y, new_state = ops.selective_scan(h, delta, A, Bm, Cm, state,
                                      algorithm=cfg.ssm_scan)
    y = y + h * ssm_p["D"].astype(h.dtype)
    return L.linear(pin(y), ssm_p["w_out"], cfg.kernels), new_state


def _embed_decode(params, tokens: jnp.ndarray, cfg: ModelConfig):
    """Shared decode preamble: embed one token per row -> (B, 1, d)."""
    return params["embed"][tokens][:, None, :].astype(jnp.dtype(cfg.dtype))


def _fuse_tail(p, x, xn, o, sstate, cfg: ModelConfig):
    """Shared hybrid-head tail for both decode disciplines: attention-out
    projection, SSM branch, per-branch norms + averaged fusion, MLP.  ONE
    copy, so the dense and paged decode paths cannot drift apart on the
    fusion math their token-identity contract depends on.
    o: (B, Hq, 1, hd) -> (new x, new ssm state)."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    pin = _pin(cfg) or (lambda a: a)
    attn_out = L.linear(
        pin(o.transpose(0, 2, 1, 3).reshape(B, 1, cfg.num_heads * hd)),
        p["attn"]["wo"], cfg.kernels)
    ssm_out, new_state = _ssm_branch(p, xn, cfg, state=sstate)
    fused = 0.5 * (L.rmsnorm(attn_out, p["ln_attn_out"], cfg.norm_eps)
                   + L.rmsnorm(ssm_out, p["ln_ssm_out"], cfg.norm_eps))
    x = x + fused
    y = L.rmsnorm(x, p["ln_mlp"], cfg.norm_eps)
    x = x + L.swiglu(y, p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"],
                     cfg.kernels, pin_fn=_pin(cfg))
    return x, new_state


def _logits_head(params, x: jnp.ndarray, cfg: ModelConfig):
    """Shared logits tail: final norm + LM head at the single position."""
    x = L.rmsnorm(x, params["ln_final"], cfg.norm_eps)
    return L.linear(x[:, 0], params["lm_head"], cfg.kernels).astype(jnp.float32)


def forward(params, tokens: jnp.ndarray, cfg: ModelConfig, **_):
    dtype = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    x = params["embed"][tokens].astype(dtype)
    positions = jnp.arange(T)
    window = cfg.layer_pattern[0].window

    def layer(x, p):
        if cfg.parallel.gather_fsdp_weights:
            from repro.distributed import sharding as _shd
            p = _shd.gather_fsdp(p, cfg)
            x = _shd.pin_batch(x, cfg)
        xn = L.rmsnorm(x, p["ln_in"], cfg.norm_eps)
        attn_out = L.attn_apply(
            p["attn"], xn, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, positions=positions,
            rope_theta=cfg.rope_theta, window=window, kernels=cfg.kernels,
            model_axis=cfg.parallel.model_axis,
            batch_axes=cfg.parallel.batch_axes)
        ssm_out, _ = _ssm_branch(p, xn, cfg)
        fused = 0.5 * (L.rmsnorm(attn_out, p["ln_attn_out"], cfg.norm_eps)
                       + L.rmsnorm(ssm_out, p["ln_ssm_out"], cfg.norm_eps))
        x = x + fused
        y = L.rmsnorm(x, p["ln_mlp"], cfg.norm_eps)
        x = x + L.swiglu(y, p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"],
                         cfg.kernels)
        return x, jnp.zeros((), jnp.float32)

    if cfg.parallel.remat != "none":
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = L.rmsnorm(x, params["ln_final"], cfg.norm_eps)
    logits = L.linear(x, params["lm_head"], cfg.kernels).astype(jnp.float32)
    return logits, 0.0


def init_cache(cfg: ModelConfig, batch: int, max_len: int, **_) -> Dict[str, Any]:
    ssm = cfg.ssm or SSMConfig()
    hd = cfg.resolved_head_dim
    window = cfg.layer_pattern[0].window or max_len
    S = min(window, max_len)
    Lc = cfg.num_layers
    dtype = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((Lc, batch, cfg.num_kv_heads, S, hd), dtype),
        "v": jnp.zeros((Lc, batch, cfg.num_kv_heads, S, hd), dtype),
        "ssm": jnp.zeros((Lc, batch, cfg.d_model, ssm.state_dim), jnp.float32),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def paged_decode_step(params, cache, table, tokens: jnp.ndarray,
                      cfg: ModelConfig, *, write=None, seq_axes=None):
    """Hymba decode straight through the page pool (DESIGN.md §6).

    Only engaged when the attention window covers the whole cache (global
    attention), i.e. the K/V leaves actually page: k/v arrive as stacked
    ``(L, num_pages, page_size, Hkv/f, f·hd)`` pool leaves (stored layout,
    ``layers.kv_fold``) CARRIED through the layer scan and appended and
    attended in place at each layer's index — never sliced out and
    restacked; the SSM state — the O(1) recurrent half of the hybrid head —
    stays dense and is frozen (like ``len``) where ``write`` is False.
    """
    del seq_axes  # hymba pages k/v iff this entry point is reached at all
    B = tokens.shape[0]
    hd = cfg.resolved_head_dim
    if write is None:
        write = jnp.ones((B,), bool)
    x = _embed_decode(params, tokens, cfg)
    pos = cache["len"]
    positions = pos[:, None]
    window = cfg.layer_pattern[0].window

    def layer(carry, inputs):
        x, kc, vc = carry
        p, sstate, li = inputs
        xn = L.rmsnorm(x, p["ln_in"], cfg.norm_eps)
        q, k, v = L.qkv_project(p["attn"], xn, cfg.num_heads,
                                cfg.num_kv_heads, hd, cfg.kernels)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        kc = L.paged_cache_write(kc, k, table, pos, write, layer=li)
        vc = L.paged_cache_write(vc, v, table, pos, write, layer=li)
        o = ops.paged_decode_attention(q, kc, vc, table, pos + 1,
                                       window=window,
                                       kernels=cfg.kernels,
                                       model_axis=cfg.parallel.model_axis,
                                       batch_axes=cfg.parallel.batch_axes,
                                       layer=li)
        x, new_state = _fuse_tail(p, x, xn, o, sstate, cfg)
        new_state = jnp.where(write[:, None, None], new_state, sstate)
        return (x, kc, vc), new_state

    n_layers = cache["ssm"].shape[0]
    (x, k, v), ssm = jax.lax.scan(
        layer, (x, cache["k"], cache["v"]),
        (params["blocks"], cache["ssm"], jnp.arange(n_layers)))
    logits = _logits_head(params, x, cfg)
    return logits, {"k": k, "v": v, "ssm": ssm,
                    "len": cache["len"] + write.astype(jnp.int32)}


def decode_step(params, cache, tokens: jnp.ndarray, cfg: ModelConfig):
    hd = cfg.resolved_head_dim
    x = _embed_decode(params, tokens, cfg)
    pos = cache["len"]
    positions = pos[:, None]

    def layer(x, inputs):
        p, kc, vc, sstate = inputs
        xn = L.rmsnorm(x, p["ln_in"], cfg.norm_eps)
        q, k, v = L.qkv_project(p["attn"], xn, cfg.num_heads, cfg.num_kv_heads, hd,
                                cfg.kernels)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        S = kc.shape[2]
        idx = pos % S  # ring buffer (window-bounded attention)
        kc = L.cache_write(kc, k[:, :, 0:1], idx,
                           cfg.parallel.aligned_decode)
        vc = L.cache_write(vc, v[:, :, 0:1], idx,
                           cfg.parallel.aligned_decode)
        eff_len = jnp.minimum(pos + 1, S)
        o = ops.decode_attention(q, kc, vc, eff_len)
        x, new_state = _fuse_tail(p, x, xn, o, sstate, cfg)
        return x, (kc, vc, new_state)

    x, (k, v, ssm) = jax.lax.scan(
        layer, x, (params["blocks"], cache["k"], cache["v"], cache["ssm"]))
    logits = _logits_head(params, x, cfg)
    return logits, {"k": k, "v": v, "ssm": ssm, "len": cache["len"] + 1}
