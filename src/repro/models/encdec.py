"""Encoder-decoder backbone (seamless-m4t-medium): bidirectional encoder over
stub modality embeddings (precomputed audio-frame vectors per the assignment)
plus a causal decoder with cross-attention.

Split-brain: all enc/dec projections are device-side; the decoder KV cache,
cross-attention and softmax are host-side.  Cross K/V are projected once at
prefill (device) and live in the host cache thereafter — exactly the paper's
"static weights vs dynamic state" split (DESIGN.md §7).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers as L


def _mlp_init(key, d, ff, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {"w1": L.dense_init(ks[0], d, ff, dtype),
            "w3": L.dense_init(ks[1], d, ff, dtype),
            "w2": L.dense_init(ks[2], ff, d, dtype)}


def _enc_block_init(key, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 2)
    hd = cfg.resolved_head_dim
    return {
        "ln_attn": jnp.zeros((cfg.d_model,), dtype),
        "ln_mlp": jnp.zeros((cfg.d_model,), dtype),
        "attn": L.attn_init(ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, hd, dtype),
        "mlp": _mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype),
    }


def _dec_block_init(key, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    hd = cfg.resolved_head_dim
    return {
        "ln_self": jnp.zeros((cfg.d_model,), dtype),
        "ln_cross": jnp.zeros((cfg.d_model,), dtype),
        "ln_mlp": jnp.zeros((cfg.d_model,), dtype),
        "self": L.attn_init(ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, hd, dtype),
        "cross": L.attn_init(ks[1], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, hd, dtype),
        "mlp": _mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype),
    }


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    k_emb, k_enc, k_dec, k_head = jax.random.split(key, 4)
    enc_keys = jax.random.split(k_enc, cfg.num_encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.num_layers)
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02,
        "enc_blocks": jax.vmap(lambda k: _enc_block_init(k, cfg))(enc_keys),
        "dec_blocks": jax.vmap(lambda k: _dec_block_init(k, cfg))(dec_keys),
        "ln_enc": jnp.zeros((cfg.d_model,)),
        "ln_final": jnp.zeros((cfg.d_model,)),
        "lm_head": L.dense_init(k_head, cfg.d_model, cfg.vocab_size),
    }


def encode(params, frontend: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """frontend: (B, T_frames, d) stub audio embeddings -> (B, T, d)."""
    dtype = jnp.dtype(cfg.dtype)
    x = frontend.astype(dtype)
    positions = jnp.arange(x.shape[1])

    def layer(x, p):
        if cfg.parallel.gather_fsdp_weights:
            from repro.distributed import sharding as _shd
            p = _shd.gather_fsdp(p, cfg)
            x = _shd.pin_batch(x, cfg)
        h = L.attn_apply(p["attn"], L.rmsnorm(x, p["ln_attn"], cfg.norm_eps),
                         num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                         head_dim=cfg.resolved_head_dim, positions=positions,
                         rope_theta=cfg.rope_theta, causal=False,
                         kernels=cfg.kernels,
                         model_axis=cfg.parallel.model_axis,
                         batch_axes=cfg.parallel.batch_axes)
        x = x + h
        y = L.rmsnorm(x, p["ln_mlp"], cfg.norm_eps)
        x = x + L.swiglu(y, p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"],
                         cfg.kernels)
        return x, None

    if cfg.parallel.remat != "none":
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, params["enc_blocks"])
    return L.rmsnorm(x, params["ln_enc"], cfg.norm_eps)


def forward(params, tokens: jnp.ndarray, cfg: ModelConfig,
            frontend: Optional[jnp.ndarray] = None, **_):
    """Teacher-forced decode over full target sequence (training)."""
    dtype = jnp.dtype(cfg.dtype)
    enc = encode(params, frontend, cfg)
    hd = cfg.resolved_head_dim
    B, T = tokens.shape
    x = params["embed"][tokens].astype(dtype)
    positions = jnp.arange(T)

    def layer(x, p):
        if cfg.parallel.gather_fsdp_weights:
            from repro.distributed import sharding as _shd
            p = _shd.gather_fsdp(p, cfg)
            x = _shd.pin_batch(x, cfg)
        h = L.attn_apply(p["self"], L.rmsnorm(x, p["ln_self"], cfg.norm_eps),
                         num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                         head_dim=hd, positions=positions,
                         rope_theta=cfg.rope_theta, kernels=cfg.kernels,
                         model_axis=cfg.parallel.model_axis,
                         batch_axes=cfg.parallel.batch_axes)
        x = x + h
        xn = L.rmsnorm(x, p["ln_cross"], cfg.norm_eps)
        Bx, Tx, _ = enc.shape
        ck = L.linear(enc, p["cross"]["wk"], cfg.kernels).reshape(Bx, Tx, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
        cv = L.linear(enc, p["cross"]["wv"], cfg.kernels).reshape(Bx, Tx, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
        h = L.attn_apply(p["cross"], xn, num_heads=cfg.num_heads,
                         num_kv_heads=cfg.num_kv_heads, head_dim=hd,
                         positions=positions, rope_theta=cfg.rope_theta,
                         kv=(ck, cv), kernels=cfg.kernels,
                         model_axis=cfg.parallel.model_axis,
                         batch_axes=cfg.parallel.batch_axes)
        x = x + h
        y = L.rmsnorm(x, p["ln_mlp"], cfg.norm_eps)
        x = x + L.swiglu(y, p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"],
                         cfg.kernels)
        return x, None

    if cfg.parallel.remat != "none":
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, params["dec_blocks"])
    x = L.rmsnorm(x, params["ln_final"], cfg.norm_eps)
    logits = L.linear(x, params["lm_head"], cfg.kernels).astype(jnp.float32)
    return logits, 0.0


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               frontend: Optional[jnp.ndarray] = None, params=None) -> Dict[str, Any]:
    hd = cfg.resolved_head_dim
    Ld = cfg.num_layers
    dtype = jnp.dtype(cfg.dtype)
    cache: Dict[str, Any] = {
        "k": jnp.zeros((Ld, batch, cfg.num_kv_heads, max_len, hd), dtype),
        "v": jnp.zeros((Ld, batch, cfg.num_kv_heads, max_len, hd), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }
    if frontend is not None and params is not None:
        enc = encode(params, frontend, cfg)
        Bx, Tx, _ = enc.shape

        def proj(p):
            ck = L.linear(enc, p["cross"]["wk"], cfg.kernels).reshape(Bx, Tx, cfg.num_kv_heads, hd)
            cv = L.linear(enc, p["cross"]["wv"], cfg.kernels).reshape(Bx, Tx, cfg.num_kv_heads, hd)
            return ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3)

        ck, cv = jax.vmap(proj)(params["dec_blocks"])
        cache["cross_k"], cache["cross_v"] = ck, cv
    return cache


def decode_step(params, cache, tokens: jnp.ndarray, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    B = tokens.shape[0]
    hd = cfg.resolved_head_dim
    x = params["embed"][tokens][:, None, :].astype(dtype)
    pos = cache["len"]
    positions = pos[:, None]

    def layer(x, inputs):
        p, kc, vc, ck, cv = inputs
        xn = L.rmsnorm(x, p["ln_self"], cfg.norm_eps)
        q, k, v = L.qkv_project(p["self"], xn, cfg.num_heads, cfg.num_kv_heads, hd,
                                cfg.kernels)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        kc = L.cache_write(kc, k[:, :, 0:1], pos,
                           cfg.parallel.aligned_decode)
        vc = L.cache_write(vc, v[:, :, 0:1], pos,
                           cfg.parallel.aligned_decode)
        dist_axis = (cfg.parallel.seq_axis
                     if cfg.parallel.decode_attn == "shard_map" else None)
        o = ops.decode_attention(q, kc, vc, pos + 1, dist_axis=dist_axis,
                                 batch_axes=cfg.parallel.batch_axes)
        x = x + L.linear(o.transpose(0, 2, 1, 3).reshape(B, 1, cfg.num_heads * hd),
                         p["self"]["wo"], cfg.kernels)
        xn = L.rmsnorm(x, p["ln_cross"], cfg.norm_eps)
        qx = L.linear(xn, p["cross"]["wq"], cfg.kernels).reshape(B, 1, cfg.num_heads, hd).transpose(0, 2, 1, 3)
        Tx = ck.shape[2]
        o = ops.decode_attention(qx, ck, cv, jnp.full((B,), Tx, jnp.int32),
                                 dist_axis=dist_axis,
                                 batch_axes=cfg.parallel.batch_axes)
        x = x + L.linear(o.transpose(0, 2, 1, 3).reshape(B, 1, cfg.num_heads * hd),
                         p["cross"]["wo"], cfg.kernels)
        y = L.rmsnorm(x, p["ln_mlp"], cfg.norm_eps)
        x = x + L.swiglu(y, p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"],
                         cfg.kernels)
        return x, (kc, vc)

    x, (k, v) = jax.lax.scan(
        layer, x, (params["dec_blocks"], cache["k"], cache["v"],
                   cache["cross_k"], cache["cross_v"]))
    x = L.rmsnorm(x, params["ln_final"], cfg.norm_eps)
    logits = L.linear(x[:, 0], params["lm_head"], cfg.kernels).astype(jnp.float32)
    new_cache = dict(cache)
    new_cache.update({"k": k, "v": v, "len": cache["len"] + 1})
    return logits, new_cache
