"""RWKV6 "Finch" (arXiv:2404.05892): attention-free LM with data-dependent
decay.  Split-brain mapping (DESIGN.md §7): all projections (r,k,v,g,o + the
decay LoRA + channel-mix matrices) are static linear maps -> ITA device; the
WKV recurrence carries dynamic state -> host.

Faithful-but-lean Finch block:
  time-mix: token-shift lerp with learned mixes; decay
      w_t = exp(-exp(w0 + lora_w(x_shift)))  (data-dependent, per channel)
  wkv: S_t = diag(w_t) S_{t-1} + k_t v_t^T ; out = r_t (S + diag(u) k v^T)
  group-norm over heads, silu(g) gate, output projection.
  channel-mix: squared-relu MLP with token shift.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import sharding as _shd
from repro.kernels import ops
from repro.models.layers import dense_init, linear, rmsnorm

HEAD_DIM = 64  # RWKV6 uses 64-wide heads
LORA_DIM = 64


def _pin(cfg: ModelConfig):
    """Serve-TP exactness hook (shd.pin_tp_exact): gathers model-sharded
    activations before contractions AND before the ln_x group norm, whose
    mean reduces over the sharded channel axis.  Identity unless
    cfg.parallel.exact_tp is set under an ambient mesh."""
    if not cfg.parallel.exact_tp:
        return lambda a: a
    return lambda a: _shd.pin_tp_exact(a, cfg)


def block_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    d = cfg.d_model
    ks = jax.random.split(key, 12)
    H = d // HEAD_DIM
    return {
        "ln_tm": jnp.zeros((d,), dtype),
        "ln_cm": jnp.zeros((d,), dtype),
        "mix": jax.random.uniform(ks[0], (5, d), dtype, 0.0, 1.0),  # r,k,v,g,w mixes
        "wr": dense_init(ks[1], d, d, dtype),
        "wk": dense_init(ks[2], d, d, dtype),
        "wv": dense_init(ks[3], d, d, dtype),
        "wg": dense_init(ks[4], d, d, dtype),
        "wo": dense_init(ks[5], d, d, dtype),
        "w0": jax.random.uniform(ks[6], (d,), dtype, -8.0, -5.0),
        "w_lora_a": dense_init(ks[7], d, LORA_DIM, dtype),
        "w_lora_b": dense_init(ks[8], LORA_DIM, d, dtype) * 0.1,
        "u": jax.random.normal(ks[9], (H, HEAD_DIM), dtype) * 0.3,
        "ln_x": jnp.zeros((d,), dtype),
        "cm_k": dense_init(ks[10], d, cfg.d_ff, dtype),
        "cm_v": dense_init(ks[11], cfg.d_ff, d, dtype),
    }


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    assert cfg.d_model % HEAD_DIM == 0
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    keys = jax.random.split(k_blocks, cfg.num_layers)
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02,
        "blocks": jax.vmap(lambda k: block_init(k, cfg))(keys),
        "ln_final": jnp.zeros((cfg.d_model,)),
        "lm_head": dense_init(k_head, cfg.d_model, cfg.vocab_size),
    }


def _token_shift(x: jnp.ndarray, x_prev: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """shifted[t] = x[t-1]; position 0 uses ``x_prev`` (decode carry) or 0."""
    if x_prev is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return jnp.concatenate([x_prev[:, None, :], x[:, :-1]], axis=1)


def _time_mix(p, x, cfg, state=None, x_prev=None):
    B, T, d = x.shape
    H = d // HEAD_DIM
    xs = _token_shift(x, x_prev)
    mix = p["mix"].astype(x.dtype)
    xr, xk, xv, xg, xw = (x + mix[i] * (xs - x) for i in range(5))
    r = linear(xr, p["wr"], cfg.kernels).reshape(B, T, H, HEAD_DIM).transpose(0, 2, 1, 3)
    k = linear(xk, p["wk"], cfg.kernels).reshape(B, T, H, HEAD_DIM).transpose(0, 2, 1, 3)
    v = linear(xv, p["wv"], cfg.kernels).reshape(B, T, H, HEAD_DIM).transpose(0, 2, 1, 3)
    pin = _pin(cfg)
    g = jax.nn.silu(linear(xg, p["wg"], cfg.kernels))
    dw = linear(pin(jnp.tanh(linear(xw, p["w_lora_a"], cfg.kernels))),
                p["w_lora_b"], cfg.kernels)
    w = jnp.exp(-jnp.exp((p["w0"].astype(jnp.float32) + dw.astype(jnp.float32))))
    w = w.reshape(B, T, H, HEAD_DIM).transpose(0, 2, 1, 3)
    if cfg.rwkv_chunk and T > 1:
        out, new_state = ops.rwkv6_chunked(
            r, k, v, w.astype(r.dtype), p["u"].astype(jnp.float32), state,
            chunk=cfg.rwkv_chunk)
    else:
        out, new_state = ops.rwkv6(r, k, v, w.astype(r.dtype),
                                   p["u"].astype(jnp.float32), state,
                                   kernels=cfg.kernels)
    out = pin(out.transpose(0, 2, 1, 3).reshape(B, T, d))
    out = rmsnorm(out, p["ln_x"], cfg.norm_eps) * g
    return linear(pin(out), p["wo"], cfg.kernels), new_state, x[:, -1]


def _channel_mix(p, x, cfg, x_prev=None, pin_fn=None):
    xs = _token_shift(x, x_prev)
    mix = p["mix"].astype(x.dtype)
    xk = x + mix[1] * (xs - x)
    h = jnp.square(jax.nn.relu(linear(xk, p["cm_k"], cfg.kernels)))
    if pin_fn is not None:
        h = pin_fn(h)
    return linear(h, p["cm_v"], cfg.kernels), x[:, -1]


def forward(params, tokens: jnp.ndarray, cfg: ModelConfig, **_):
    dtype = jnp.dtype(cfg.dtype)
    x = params["embed"][tokens].astype(dtype)

    def layer(x, p):
        if cfg.parallel.gather_fsdp_weights:
            from repro.distributed import sharding as _shd
            p = _shd.gather_fsdp(p, cfg)
            x = _shd.pin_batch(x, cfg)
        h, _, _ = _time_mix(p, rmsnorm(x, p["ln_tm"], cfg.norm_eps), cfg)
        x = x + h
        h, _ = _channel_mix(p, rmsnorm(x, p["ln_cm"], cfg.norm_eps), cfg,
                            pin_fn=_pin(cfg) if cfg.parallel.exact_tp else None)
        return x + h, jnp.zeros((), jnp.float32)

    if cfg.parallel.remat != "none":
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = rmsnorm(x, params["ln_final"], cfg.norm_eps)
    logits = linear(x, params["lm_head"], cfg.kernels).astype(jnp.float32)
    return logits, 0.0


def init_cache(cfg: ModelConfig, batch: int, max_len: int = 0, **_) -> Dict[str, Any]:
    """Recurrent state: O(1) in sequence length — this is why rwkv6 runs the
    long_500k cell that full-attention archs skip."""
    H = cfg.d_model // HEAD_DIM
    L = cfg.num_layers
    return {
        "wkv": jnp.zeros((L, batch, H, HEAD_DIM, HEAD_DIM), jnp.float32),
        "x_tm": jnp.zeros((L, batch, cfg.d_model), jnp.dtype(cfg.dtype)),
        "x_cm": jnp.zeros((L, batch, cfg.d_model), jnp.dtype(cfg.dtype)),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def decode_step(params, cache, tokens: jnp.ndarray, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    x = params["embed"][tokens][:, None, :].astype(dtype)

    def layer(x, inputs):
        p, wkv, x_tm, x_cm = inputs
        h, new_wkv, last_tm = _time_mix(
            p, rmsnorm(x, p["ln_tm"], cfg.norm_eps), cfg, state=wkv, x_prev=x_tm)
        x = x + h
        h, last_cm = _channel_mix(p, rmsnorm(x, p["ln_cm"], cfg.norm_eps), cfg,
                                  x_prev=x_cm,
                                  pin_fn=_pin(cfg) if cfg.parallel.exact_tp
                                  else None)
        return x + h, (new_wkv, last_tm, last_cm)

    x, (wkv, x_tm, x_cm) = jax.lax.scan(
        layer, x, (params["blocks"], cache["wkv"], cache["x_tm"], cache["x_cm"]))
    x = rmsnorm(x, params["ln_final"], cfg.norm_eps)
    logits = linear(x[:, 0], params["lm_head"], cfg.kernels).astype(jnp.float32)
    return logits, {"wkv": wkv, "x_tm": x_tm, "x_cm": x_cm,
                    "len": cache["len"] + 1}
