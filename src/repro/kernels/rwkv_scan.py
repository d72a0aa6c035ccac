"""Pallas TPU kernel: RWKV6 (Finch) WKV recurrence with data-dependent decay.

The recurrence S_t = diag(w_t) S_{t-1} + k_t v_t^T is the inference hot-spot
of the attention-free archs (rwkv6-7b) and maps poorly to plain XLA (a long
scalar scan).  Here the time axis is blocked: grid (B, H, T/bt) with the
(D, D) state carried in VMEM scratch across time blocks ("arbitrary"
semantics), and a ``fori_loop`` stepping through the block entirely in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 256

def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sfinal_ref, s_ref, *,
            bt: int, n_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0, 0].astype(jnp.float32)  # (D,)

    def step(t, _):
        rt = r_ref[0, 0, t].astype(jnp.float32)   # (D,)
        kt = k_ref[0, 0, t].astype(jnp.float32)
        vt = v_ref[0, 0, t].astype(jnp.float32)
        wt = w_ref[0, 0, t].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]            # (D, D)
        out = jnp.sum(rt[:, None] * (s_ref[...] + u[:, None] * kv), axis=0)
        o_ref[0, 0, t] = out.astype(o_ref.dtype)
        s_ref[...] = wt[:, None] * s_ref[...] + kv
        return ()

    jax.lax.fori_loop(0, bt, step, ())

    @pl.when(ti == n_t - 1)
    def _done():
        sfinal_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def rwkv6_scan(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               w: jnp.ndarray, u: jnp.ndarray, *, bt: int = DEFAULT_BT,
               interpret: bool = False):
    """r,k,v,w: (B,H,T,D); u: (H,D). Returns (out (B,H,T,D), state (B,H,D,D))."""
    B, H, T, D = r.shape
    bt_ = min(bt, T)
    assert T % bt_ == 0, (T, bt_)
    n_t = T // bt_

    out, sfinal = pl.pallas_call(
        functools.partial(_kernel, bt=bt_, n_t=n_t),
        grid=(B, H, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, bt_, D), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bt_, D), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bt_, D), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bt_, D), lambda b, h, t: (b, h, t, 0)),
            # u as (H, 1, D): a (1, 1, D) block's last two dims equal the
            # array's, which the TPU lowering requires ((1, D) over (H, D)
            # is refused)
            pl.BlockSpec((1, 1, D), lambda b, h, t: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bt_, D), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), r.dtype),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, D))
    return out, sfinal
