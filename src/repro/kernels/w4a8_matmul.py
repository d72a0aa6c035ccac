"""Pallas TPU kernel: fused INT4-dequant matmul — the ITA MAC, TPU-native.

Paper §IV-C hardwires INT4 weights into shift-add trees so no weight ever
crosses a memory hierarchy.  The TPU analogue (DESIGN.md §2): keep weights as
INT4 codes in HBM (4x less traffic than bf16), stream each (bk, bn) tile into
VMEM **once**, dequantize in-register, and feed the MXU directly.  The
activation side is INT8 with per-row scales, matching the paper's W4A8
datapath; accumulation is exact int32 on the integer path.

Grid: (M/bm, N/bn, K/bk), K innermost so the fp32 scratch accumulator in
VMEM is revisited; the output tile is written once on the final K step.
Block shapes are MXU-aligned (multiples of 128 on the contracting/lane dims).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 256, 256, 512

def _kernel(x_ref, xs_ref, w_ref, ws_ref, o_ref, acc_ref, *, n_k: int):
    """One (bm, bn) output tile; accumulate over the K grid dimension."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]          # (bm, bk) int8
    w = w_ref[...]          # (bk, bn) int8 (int4 codes)
    # int8 x int4-codes-in-int8 -> int32, exact on the MXU (Mosaic takes
    # integer dots only as int8 operands with an int32 result)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32,
    ).astype(jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _done():
        xs = xs_ref[...]    # (bm, 1) f32 activation scales
        ws = ws_ref[...]    # (1, bn) f32 weight scales
        o_ref[...] = (acc_ref[...] * xs * ws).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def w4a8_matmul(qx: jnp.ndarray, x_scale: jnp.ndarray, codes: jnp.ndarray,
                w_scale: jnp.ndarray, *, bm: int = DEFAULT_BM,
                bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                out_dtype=jnp.bfloat16, interpret: bool = False) -> jnp.ndarray:
    """qx (M,K) int8, x_scale (M,1) f32, codes (K,N) int8, w_scale (N,) f32."""
    M, K = qx.shape
    _, N = codes.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk
    ws2d = w_scale.reshape(1, N)

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qx, x_scale, codes, ws2d)
