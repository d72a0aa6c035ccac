"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv_scan import rwkv6_scan
from repro.kernels.w4a8_matmul import w4a8_matmul


# ----------------------------------------------------------------- w4a8 matmul
@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 512, 384, 128, 128, 128),
    (64, 256, 128, 64, 64, 256),     # single K step vs multi
    (512, 256, 256, 256, 128, 64),
])
@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_w4a8_matches_oracle(M, K, N, bm, bn, bk, out_dtype):
    rng = np.random.default_rng(M + K + N)
    qx = jnp.asarray(rng.integers(-127, 128, (M, K)).astype(np.int8))
    xs = jnp.asarray(rng.uniform(0.01, 0.1, (M, 1)).astype(np.float32))
    codes = jnp.asarray(rng.integers(-7, 8, (K, N)).astype(np.int8))
    ws = jnp.asarray(rng.uniform(0.01, 0.1, (N,)).astype(np.float32))
    got = w4a8_matmul(qx, xs, codes, ws, bm=bm, bn=bn, bk=bk,
                      out_dtype=out_dtype, interpret=True)
    want = ref.w4a8_matmul(qx, xs, codes, ws, out_dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2 if out_dtype == jnp.bfloat16 else 1e-6)


def test_w4a8_integer_path_bit_exact():
    """int32 accumulation must be exact — the hardware-equivalence claim."""
    rng = np.random.default_rng(7)
    qx = jnp.asarray(rng.integers(-127, 128, (128, 256)).astype(np.int8))
    codes = jnp.asarray(rng.integers(-7, 8, (256, 128)).astype(np.int8))
    ones_m = jnp.ones((128, 1), jnp.float32)
    ones_n = jnp.ones((128,), jnp.float32)
    got = w4a8_matmul(qx, ones_m, codes, ones_n, bm=64, bn=64, bk=64,
                      out_dtype=jnp.float32, interpret=True)
    want = np.asarray(qx, np.int64) @ np.asarray(codes, np.int64)
    np.testing.assert_array_equal(np.asarray(got).astype(np.int64), want)


# ------------------------------------------------------------- flash attention
ATTN_CASES = [
    dict(B=2, Hq=4, Hkv=2, Tq=128, Tk=128, D=64, causal=True),
    dict(B=1, Hq=8, Hkv=2, Tq=96, Tk=96, D=32, causal=True, window=48),
    dict(B=2, Hq=4, Hkv=4, Tq=64, Tk=64, D=64, causal=True, softcap=30.0),
    dict(B=1, Hq=4, Hkv=1, Tq=64, Tk=128, D=64, causal=False),
    dict(B=1, Hq=2, Hkv=2, Tq=80, Tk=80, D=16, causal=True),  # ragged blocks
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_naive(case, dtype):
    c = dict(case)
    B, Hq, Hkv, Tq, Tk, D = (c.pop(k) for k in ("B", "Hq", "Hkv", "Tq", "Tk", "D"))
    rng = np.random.default_rng(Tq + Tk)
    q = jnp.asarray(rng.normal(size=(B, Hq, Tq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Tk, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Tk, D)), dtype)
    kvo = Tk - Tq if c.get("causal") else 0
    want = ref.mha(q, k, v, kv_offset=kvo, **c)
    got = flash_attention(q, k, v, kv_offset=kvo, bq=32, bk=32,
                          interpret=True, **c)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("start", [0, 32, 96])
@pytest.mark.parametrize("Hq,Hkv,window", [(4, 2, None), (4, 4, 48)])
def test_chunk_attention_kernel_matches_ref(start, Hq, Hkv, window):
    """Chunked prefill through the flash kernel: a 32-row chunk at a TRACED
    start attends causally over a 128-slot linear cache (garbage past the
    chunk must stay masked) — one compiled program for every start."""
    from repro.kernels import ops
    rng = np.random.default_rng(start + Hkv)
    B, W, S, D = 2, 32, 128, 16
    q = jnp.asarray(rng.normal(size=(B, Hq, W, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    q_pos = start + jnp.arange(W)[None, :].repeat(B, 0)
    want = ref.chunk_attention(q, kc, vc, q_pos, window=window)
    got = jax.jit(lambda *a: ops.chunk_attention(
        *a, window=window, kernels="interpret"))(q, kc, vc, q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("case", ATTN_CASES)
def test_chunked_ref_matches_naive(case):
    c = dict(case)
    B, Hq, Hkv, Tq, Tk, D = (c.pop(k) for k in ("B", "Hq", "Hkv", "Tq", "Tk", "D"))
    rng = np.random.default_rng(Tq)
    q = jnp.asarray(rng.normal(size=(B, Hq, Tq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, Tk, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, Tk, D)).astype(np.float32))
    kvo = Tk - Tq if c.get("causal") else 0
    want = ref.mha(q, k, v, kv_offset=kvo, **c)
    got = ref.mha_chunked(q, k, v, kv_offset=kvo, q_chunk=32, kv_chunk=32, **c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_decode_attention_matches_full():
    rng = np.random.default_rng(0)
    B, Hq, Hkv, S, D = 3, 8, 2, 64, 32
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)).astype(np.float32))
    kc = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    lens = jnp.asarray([10, 64, 33], jnp.int32)
    got = ref.decode_attention(q, kc, vc, lens)
    for b in range(B):
        L = int(lens[b])
        want = ref.mha(q[b:b + 1], kc[b:b + 1, :, :L], vc[b:b + 1, :, :L],
                       causal=True, kv_offset=L - 1)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want[0]),
                                   atol=2e-5)


# ----------------------------------------------------------------- rwkv kernel
@pytest.mark.parametrize("B,H,T,D,bt", [
    (2, 3, 64, 16, 16),
    (1, 2, 128, 32, 64),
    (1, 1, 32, 64, 32),
])
def test_rwkv_kernel_matches_ref(B, H, T, D, bt):
    rng = np.random.default_rng(B * T)
    r, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.8, 0.999, (B, H, T, D)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(H, D)).astype(np.float32))
    want_o, want_s = ref.rwkv6_scan(r, k, v, w, u)
    got_o, got_s = rwkv6_scan(r, k, v, w, u, bt=bt, interpret=True)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=1e-4)


def test_rwkv_ref_state_continuation():
    """Processing [t0:t1] then [t1:t2] with carried state == full scan."""
    rng = np.random.default_rng(5)
    B, H, T, D = 1, 2, 32, 8
    r, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.9, 0.999, (B, H, T, D)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(H, D)).astype(np.float32))
    full, _ = ref.rwkv6_scan(r, k, v, w, u)
    h = T // 2
    o1, s1 = ref.rwkv6_scan(r[:, :, :h], k[:, :, :h], v[:, :, :h], w[:, :, :h], u)
    o2, _ = ref.rwkv6_scan(r[:, :, h:], k[:, :, h:], v[:, :, h:], w[:, :, h:], u, state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 2)),
                               np.asarray(full), atol=1e-4)


def test_selective_scan_state_continuation():
    rng = np.random.default_rng(6)
    B, T, D, N = 2, 24, 8, 4
    x = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
    delta = jnp.asarray(rng.uniform(0.01, 0.5, (B, T, D)).astype(np.float32))
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (D, N)).astype(np.float32))
    Bm = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    Cm = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    full, _ = ref.selective_scan(x, delta, A, Bm, Cm)
    h = T // 2
    y1, s1 = ref.selective_scan(x[:, :h], delta[:, :h], A, Bm[:, :h], Cm[:, :h])
    y2, _ = ref.selective_scan(x[:, h:], delta[:, h:], A, Bm[:, h:], Cm[:, h:], state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(full), atol=1e-5)
