"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are tested against, and they double
as the CPU/dry-run execution path of the framework (``kernels="jnp"``).
The chunked attention reference is written with ``lax.scan`` so that lowering
never materializes a (T x T) score matrix — required for the 32k-prefill
dry-run cells.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ----------------------------------------------------------------------------
# W4A8 matmul (the ITA MAC datapath)
# ----------------------------------------------------------------------------
def w4a8_matmul(qx: jnp.ndarray, x_scale: jnp.ndarray, codes: jnp.ndarray,
                w_scale: jnp.ndarray, out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """int8 activations (M,K) x int4 codes (K,N) -> scaled (M,N).

    Bit-exact int32 accumulation, then rescale by per-row activation scale
    and per-column weight scale.
    """
    # int8 operands go STRAIGHT into the dot (preferred_element_type=int32):
    # the MXU widens in the datapath, so the weights stream at 1 byte/param.
    # (Casting operands to int32 first would materialize 4-byte weights —
    # measured 4x worse than bf16 on the decode cells; §Perf H3 log.)
    acc = jax.lax.dot_general(
        qx, codes, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


# ----------------------------------------------------------------------------
# Attention
# ----------------------------------------------------------------------------
def _soft_cap(logits: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
        causal: bool = True, window: Optional[int] = None,
        softcap: Optional[float] = None, scale: Optional[float] = None,
        kv_offset: int = 0) -> jnp.ndarray:
    """Naive full-materialization attention. Oracle only — O(Tq*Tk) memory.

    q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D) with Hq % Hkv == 0 (GQA).
    ``kv_offset`` is the absolute position of q[0] minus that of k[0]
    (used for decode, where Tq=1 sits at the end of the KV cache).
    """
    B, Hq, Tq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    s = (scale if scale is not None else D ** -0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32)) * s
    logits = _soft_cap(logits, softcap)
    qpos = jnp.arange(Tq)[:, None] + kv_offset
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((Tq, k.shape[2]), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def mha_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                causal: bool = True, window: Optional[int] = None,
                softcap: Optional[float] = None, scale: Optional[float] = None,
                kv_offset: int = 0, q_chunk: int = 512, kv_chunk: int = 1024,
                skip_masked_blocks: bool = True) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure jnp (lax.scan x2).

    Memory is O(q_chunk * kv_chunk); this is the lowering-safe path for 32k
    sequences.  With ``skip_masked_blocks`` (and causal masking), fully
    masked KV blocks are skipped via ``lax.cond`` so the compiled FLOPs count
    ~T^2/2 instead of T^2 — one of the §Perf optimizations.
    """
    B, Hq, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    group = Hq // Hkv
    s = (scale if scale is not None else D ** -0.5)

    Tq_pad = (-Tq) % q_chunk
    Tk_pad = (-Tk) % kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Tq_pad), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Tk_pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Tk_pad), (0, 0)))
    nq, nk = qp.shape[2] // q_chunk, kp.shape[2] // kv_chunk
    qs = qp.reshape(B, Hq, nq, q_chunk, D).transpose(2, 0, 1, 3, 4)
    ks = kp.reshape(B, Hkv, nk, kv_chunk, D).transpose(2, 0, 1, 3, 4)
    vs = vp.reshape(B, Hkv, nk, kv_chunk, D).transpose(2, 0, 1, 3, 4)

    kv_valid = jnp.arange(kp.shape[2]) < Tk

    def q_step(_, qi_qblk):
        qi, qblk = qi_qblk
        qpos = qi * q_chunk + jnp.arange(q_chunk) + kv_offset

        def kv_step(carry, ki_kv):
            m, l, acc = carry
            ki, kblk, vblk = ki_kv
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)

            def compute(m, l, acc):
                # GQA via grouped einsum — no jnp.repeat of K/V (group x less
                # HBM traffic), and bf16 operands feed the MXU directly with
                # f32 accumulation (preferred_element_type) instead of
                # explicit converts (§Perf global optimization G1).
                B_, Hq_, qc, D_ = qblk.shape
                qg = qblk.reshape(B_, Hkv, group, qc, D_)
                logits = jnp.einsum(
                    "bhgqd,bhkd->bhgqk", qg, kblk,
                    preferred_element_type=jnp.float32) * s
                logits = _soft_cap(logits, softcap)
                msk = kv_valid[ki * kv_chunk + jnp.arange(kv_chunk)][None, :]
                if causal:
                    msk = msk & (kpos[None, :] <= qpos[:, None])
                if window is not None:
                    msk = msk & (kpos[None, :] > qpos[:, None] - window)
                logits = jnp.where(msk[None, None, None], logits, NEG_INF)
                logits = logits.reshape(B_, Hq_, qc, -1)
                m_new = jnp.maximum(m, logits.max(-1))
                p = jnp.exp(logits - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + p.sum(-1)
                pg = p.reshape(B_, Hkv, group, qc, -1).astype(vblk.dtype)
                pv = jnp.einsum("bhgqk,bhkd->bhgqd", pg, vblk,
                                preferred_element_type=jnp.float32)
                acc_new = acc * corr[..., None] + pv.reshape(B_, Hq_, qc, D_)
                return m_new, l_new, acc_new

            if causal and skip_masked_blocks:
                # whole block in the future -> skip (saves ~half the FLOPs)
                block_needed = ki * kv_chunk <= qpos[-1]
                m, l, acc = jax.lax.cond(
                    block_needed, compute, lambda m, l, acc: (m, l, acc), m, l, acc)
            else:
                m, l, acc = compute(m, l, acc)
            return (m, l, acc), None

        m0 = jnp.full((B, Hq, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hq, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hq, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, Hq, nq * q_chunk, D)
    return out[:, :, :Tq]


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     cache_len, *, window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-position attention against a (possibly padded) KV cache.

    q: (B, Hq, 1, D); caches: (B, Hkv, S, D); cache_len: (B,) valid lengths.
    """
    B, Hq, _, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    s = (scale if scale is not None else D ** -0.5)
    kr = jnp.repeat(k_cache, group, axis=1).astype(jnp.float32)
    vr = jnp.repeat(v_cache, group, axis=1).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr) * s
    logits = _soft_cap(logits, softcap)
    pos = jnp.arange(S)[None, :]
    valid = pos < cache_len[:, None]
    if window is not None:
        valid &= pos > (cache_len[:, None] - 1 - window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vr).astype(q.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, page_table: jnp.ndarray,
                           cache_len, *, window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None,
                           layer) -> jnp.ndarray:
    """Single-position attention computed THROUGH the page table.

    The gather-free oracle: a ``lax.scan`` over the page-table columns with
    flash-decode online-softmax accumulation — pages are the split-K axis.
    Each step touches one ``(B, page_size, ...)`` block of the pool, so no
    ``(B, S, ...)`` dense-view transient is ever materialized (the
    memory-wall copy ``gather_view`` + :func:`decode_attention` pays).

    q: (B, Hq, 1, D); pools: the whole stacked ``(L, num_pages,
    page_size, Hkv/f, f·D)`` pool in its stored, possibly lane-folded
    layout (``layers.kv_fold``), read at layer ``layer``; page_table: (B, P)
    physical page ids (unallocated entries may point at the scratch page —
    masked positions never contribute); cache_len: (B,) valid lengths.
    Token position t of slot b lives at
    ``(page_table[b, t // page_size], t % page_size)``.

    ``k_scale``/``v_scale`` (L, num_pages, Hkv) f32, when given, dequantize a
    QUANTIZED pool (int8/fp8 codes, DESIGN.md §13) at page-fetch time:
    each fetched page block is multiplied by its per-page, per-kv-head
    scale before entering the online softmax — the oracle for the fused
    dequant in the Pallas kernel.
    """
    B, Hq, _, D = q.shape
    ps = k_pool.shape[-3]
    Hkv = k_pool.shape[-2] * k_pool.shape[-1] // D
    P = page_table.shape[1]
    group = Hq // Hkv
    s = (scale if scale is not None else D ** -0.5)
    qg = q[:, :, 0, :].reshape(B, Hkv, group, D).astype(jnp.float32)
    cache_len = jnp.asarray(cache_len, jnp.int32)

    def page_step(carry, inputs):
        m, l, acc = carry
        pi, pid = inputs                     # page column index, (B,) phys ids
        unfold = lambda a: a.reshape(B, ps, Hkv, D).astype(jnp.float32)
        kb = unfold(k_pool[layer, pid])                 # (B, ps, Hkv, D)
        vb = unfold(v_pool[layer, pid])
        if k_scale is not None:
            kb = kb * k_scale[layer, pid][:, None, :, None]   # (B,1,Hkv,1)
        if v_scale is not None:
            vb = vb * v_scale[layer, pid][:, None, :, None]
        logits = jnp.einsum("bhgd,bshd->bhgs", qg, kb) * s
        logits = _soft_cap(logits, softcap)
        pos = pi * ps + jnp.arange(ps)                   # absolute positions
        valid = pos[None, :] < cache_len[:, None]
        if window is not None:
            valid &= pos[None, :] > (cache_len[:, None] - 1 - window)
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1))
        # all-masked-so-far rows (m_new still NEG_INF) contribute nothing:
        # exp(NEG_INF - NEG_INF) would be 1, which for a cache_len of 0
        # (every page masked) would average raw pool V rows instead of
        # returning the Pallas kernel's zeros
        live = m_new > NEG_INF
        p = jnp.where(live[..., None], jnp.exp(logits - m_new[..., None]),
                      0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        pv = jnp.einsum("bhgs,bshd->bhgd", p, vb)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, group), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, group), jnp.float32)
    a0 = jnp.zeros((B, Hkv, group, D), jnp.float32)
    # unroll the (short) page loop: straight-line per-page blocks keep the
    # transient at O(B x page_size) while avoiding the sequential while-loop
    # dispatch overhead that would otherwise lose to the one-shot gather on
    # CPU; capped so a long table doesn't blow up compile time
    (m, l, acc), _ = jax.lax.scan(
        page_step, (m0, l0, a0),
        (jnp.arange(P), page_table.T.astype(jnp.int32)),
        unroll=min(P, 16))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Hq, 1, D).astype(q.dtype)


def chunk_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, q_pos: jnp.ndarray, *,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None) -> jnp.ndarray:
    """Attention of a chunk already written into a linear KV cache.

    The chunked-prefill primitive: the chunk's own K/V sit in the cache at
    absolute positions ``q_pos`` (per query row), preceded by the cached
    prefix.  Query row r may see key slot s iff ``s <= q_pos[b, r]`` (and
    within ``window`` if set) — causal over absolute positions, so bucket
    padding rows and garbage past the written region are masked out.

    q: (B, Hq, W, D); caches: (B, Hkv, S, D) linear (non-ring) layout;
    q_pos: (B, W) absolute positions of the chunk rows.
    """
    B, Hq, W, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    s = (scale if scale is not None else D ** -0.5)
    kr = jnp.repeat(k_cache, group, axis=1).astype(jnp.float32)
    vr = jnp.repeat(v_cache, group, axis=1).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr) * s
    logits = _soft_cap(logits, softcap)
    key_pos = jnp.arange(S)[None, None, :]
    valid = key_pos <= q_pos[:, :, None]                   # (B, W, S)
    if window is not None:
        valid &= key_pos > (q_pos[:, :, None] - window)
    logits = jnp.where(valid[:, None, :, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vr).astype(q.dtype)


# ----------------------------------------------------------------------------
# RWKV6 (Finch) WKV recurrence with data-dependent decay
# ----------------------------------------------------------------------------
def rwkv6_scan(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               w: jnp.ndarray, u: jnp.ndarray,
               state: Optional[jnp.ndarray] = None):
    """RWKV6 recurrence.

    r,k,v: (B, H, T, D); w: (B, H, T, D) data-dependent decay in (0,1);
    u: (H, D) bonus. state: (B, H, D, D) mapping k-dim -> v-dim.

      S_t   = diag(w_t) S_{t-1} + k_t v_t^T
      out_t = r_t (S_{t-1} + diag(u) k_t v_t^T)

    Returns (out (B,H,T,D), final_state).
    """
    B, H, T, D = r.shape
    if state is None:
        state = jnp.zeros((B, H, D, D), jnp.float32)

    def step(S, rkvw):
        rt, kt, vt, wt = rkvw  # each (B, H, D)
        kv = kt[..., :, None] * vt[..., None, :]           # (B,H,D,D)
        out = jnp.einsum("bhk,bhkv->bhv", rt, S + u[..., :, None] * kv)
        S_new = wt[..., :, None] * S + kv
        return S_new, out

    rs = r.transpose(2, 0, 1, 3).astype(jnp.float32)
    ks = k.transpose(2, 0, 1, 3).astype(jnp.float32)
    vs = v.transpose(2, 0, 1, 3).astype(jnp.float32)
    ws = w.transpose(2, 0, 1, 3).astype(jnp.float32)
    final, outs = jax.lax.scan(step, state, (rs, ks, vs, ws))
    return outs.transpose(1, 2, 0, 3).astype(r.dtype), final


# ----------------------------------------------------------------------------
# Mamba-style selective scan (used by Hymba's SSM heads)
# ----------------------------------------------------------------------------
def selective_scan(x: jnp.ndarray, delta: jnp.ndarray, A: jnp.ndarray,
                   Bm: jnp.ndarray, Cm: jnp.ndarray,
                   state: Optional[jnp.ndarray] = None):
    """S4/Mamba selective state-space scan.

    x, delta: (B, T, D); A: (D, N); Bm, Cm: (B, T, N); state: (B, D, N).
      h_t = exp(delta_t * A) h_{t-1} + delta_t * B_t * x_t
      y_t = (h_t C_t^T)
    Returns (y (B,T,D), final_state (B,D,N)).
    """
    Bsz, T, D = x.shape
    N = A.shape[1]
    if state is None:
        state = jnp.zeros((Bsz, D, N), jnp.float32)

    dA = jnp.exp(delta[..., None] * A[None, None])                # (B,T,D,N)
    dBx = delta[..., None] * Bm[:, :, None, :] * x[..., None]     # (B,T,D,N)

    def step(h, inputs):
        dAt, dBxt, Ct = inputs
        h = dAt * h + dBxt
        y = jnp.einsum("bdn,bn->bd", h, Ct)
        return h, y

    final, ys = jax.lax.scan(
        step, state,
        (dA.transpose(1, 0, 2, 3).astype(jnp.float32),
         dBx.transpose(1, 0, 2, 3).astype(jnp.float32),
         Cm.transpose(1, 0, 2).astype(jnp.float32)))
    return ys.transpose(1, 0, 2).astype(x.dtype), final


def rwkv6_scan_chunked(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       w: jnp.ndarray, u: jnp.ndarray,
                       state: Optional[jnp.ndarray] = None, chunk: int = 64):
    """Chunked (matmul-form) RWKV6 — §Perf hillclimb H1.

    The naive recurrence materializes the (B,H,D,D) state every timestep
    (O(T) HBM round-trips of a D^2 tensor — the worst cell in the baseline
    roofline table).  This reformulation materializes state once per CHUNK
    and turns the within-chunk work into three (C,C)/(C,D) matmuls
    (MXU-friendly), exactly the GLA/flash-linear-attention trick applied to
    RWKV6's data-dependent decay:

      with A_t = sum_{j<=t} log w_j (inclusive cumsum within the chunk):
        inter_t = (r_t * e^{A_{t-1}}) . S_0
        intra_t = sum_{s<t} [(r_t e^{A_{t-1}}) . (k_s e^{-A_s})] v_s
                  + (r_t . (u * k_t)) v_t
        S_end   = diag(e^{A_C}) S_0 + sum_s (k_s e^{A_C - A_s}) v_s^T

    Exactness: algebraically identical to the recurrence; floating-point
    differences come only from exp/cumsum reassociation (validated to ~1e-4
    against the naive scan in tests/test_kernels.py).
    """
    B, H, T, D = r.shape
    C = min(chunk, T)
    assert T % C == 0, (T, C)
    n = T // C
    if state is None:
        state = jnp.zeros((B, H, D, D), jnp.float32)

    f32 = jnp.float32
    rs = r.reshape(B, H, n, C, D).transpose(2, 0, 1, 3, 4).astype(f32)
    ks = k.reshape(B, H, n, C, D).transpose(2, 0, 1, 3, 4).astype(f32)
    vs = v.reshape(B, H, n, C, D).transpose(2, 0, 1, 3, 4).astype(f32)
    logw = jnp.log(jnp.maximum(w.astype(f32), 1e-30))
    As = jnp.cumsum(logw.reshape(B, H, n, C, D), axis=3)  # inclusive, per chunk
    As = As.transpose(2, 0, 1, 3, 4)

    mask = jnp.tril(jnp.ones((C, C), bool), -1)  # strict lower: s < t

    def chunk_step(S, inputs):
        rc, kc, vc, Ac = inputs                  # (B,H,C,D)
        A_ex = Ac - logw_chunk(Ac)               # exclusive prefix
        q_t = rc * jnp.exp(A_ex)                 # (B,H,C,D)
        k_s = kc * jnp.exp(-Ac)
        inter = jnp.einsum("bhtd,bhdv->bhtv", q_t, S)
        scores = jnp.einsum("bhtd,bhsd->bhts", q_t, k_s)
        scores = jnp.where(mask[None, None], scores, 0.0)
        diag = jnp.einsum("bhtd,bhtd->bht", rc, u[None, :, None, :] * kc)
        intra = jnp.einsum("bhts,bhsv->bhtv", scores, vc) + diag[..., None] * vc
        A_last = Ac[:, :, -1:, :]                # (B,H,1,D)
        S_new = (jnp.exp(A_last[:, :, 0, :, None]) * S
                 + jnp.einsum("bhsd,bhsv->bhdv", kc * jnp.exp(A_last - Ac), vc))
        return S_new, inter + intra

    def logw_chunk(Ac):
        # recover per-step log w from the inclusive cumsum: logw_t = A_t - A_{t-1}
        return jnp.concatenate([Ac[:, :, :1], jnp.diff(Ac, axis=2)], axis=2)

    final, outs = jax.lax.scan(chunk_step, state, (rs, ks, vs, As))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, T, D)
    return out.astype(r.dtype), final


def selective_scan_assoc(x: jnp.ndarray, delta: jnp.ndarray, A: jnp.ndarray,
                         Bm: jnp.ndarray, Cm: jnp.ndarray,
                         state: Optional[jnp.ndarray] = None):
    """Associative-scan selective scan — §Perf hillclimb H5.

    The sequential form steps a (B,D,N) carry T times through a while loop
    (XLA materializes carry copies and per-step slices; measured 369 s/step
    memory term on hymba train_4k).  The recurrence h_t = a_t*h_{t-1} + b_t
    is associative under (a1,b1)∘(a2,b2) = (a1*a2, b2 + a2*b1), so
    ``jax.lax.associative_scan`` computes all h_t in ~log2(T) vectorized
    passes — no division, no log-space overflow (unlike the cumprod-ratio
    chunk form, which overflows exp(-cumsum log a) for strong decays).
    Matches ``selective_scan`` to fp tolerance (tests).
    """
    Bsz, T, D = x.shape
    N = A.shape[1]
    a = jnp.exp(delta[..., None].astype(jnp.float32) * A[None, None])  # (B,T,D,N)
    b = (delta[..., None] * Bm[:, :, None, :] * x[..., None]).astype(jnp.float32)
    if state is not None:
        # fold the incoming state into the first step: b_0 += a_0 * h_0
        b = b.at[:, 0].add(a[:, 0] * state)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, br + ar * bl

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    y = jnp.einsum("btdn,btn->btd", h, Cm.astype(jnp.float32))
    return y.astype(x.dtype), h[:, -1]
