"""Gather-free paged decode attention (DESIGN.md §6).

Three layers of parity, each against the previous verified path:

  * the jnp scan-over-pages oracle (``ref.paged_decode_attention``) vs
    gathering the dense view and running dense ``ref.decode_attention`` —
    swept over page_size (1 / odd / 8), ragged cache lengths, GQA groups,
    sliding window, softcap  [tier-1],
  * the Pallas kernel (TPU interpreter on CPU) vs the oracle  [slow],
  * the in-place paged engines (``paged_attn="inplace"``) vs the PR-3
    gather discipline, token-identical through the continuous-batching
    scheduler across lm / mixed-window lm / hymba mixes and the
    split-brain engine  [tier-1],

plus the live-page KV-read accounting the in-place path exists for.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import LayerSpec
from repro.kernels import ref
from repro.models import api
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousBatchingScheduler, Request
from repro.serve.splitbrain_engine import SplitBrainEngine

MAX_NEW = 6
PROMPT_LENS = (1, 3, 5, 9, 4)


def _rand_paged(rng, B, Hq, Hkv, D, ps, P):
    """Random pool + per-slot tables (distinct pages, page 0 = scratch) +
    ragged lens, and the dense view gathered through the table."""
    N = B * P + 1
    q = jnp.asarray(rng.standard_normal((B, Hq, 1, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((N, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((N, ps, Hkv, D)), jnp.float32)
    # disjoint pages per slot, like the real allocator (page 0 = scratch)
    table = rng.permutation(np.arange(1, N))[:B * P].reshape(B, P)
    table = table.astype(np.int32)
    lens = rng.integers(1, P * ps + 1, (B,)).astype(np.int32)
    dense_k = jnp.asarray(np.asarray(kp)[table].reshape(B, P * ps, Hkv, D)
                          .transpose(0, 2, 1, 3))
    dense_v = jnp.asarray(np.asarray(vp)[table].reshape(B, P * ps, Hkv, D)
                          .transpose(0, 2, 1, 3))
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lens), dense_k, dense_v


CASES = [
    # (B, Hq, Hkv, D, ps, P, window, softcap)
    (3, 4, 2, 16, 8, 4, None, None),     # GQA, the serve default page size
    (2, 4, 4, 8, 1, 7, None, None),      # page_size=1: one token per page
    (3, 6, 2, 16, 3, 5, None, None),     # odd page size
    (2, 4, 1, 16, 8, 3, None, None),     # MQA (group = Hq)
    (3, 4, 2, 16, 4, 4, 5, None),        # sliding window
    (2, 4, 2, 16, 3, 4, 7, 30.0),        # window + softcap together
    (2, 8, 2, 32, 8, 2, None, 50.0),     # softcap (gemma2 style)
]


@pytest.mark.parametrize("case", CASES)
def test_oracle_matches_gather_plus_dense(case):
    """scan-over-pages online softmax == gather_view + dense softmax."""
    B, Hq, Hkv, D, ps, P, window, softcap = case
    rng = np.random.default_rng(hash(case) % 2**32)
    q, kp, vp, table, lens, dk, dv = _rand_paged(rng, B, Hq, Hkv, D, ps, P)
    want = ref.decode_attention(q, dk, dv, lens, window=window,
                                softcap=softcap)
    got = ref.paged_decode_attention(q, kp, vp, table, lens, window=window,
                                     softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)


def test_oracle_never_reads_unallocated_pages():
    """Positions past ``cache_len`` are masked, so garbage on the scratch
    page (or stale freed pages) cannot leak into live slots' outputs."""
    rng = np.random.default_rng(0)
    q, kp, vp, table, lens, dk, dv = _rand_paged(rng, 2, 4, 2, 16, 4, 4)
    lens = jnp.asarray([3, 9], jnp.int32)
    base = ref.paged_decode_attention(q, kp, vp, table, lens)
    # poison every page beyond each slot's live prefix AND the scratch page
    poison = np.asarray(kp).copy()
    poison[0] = 1e9                                    # scratch page
    for b, ln in enumerate([3, 9]):
        for p in range(-(-ln // 4), 4):
            poison[int(table[b, p])] = 1e9
    got = ref.paged_decode_attention(q, jnp.asarray(poison), vp, table, lens)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


# -------------------------------------------- quantized pools (DESIGN.md §13)
def _quantize(pool, kv_dtype):
    """Reference whole-pool quantizer: per-page, per-kv-head pow2 scales."""
    from repro.core.quant import QuantizedLeaf
    from repro.models.layers import kv_pow2_scale, kv_quantize
    amax = jnp.max(jnp.abs(pool), axis=(1, 3))
    sc = kv_pow2_scale(amax, kv_dtype)
    codes = kv_quantize(pool, sc[:, None, :, None], kv_dtype)
    return QuantizedLeaf(codes, sc, kv_dtype, "float32")


@pytest.mark.parametrize("case", CASES)
def test_quant_oracle_matches_dequantized_dense(case):
    """ref oracle with k_scale/v_scale == dense softmax over the explicitly
    dequantized view: fused dequant changes where the multiply happens,
    not the math."""
    B, Hq, Hkv, D, ps, P, window, softcap = case
    rng = np.random.default_rng(hash(case) % 2**32)
    q, kp, vp, table, lens, _, _ = _rand_paged(rng, B, Hq, Hkv, D, ps, P)
    kq, vq = _quantize(kp, "int8"), _quantize(vp, "int8")
    deq = lambda z: (z.codes.astype(jnp.float32)
                     * z.scales[:, None, :, None])
    dk = jnp.asarray(np.asarray(deq(kq))[np.asarray(table)]
                     .reshape(B, P * ps, Hkv, D).transpose(0, 2, 1, 3))
    dv = jnp.asarray(np.asarray(deq(vq))[np.asarray(table)]
                     .reshape(B, P * ps, Hkv, D).transpose(0, 2, 1, 3))
    want = ref.decode_attention(q, dk, dv, lens, window=window,
                                softcap=softcap)
    got = ref.paged_decode_attention(q, kq.codes, vq.codes, table, lens,
                                     window=window, softcap=softcap,
                                     k_scale=kq.scales, v_scale=vq.scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quant_ops_dispatch_unpacks_quantized_leaf(kv_dtype):
    """ops.paged_decode_attention accepts QuantizedLeaf pools directly and
    routes the scales to whichever backend runs."""
    from repro.kernels import ops
    rng = np.random.default_rng(7)
    q, kp, vp, table, lens, _, _ = _rand_paged(rng, 2, 4, 2, 16, 8, 3)
    kq, vq = _quantize(kp, kv_dtype), _quantize(vp, kv_dtype)
    want = ref.paged_decode_attention(q, kq.codes, vq.codes, table, lens,
                                      k_scale=kq.scales, v_scale=vq.scales)
    got = ops.paged_decode_attention(q, kq, vq, table, lens, kernels="jnp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
@pytest.mark.parametrize("case", CASES)
def test_quant_pallas_kernel_matches_oracle(case):
    """The fused-dequant Pallas kernel (per-page scale blocks fetched
    through the page table, multiplied into scores and value sums) vs the
    scaled oracle — page sizes
    {1, odd, 8}, GQA/MQA, window, softcap, all on int8 pools."""
    from repro.kernels.paged_attention import paged_decode_attention
    B, Hq, Hkv, D, ps, P, window, softcap = case
    rng = np.random.default_rng(hash(case) % 2**32)
    q, kp, vp, table, lens, _, _ = _rand_paged(rng, B, Hq, Hkv, D, ps, P)
    kq, vq = _quantize(kp, "int8"), _quantize(vp, "int8")
    want = ref.paged_decode_attention(q, kq.codes, vq.codes, table, lens,
                                      window=window, softcap=softcap,
                                      k_scale=kq.scales, v_scale=vq.scales)
    got = paged_decode_attention(q, kq.codes, vq.codes, table, lens,
                                 window=window, softcap=softcap,
                                 k_scale=kq.scales, v_scale=vq.scales,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("case", CASES)
def test_pallas_kernel_matches_oracle(case):
    """The Pallas flash-decode kernel (TPU interpreter on CPU): scalar-
    prefetched page-table index maps, pl.when page skipping, online-softmax
    scratch accumulation — vs the jnp oracle."""
    from repro.kernels.paged_attention import paged_decode_attention
    B, Hq, Hkv, D, ps, P, window, softcap = case
    rng = np.random.default_rng(hash(case) % 2**32)
    q, kp, vp, table, lens, dk, dv = _rand_paged(rng, B, Hq, Hkv, D, ps, P)
    want = ref.paged_decode_attention(q, kp, vp, table, lens, window=window,
                                      softcap=softcap)
    got = paged_decode_attention(q, kp, vp, table, lens, window=window,
                                 softcap=softcap, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)


# --------------------------------------------------------- engine parity
def _serve_engine(cfg, paged_attn, max_len=32, page_size=8, num_pages=None):
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, max_len=max_len, page_size=page_size,
                       num_pages=num_pages, paged_attn=paged_attn)


def _mix_cfgs():
    lm = get_config("stablelm-1.6b").reduced()
    # gemma2: local ring slots stay dense, global slots page — the mixed
    # pattern exercises the "which leaves stay on the gather fallback" rule
    gemma = get_config("gemma2-27b").reduced()
    # hymba with global attention: paged K/V + dense SSM state in ONE step
    hymba = get_config("hymba-1.5b").reduced(
        layer_pattern=(LayerSpec(window=None),))
    out = []
    for cfg in (lm, gemma, hymba):
        out.append(dataclasses.replace(
            cfg, parallel=dataclasses.replace(cfg.parallel, remat="none")))
    return out


@pytest.mark.parametrize("cfg", _mix_cfgs(), ids=lambda c: c.name)
def test_inplace_matches_gather_through_scheduler(cfg):
    """paged_attn='inplace' (attention through the page table, no dense
    view) is token-identical to the PR-3 gather discipline under the
    continuous-batching scheduler, chunked prefill included."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, (t,)).astype(np.int32)
               for t in PROMPT_LENS]
    reqs = [Request(uid=i, prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(prompts)]
    outs = {}
    for mode in ("gather", "inplace"):
        eng = _serve_engine(cfg, mode)
        sched = ContinuousBatchingScheduler(eng, max_slots=2,
                                            prefill_chunk=4)
        res = sched.run([dataclasses.replace(r) for r in reqs])
        assert not res["rejected"]
        assert eng._paging_active, "mix config was expected to page"
        outs[mode] = res["results"]
    for g, i in zip(outs["gather"], outs["inplace"]):
        assert g.uid == i.uid
        np.testing.assert_array_equal(g.tokens, i.tokens)
        assert g.gen_len == i.gen_len


def test_splitbrain_inplace_matches_gather():
    """Same parity for the split-brain engine's stacked (L, ...) pools."""
    cfg = get_config("tinyllama-1.1b").reduced(vocab_size=128)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, (t,)).astype(np.int32)
               for t in (2, 9, 3, 6)]
    outs = {}
    for mode in ("gather", "inplace"):
        eng = SplitBrainEngine(cfg, params, max_len=32, quantize=False,
                               page_size=8, num_pages=9, paged_attn=mode)
        sched = ContinuousBatchingScheduler(eng, max_slots=2,
                                            prefill_chunk=4)
        res = sched.run([Request(uid=i, prompt=p, max_new=5)
                         for i, p in enumerate(prompts)])
        outs[mode] = res["results"]
    for g, i in zip(outs["gather"], outs["inplace"]):
        np.testing.assert_array_equal(g.tokens, i.tokens)


def test_invalid_paged_attn_mode_rejected():
    cfg = get_config("stablelm-1.6b").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged_attn"):
        ServeEngine(cfg, params, max_len=32, page_size=8,
                    paged_attn="dense")


# --------------------------------------------- live-page KV-read accounting
def test_kv_read_accounting_counts_live_pages_only():
    """The meter's host_read channel: the gather discipline reads the full
    max_slots x max_len dense view every step; the in-place discipline
    reads only live pages of active slots — strictly fewer bytes on short
    sequences — and neither perturbs the eq. 7-10 boundary accounting."""
    cfg = _mix_cfgs()[0]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, (t,)).astype(np.int32)
               for t in PROMPT_LENS]
    reqs = [Request(uid=i, prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(prompts)]
    reads, boundary = {}, {}
    for mode in ("gather", "inplace"):
        eng = _serve_engine(cfg, mode)
        sched = ContinuousBatchingScheduler(eng, max_slots=2)
        res = sched.run([dataclasses.replace(r) for r in reqs])
        reads[mode] = (eng.meter.host_read_bytes, res["steps"],
                       eng._kv_tok_bytes)
        boundary[mode] = eng.measured_bytes()["total"]
    gb, steps, tok_bytes = reads["gather"]
    # gather: every step materializes (and reads) the whole dense view
    assert gb == steps * 2 * 32 * tok_bytes          # max_slots x max_len
    # in-place: strictly less — only live pages of active slots
    assert 0 < reads["inplace"][0] < gb
    # host reads live OUTSIDE the boundary log: eq. 7-10 bytes unchanged
    assert boundary["gather"] == boundary["inplace"] > 0


def test_gather_transient_metric():
    """gather_transient_bytes_per_step: the per-dispatch dense-view copy —
    nonzero for the gather discipline, ZERO for in-place (the serve_bench
    regression gate), zero for layouts that never page."""
    cfg = _mix_cfgs()[0]
    for mode, expect_zero in (("gather", False), ("inplace", True)):
        eng = _serve_engine(cfg, mode)
        eng.init_slot_cache(2)
        t = eng.gather_transient_bytes_per_step()
        assert (t == 0) == expect_zero, (mode, t)
        if not expect_zero:
            assert t == 2 * 32 * eng._kv_tok_bytes
    # rwkv: nothing pages, dense fallback, no transient in either mode
    rcfg = get_config("rwkv6-7b").reduced()
    params = api.init_params(rcfg, jax.random.PRNGKey(0))
    eng = ServeEngine(rcfg, params, max_len=32, page_size=8,
                      paged_attn="gather")
    eng.init_slot_cache(2)
    assert not eng._paging_active
    assert eng.gather_transient_bytes_per_step() == 0


def test_inplace_refuses_seq_sharded_decode():
    """ops.paged_decode_attention has no dist_axis variant: an in-place
    paged engine on a decode_attn='shard_map' config must refuse loudly
    WHEN PAGING ENGAGES instead of silently dropping the sharding (gather
    remains available, and never-paging families keep their dense
    fallback)."""
    def shard_map_cfg(name):
        cfg = get_config(name).reduced()
        return dataclasses.replace(
            cfg, parallel=dataclasses.replace(cfg.parallel,
                                              decode_attn="shard_map"))

    cfg = shard_map_cfg("stablelm-1.6b")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=32, page_size=8)
    with pytest.raises(ValueError, match="shard_map"):
        eng.init_slot_cache(2)
    gat = ServeEngine(cfg, params, max_len=32, page_size=8,
                      paged_attn="gather")
    gat.init_slot_cache(2)
    # a never-paging family with the same flags keeps its dense fallback
    rcfg = shard_map_cfg("rwkv6-7b")
    rparams = api.init_params(rcfg, jax.random.PRNGKey(0))
    reng = ServeEngine(rcfg, rparams, max_len=32, page_size=8)
    reng.init_slot_cache(2)
    assert not reng._paging_active


def test_zero_length_slot_returns_zeros():
    """cache_len == 0 masks every position: the oracle must return zeros
    (as the Pallas kernel's page-skip does), not an average of pool rows."""
    rng = np.random.default_rng(3)
    q, kp, vp, table, lens, dk, dv = _rand_paged(rng, 2, 4, 2, 16, 4, 4)
    lens = jnp.asarray([0, 9], jnp.int32)
    out = np.asarray(ref.paged_decode_attention(q, kp, vp, table, lens))
    np.testing.assert_array_equal(out[0], 0.0)
    assert np.abs(out[1]).max() > 0
