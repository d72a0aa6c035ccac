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
    got = ref.paged_decode_attention(q, kp[None], vp[None], table, lens,
                                     window=window, softcap=softcap, layer=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)


def test_oracle_never_reads_unallocated_pages():
    """Positions past ``cache_len`` are masked, so garbage on the scratch
    page (or stale freed pages) cannot leak into live slots' outputs."""
    rng = np.random.default_rng(0)
    q, kp, vp, table, lens, dk, dv = _rand_paged(rng, 2, 4, 2, 16, 4, 4)
    lens = jnp.asarray([3, 9], jnp.int32)
    base = ref.paged_decode_attention(q, kp[None], vp[None], table, lens,
                                      layer=0)
    # poison every page beyond each slot's live prefix AND the scratch page
    poison = np.asarray(kp).copy()
    poison[0] = 1e9                                    # scratch page
    for b, ln in enumerate([3, 9]):
        for p in range(-(-ln // 4), 4):
            poison[int(table[b, p])] = 1e9
    got = ref.paged_decode_attention(q, jnp.asarray(poison)[None], vp[None],
                                     table, lens, layer=0)
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
    got = ref.paged_decode_attention(q, kq.codes[None], vq.codes[None],
                                     table, lens, window=window,
                                     softcap=softcap, k_scale=kq.scales[None],
                                     v_scale=vq.scales[None], layer=0)
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
    stack = lambda z: jax.tree.map(lambda a: a[None], z)
    want = ref.paged_decode_attention(q, kq.codes[None], vq.codes[None],
                                      table, lens, k_scale=kq.scales[None],
                                      v_scale=vq.scales[None], layer=0)
    got = ops.paged_decode_attention(q, stack(kq), stack(vq), table, lens,
                                     kernels="jnp", layer=0)
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
    args = (q, kq.codes[None], vq.codes[None], table, lens)
    kw = dict(window=window, softcap=softcap, k_scale=kq.scales[None],
              v_scale=vq.scales[None], layer=0)
    want = ref.paged_decode_attention(*args, **kw)
    got = paged_decode_attention(*args, **kw, interpret=True)
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
    args = (q, kp[None], vp[None], table, lens)
    want = ref.paged_decode_attention(*args, window=window, softcap=softcap,
                                      layer=0)
    got = paged_decode_attention(*args, window=window, softcap=softcap,
                                 layer=0, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)


# ------------------------------------ the stored pool layout (DESIGN.md §5)
def _stored(pool, fold):
    """(.., Hkv, D) -> the lane-folded stored layout (.., Hkv/f, f·D)."""
    return pool.reshape(pool.shape[:-2] + (pool.shape[-2] // fold, -1))


STORED_CASES = [
    # (Hq, Hkv, D, quant, lse): the fold is layers.kv_fold(Hkv, D)
    (8, 4, 64, False, False),     # fold 2: two heads per 128-lane row
    (8, 8, 16, False, True),      # fold 8, with the log-sum-exp output
    (8, 4, 32, True, False),      # fold 4, int8 codes and per-head scales
    (6, 3, 64, False, False),     # odd Hkv: unfoldable, plain layout
    (4, 2, 96, False, False),     # D 96 does not divide 128: plain layout
    (8, 2, 128, True, True),      # D 128: already lane-dense
]


@pytest.mark.parametrize("case", STORED_CASES,
                         ids=lambda c: "hq{}-hkv{}-d{}-q{:d}-lse{:d}".format(*c))
def test_pallas_kernel_reads_stored_stacked_pool(case):
    """The kernel reads one layer of the whole stacked pool in its stored,
    lane-folded layout (scalar-prefetched layer index; per-head lane-group
    reductions) and matches the oracle on that layer's plain pool."""
    from repro.kernels.paged_attention import paged_decode_attention
    from repro.models.layers import kv_fold
    Hq, Hkv, D, quant, lse = case
    B, ps, P, L, layer = 3, 4, 3, 3, 1
    rng = np.random.default_rng(Hkv * D)
    per_layer = [_rand_paged(rng, B, Hq, Hkv, D, ps, P) for _ in range(L)]
    q, _, _, table, lens, _, _ = per_layer[layer]
    kp = jnp.stack([c[1] for c in per_layer])
    vp = jnp.stack([c[2] for c in per_layer])
    scales = {}
    if quant:
        qk, qv = (_quantize(p.reshape((-1,) + p.shape[2:]), "int8")
                  for p in (kp, vp))
        kp, vp = (z.codes.reshape(kp.shape) for z in (qk, qv))
        scales = {"k_scale": qk.scales.reshape(L, -1, Hkv),
                  "v_scale": qv.scales.reshape(L, -1, Hkv)}
    fold = kv_fold(Hkv, D)
    want = ref.paged_decode_attention(q, kp, vp, table, lens, layer=layer,
                                      **scales)
    got = paged_decode_attention(q, _stored(kp, fold), _stored(vp, fold),
                                 table, lens, layer=jnp.int32(layer),
                                 interpret=True, return_lse=lse, **scales)
    if lse:
        got, got_lse = got
        _, want_lse = paged_decode_attention(
            q, kp[layer][None], vp[layer][None], table, lens, layer=0,
            interpret=True, return_lse=True,
            **{n: sc[layer][None] for n, sc in scales.items()})
        np.testing.assert_allclose(np.asarray(got_lse),
                                   np.asarray(want_lse), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)


def _step_cfg(num_heads, num_kv_heads, head_dim):
    cfg = get_config("stablelm-1.6b").reduced(
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        vocab_size=128, kernels="interpret")
    return dataclasses.replace(cfg, parallel=dataclasses.replace(
        cfg.parallel, remat="none", aligned_decode=False))


@pytest.mark.parametrize("heads,kv_dtype", [
    ((4, 4, 64), "bf16"),         # fold 2
    ((4, 4, 64), "int8"),         # fold 2, quantized pool
    ((6, 3, 64), "bf16"),         # odd Hkv: unfoldable
    ((4, 2, 96), "bf16"),         # D 96: unfoldable
    ((4, 2, 128), "bf16"),        # D 128: lane-dense as it is
], ids=["fold2", "fold2-int8", "odd-hkv", "d96", "d128"])
def test_paged_step_on_stored_pool_matches_dense_and_gather(heads, kv_dtype):
    """``api.paged_decode_step`` with the Pallas kernel (interpreted) over
    a pool built by ``insert_tree`` in the stored layout — carried through
    the layer scan and updated in place — emits the tokens of the dense
    ``decode_step`` on the same cache (bf16) and of the gather discipline
    (gather the dense view, ``decode_step``, scatter the token back), with
    slot 1 frozen for the first steps."""
    from repro.models.layers import kv_fold
    from repro.serve import pages, slots
    cfg = _step_cfg(*heads)
    dense_cfg = dataclasses.replace(cfg, kernels="jnp")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    B, T, ps, steps = 3, 32, 8, 6
    cache = lambda b, t: jax.eval_shape(lambda: api.init_cache(cfg, b, t))
    ba = slots.batch_axes(cache(1, T), cache(2, T))
    sa = pages.seq_axes(cache(2, T), cache(2, T + ps), ps)
    host = pages.PagePool(B * (T // ps) + 1, ps, n_slots=B,
                          slot_pages=T // ps)
    pool = pages.make_pool(cache(B, T), ba, sa, host.num_pages, ps,
                           kv_dtype=kv_dtype)
    assert pool["k"][0].shape[-2:] == (
        heads[1] // kv_fold(heads[1], heads[2]),
        heads[2] * kv_fold(heads[1], heads[2]))
    # prefill three prompts of ragged lengths into B=1 caches and insert
    rng = np.random.default_rng(sum(heads))
    dense = api.init_cache(cfg, B, T)
    toks = np.zeros((B,), np.int32)
    for b, n in enumerate((5, 11, 17)):
        prompt = rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
        one = api.init_cache(cfg, 1, T)
        _, one = api.prefill(params, one, jnp.asarray(prompt[None, :-1]),
                             dense_cfg)
        if kv_dtype != "bf16":
            one = pages.fake_quant_tree(one, n - 1, sa, ps, kv_dtype)
        assert host.try_reserve(b, T)
        host.ensure(b, T)
        pool = pages.insert_tree(pool, one, jnp.asarray(host.table[b]),
                                 jnp.int32(b), ba, sa,
                                 n_tokens=jnp.int32(n - 1))
        dense = slots.make_slot_insert(ba)(dense, one, jnp.int32(b))
        toks[b] = prompt[-1]
    table = jnp.asarray(host.table)
    step = jax.jit(lambda p, c, t, w: api.paged_decode_step(
        p, c, table, t, cfg, write=w, seq_axes=sa))

    @jax.jit
    def gather_step(p, c, t, w):
        view = pages.gather_tree(c, table, ba, sa, cfg.resolved_head_dim)
        logits, new = api.decode_step(p, view, t, dense_cfg)
        new = slots.select_slots(w, new, view, ba)
        return logits, pages.scatter_token_tree(c, new, table, view["len"],
                                                w, ba, sa)

    @jax.jit
    def dense_step(p, c, t, w):
        logits, new = api.decode_step(p, c, t, dense_cfg)
        return logits, slots.select_slots(w, new, c, ba)

    inplace, gathered, dense_toks = pool, pool, jnp.asarray(toks)
    t_in = t_ga = jnp.asarray(toks)
    for k in range(steps):
        write = jnp.asarray([True, k >= 2, True])
        lg_in, inplace = step(params, inplace, t_in, write)
        lg_ga, gathered = gather_step(params, gathered, t_ga, write)
        nxt_in = jnp.argmax(lg_in, -1).astype(jnp.int32)
        nxt_ga = jnp.argmax(lg_ga, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(nxt_in)[np.asarray(write)],
                                      np.asarray(nxt_ga)[np.asarray(write)])
        if kv_dtype == "bf16":
            lg_d, dense = dense_step(params, dense, dense_toks, write)
            nxt_d = jnp.argmax(lg_d, -1).astype(jnp.int32)
            np.testing.assert_array_equal(
                np.asarray(nxt_in)[np.asarray(write)],
                np.asarray(nxt_d)[np.asarray(write)])
            dense_toks = jnp.where(write, nxt_d, dense_toks)
        t_in = jnp.where(write, nxt_in, t_in)
        t_ga = jnp.where(write, nxt_ga, t_ga)
    # the first layer's K/V depend on the tokens alone: the in-place
    # appends hold exactly what the gather discipline wrote back
    view_in = pages.gather_tree(inplace, table, ba, sa, cfg.resolved_head_dim)
    view_ga = pages.gather_tree(gathered, table, ba, sa,
                                cfg.resolved_head_dim)
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(view_in[n][0][0]),
                                      np.asarray(view_ga[n][0][0]))
    np.testing.assert_array_equal(np.asarray(view_in["len"]),
                                  np.asarray(view_ga["len"]))


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
    # pool layouts by head shape (layers.kv_fold): two heads per lane row,
    # an odd Hkv that cannot fold, and a head_dim already 128 lanes wide
    heads = lambda name, hq, hkv, hd: dataclasses.replace(
        lm, name=name, num_heads=hq, num_kv_heads=hkv, head_dim=hd)
    fold2 = heads("lm-fold2", 4, 4, 64)
    odd = heads("lm-odd-hkv", 6, 3, 64)
    d128 = heads("lm-d128", 4, 2, 128)
    out = []
    for cfg in (lm, gemma, hymba, fold2, odd, d128):
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
    out = np.asarray(ref.paged_decode_attention(q, kp[None], vp[None],
                                                table, lens, layer=0))
    np.testing.assert_array_equal(out[0], 0.0)
    assert np.abs(out[1]).max() > 0
