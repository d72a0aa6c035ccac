"""Paged KV-cache plumbing (serve/pages.py): host allocator invariants,
sequence-axis discovery, and the gather/scatter page-table ops that the
paged slot protocol is built from."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serve import pages


# ----------------------------------------------------------------- PagePool
def test_page_pool_alloc_free_and_peak():
    pool = pages.PagePool(num_pages=9, page_size=4, n_slots=3, slot_pages=4)
    assert pool.capacity == 8                     # page 0 is scratch
    assert pool.try_reserve(0, 10)                # 3 pages worst case
    assert pool.try_reserve(1, 16)                # 4 pages
    pool.ensure(0, 5)                             # 2 pages resident
    pool.ensure(1, 16)                            # 4 pages resident
    assert pool.pages_in_use == 6
    assert pool.peak_pages_in_use == 6
    # scratch page never handed out, tables point at real pages
    assert all(p != pages.SCRATCH_PAGE for p in pool.table[0][:2])
    assert all(p == pages.SCRATCH_PAGE for p in pool.table[0][2:])
    pool.free_slot(1)
    assert pool.pages_in_use == 2
    assert pool.peak_pages_in_use == 6            # peak is sticky
    assert pool.total_reserved == 3
    # freed pages are reusable; a request longer than one slot's page
    # table is refused outright
    assert not pool.try_reserve(1, 20)            # 5 pages > slot_pages
    assert pool.try_reserve(1, 14)                # 4 pages fit again
    pool.ensure(1, 14)
    assert pool.pages_in_use == 6


def test_page_pool_reservation_admission_control():
    pool = pages.PagePool(num_pages=5, page_size=4, n_slots=2, slot_pages=4)
    assert pool.try_reserve(0, 12)                # 3 of 4 pages
    assert not pool.try_reserve(1, 8)             # 2 more would overcommit
    assert pool.try_reserve(1, 4)                 # 1 fits exactly
    pool.free_slot(0)
    assert pool.try_reserve(0, 12)                # reservation returned


def test_page_pool_rejects_degenerate_sizes():
    with pytest.raises(ValueError):
        pages.PagePool(num_pages=1, page_size=4, n_slots=1, slot_pages=1)
    with pytest.raises(ValueError):
        pages.PagePool(num_pages=5, page_size=4, n_slots=1, slot_pages=4,
                       double_free="maybe")


def test_page_pool_double_free_policy():
    """free-after-free is detected explicitly: ValueError under the default
    'raise' policy, a silent no-op under 'ignore' — and the no-op must not
    corrupt the free list (pages are returned exactly once).  Reserve-after-
    free of the SAME slot is the normal lifecycle and succeeds; a second
    reserve without a free between raises."""
    pool = pages.PagePool(num_pages=5, page_size=4, n_slots=2, slot_pages=4)
    assert pool.try_reserve(0, 8)
    pool.ensure(0, 8)
    pool.free_slot(0)
    with pytest.raises(ValueError, match="double free"):
        pool.free_slot(0)
    assert pool.try_reserve(0, 8)               # reserve-after-free: fine
    with pytest.raises(ValueError, match="already reserved"):
        pool.try_reserve(0, 4)                  # reserve-after-reserve: bug
    pool.free_slot(0)

    lax = pages.PagePool(num_pages=5, page_size=4, n_slots=2, slot_pages=4,
                         double_free="ignore")
    assert lax.try_reserve(1, 8)
    lax.ensure(1, 8)
    lax.free_slot(1)
    free_before = len(lax._free)
    lax.free_slot(1)                            # no-op by policy
    assert len(lax._free) == free_before
    assert lax.pages_in_use == 0


def test_host_pager_double_free_raises():
    pager = pages.HostPager(page_size=4, num_pages=None, max_len=16)
    pager.reset(2)
    assert pager.try_reserve(0, prompt_len=3, max_new=4)
    pager.note_insert(0, 2)
    pager.free(0)
    with pytest.raises(ValueError, match="double free"):
        pager.free(0)


def test_page_size_one_pool_boundaries():
    """ps=1 degenerate geometry: every token is its own page; worst-case
    math, ensure and free must stay exact."""
    pool = pages.PagePool(num_pages=9, page_size=1, n_slots=2, slot_pages=8)
    assert pool.pages_for(5) == 5
    assert pool.try_reserve(0, 8)               # exactly fills the pool
    pool.ensure(0, 8)
    assert pool.pages_in_use == 8
    assert not pool.try_reserve(1, 1)           # full occupancy
    pool.free_slot(0)
    assert pool.try_reserve(1, 1)
    pool.ensure(1, 1)
    assert pool.pages_in_use == 1


def test_prompt_exactly_filling_the_pool_is_admitted():
    """A request whose worst case lands EXACTLY on pool capacity (and on
    the slot's page-table length) is admitted and can grow to the last
    token; one page more is refused."""
    pager = pages.HostPager(page_size=4, num_pages=5, max_len=16)
    pager.reset(n_slots=2)                      # capacity 4 == slot_pages
    # prompt_len - 1 + max_new = 16 tokens = 4 pages = capacity
    assert pager.can_ever_admit(prompt_len=9, max_new=8)
    assert pager.try_reserve(0, prompt_len=9, max_new=8)
    pager.note_insert(0, 8)
    for _ in range(8):                          # decode to position 16
        pager.pre_decode(np.asarray([True, False]))
        pager.post_decode(np.asarray([True, False]))
    assert pager.pool.pages_in_use == 4
    assert not pager.try_reserve(1, prompt_len=2, max_new=1)
    # 17 tokens needs 5 pages: impossible even in an idle pool
    assert not pager.can_ever_admit(prompt_len=10, max_new=8)
    pager.free(0)
    assert pager.try_reserve(1, prompt_len=2, max_new=1)


def test_can_ever_admit_agrees_with_idle_try_reserve():
    """Contract under full occupancy: can_ever_admit(x) False implies
    try_reserve(x) False in EVERY pool state, and True implies try_reserve
    succeeds once the pool is idle again — the scheduler relies on exactly
    this to decide reject-now vs wait-for-frees."""
    pager = pages.HostPager(page_size=4, num_pages=7, max_len=16)
    pager.reset(n_slots=3)
    # occupy the pool fully: 16 tokens worst case across slot 0 + slot 1
    assert pager.try_reserve(0, prompt_len=9, max_new=4)   # 3 pages
    assert pager.try_reserve(1, prompt_len=9, max_new=4)   # 3 pages
    cases = [(1, 1), (2, 3), (5, 4), (9, 8), (13, 4), (2, 16), (17, 1)]
    for prompt_len, max_new in cases:
        ever = pager.can_ever_admit(prompt_len, max_new)
        now = pager.try_reserve(2, prompt_len, max_new)
        if now:
            pager.pool.free_slot(2)
        assert ever or not now, (prompt_len, max_new)   # ¬ever ⇒ ¬now
    pager.free(0)
    pager.free(1)
    for prompt_len, max_new in cases:
        ever = pager.can_ever_admit(prompt_len, max_new)
        now = pager.try_reserve(2, prompt_len, max_new)
        if now:
            pager.pool.free_slot(2)
        assert ever == now, (prompt_len, max_new)       # idle: equivalent


# ---------------------------------------------------- layout discovery
def test_seq_axes_discovery_lm_vs_recurrent():
    """KV leaves page (their S axis scales with max_len); recurrent state,
    ring buffers and ``len`` stay dense — the no-op page table."""
    lm = get_config("stablelm-1.6b").reduced()
    a = jax.eval_shape(lambda: api.init_cache(lm, 2, 16))
    b = jax.eval_shape(lambda: api.init_cache(lm, 2, 24))
    sa = pages.seq_axes(a, b, 8)
    assert all(ax == 4 for ax in jax.tree.leaves(sa["k"]))
    assert all(ax == 4 for ax in jax.tree.leaves(sa["v"]))
    assert sa["len"] == -1

    rwkv = get_config("rwkv6-7b").reduced()
    a = jax.eval_shape(lambda: api.init_cache(rwkv, 2, 16))
    b = jax.eval_shape(lambda: api.init_cache(rwkv, 2, 24))
    assert all(ax == -1 for ax in jax.tree.leaves(
        pages.seq_axes(a, b, 8)))


# ------------------------------------------------- gather / scatter ops
def _toy_pool(B=3, S=8, ps=4, extra=2, num_pages=2 * 3 * 2 + 1):
    """One leaf shaped like a small stacked KV cache: (L, B, Hkv, S, hd)
    pattern collapsed to (extra, B, S) with ba=1, sa=2.  The pool uses the
    kernel-friendly layout — page axes sit where the batch axis sat, so the
    leading (layer-like) axis stays leading: (extra, num_pages, ps)."""
    rng = np.random.default_rng(0)
    dense = rng.standard_normal((extra, B, S)).astype(np.float32)
    pool = np.zeros((extra, num_pages, ps), np.float32)
    return dense, pool


def test_insert_gather_roundtrip_and_scratch_isolation():
    ba, sa, ps = 1, 2, 4
    dense, pool = _toy_pool()
    extra, B, S = dense.shape
    P = S // ps
    host = pages.PagePool(pool.shape[1], ps, n_slots=B, slot_pages=P)
    pool = jnp.asarray(pool)
    # insert each row as a B=1 single cache with a full page table
    for b in range(B):
        assert host.try_reserve(b, S)
        host.ensure(b, S)
        single = jnp.asarray(dense[:, b:b + 1, :])
        pool = pages.insert_tree(pool, single, jnp.asarray(host.table[b]),
                                 jnp.int32(b), ba, sa)
    table = jnp.asarray(host.table)
    view = pages.gather_tree(pool, table, ba, sa, S)
    np.testing.assert_array_equal(np.asarray(view), dense)

    # scatter one token per slot at ragged positions; only active slots
    # may touch real pages — the inactive write lands on scratch
    pos = jnp.asarray([1, 5, 7], jnp.int32)
    write = jnp.asarray([True, False, True])
    new = jnp.asarray(dense + 100.0)
    pool2 = pages.scatter_token_tree(pool, new, table, pos, write, ba, sa)
    view2 = np.asarray(pages.gather_tree(pool2, table, ba, sa, S))
    expect = dense.copy()
    expect[:, 0, 1] += 100.0
    expect[:, 2, 7] += 100.0                      # slot 1 frozen (inactive)
    np.testing.assert_array_equal(view2, expect)


def test_insert_excess_logical_pages_hit_scratch_only():
    """A short prompt's insert writes its full fixed page count, but the
    excess blocks must land on the scratch page, not on other slots."""
    ba, sa, ps = 1, 2, 4
    dense, pool = _toy_pool()
    extra, B, S = dense.shape
    P = S // ps
    host = pages.PagePool(pool.shape[1], ps, n_slots=B, slot_pages=P)
    pool = jnp.asarray(pool)
    # slot 0 owns all its pages and holds known data
    assert host.try_reserve(0, S)
    host.ensure(0, S)
    pool = pages.insert_tree(pool, jnp.asarray(dense[:, 0:1]),
                             jnp.asarray(host.table[0]), jnp.int32(0),
                             ba, sa)
    before = np.asarray(pages.gather_view(pool, jnp.asarray(host.table[0:1]),
                                          ba, sa, S))
    # slot 1 inserts a 3-token prompt: 1 real page, 1 scratch block
    assert host.try_reserve(1, 3)
    host.ensure(1, 3)
    pool = pages.insert_tree(pool, jnp.asarray(dense[:, 1:2]),
                             jnp.asarray(host.table[1]), jnp.int32(1),
                             ba, sa)
    after = np.asarray(pages.gather_view(pool, jnp.asarray(host.table[0:1]),
                                         ba, sa, S))
    np.testing.assert_array_equal(after, before)
    got = np.asarray(pages.gather_view(pool, jnp.asarray(host.table[1:2]),
                                       ba, sa, S))
    np.testing.assert_array_equal(got[:, :, :ps], dense[:, 1:2, :ps])


def test_pool_byte_accounting():
    dense, pool = _toy_pool()
    extra, num_pages, ps = pool.shape
    pool = jnp.asarray(pool)
    assert pages.pool_bytes(pool, 2) == pool.nbytes
    assert pages.pool_bytes(pool, -1) == 0
    # (extra, N, ps) pool: each token position carries `extra` floats
    assert pages.page_token_bytes(pool, 2, num_pages, ps) == extra * 4
    # dense-shape accounting agrees: same KV bytes per token per slot
    dense_shape = jax.eval_shape(lambda: jnp.asarray(dense))
    assert pages.kv_token_bytes(dense_shape, 1, 2) == extra * 4
    assert pages.kv_token_bytes(dense_shape, 1, -1) == 0


def test_make_pool_kernel_friendly_layout():
    """Page axes land where the batch axis sat; leading layer/group axes
    stay leading so depth scans sweep per-layer (N, ps, *tail) slices."""
    shape = {"k": jax.ShapeDtypeStruct((5, 3, 2, 8, 4), jnp.float32),
             "len": jax.ShapeDtypeStruct((3,), jnp.int32)}
    ba = {"k": 1, "len": 0}
    sa = {"k": 3, "len": -1}
    pool = pages.make_pool(shape, ba, sa, num_pages=7, page_size=4)
    assert pool["k"].shape == (5, 7, 4, 2, 4)     # (L, N, ps, Hkv, hd)
    assert pool["len"].shape == (3,)
    assert pages.page_axis(1, 3) == 1
    assert pages.page_axis(2, 0) == 1             # seq axis before batch


# ------------------------------------------------ quantized pools (§13)
def _quant_shape(B=1, Hkv=2, S=24, hd=16, L=3):
    """KV-like leaf (L, B, Hkv, S, hd) with ba=1, sa=3 plus a dense len."""
    shape = {"k": jax.ShapeDtypeStruct((L, B, Hkv, S, hd), jnp.bfloat16),
             "len": jax.ShapeDtypeStruct((B,), jnp.int32)}
    return shape, {"k": 1, "len": 0}, {"k": 3, "len": -1}


def test_quant_pool_scale_shape_per_page_and_kv_head():
    """The scale array drops exactly the within-page and head_dim axes:
    one f32 scale per (lead, page, kv-head), rest of the layout intact."""
    shape, ba, sa = _quant_shape()
    pool = pages.make_pool(shape, ba, sa, num_pages=7, page_size=4,
                           kv_dtype="int8")
    leaf = pool["k"]
    assert isinstance(leaf, pages.QuantizedLeaf)
    assert leaf.codes.shape == (3, 7, 4, 2, 16)   # (L, N, ps, Hkv, hd)
    assert leaf.codes.dtype == jnp.int8
    assert leaf.scales.shape == (3, 7, 2)         # (L, N, Hkv)
    assert leaf.scales.dtype == jnp.float32
    assert leaf.dtype == jnp.int8 and leaf.out_dtype == "bfloat16"
    assert pool["len"].shape == (1,)              # dense leaves untouched
    # dtype-aware byte accounting: codes + scales, not the dense figure
    assert pages.pool_bytes(pool, sa) == leaf.nbytes
    dense_bytes = pages.kv_token_bytes(shape, ba, sa)
    stored = pages.kv_token_bytes_quant(shape, ba, sa, 4, "int8")
    assert stored == 3 * 2 * (16 * 1 + 4.0 / 4)   # L*Hkv*(hd + scale/ps)
    assert dense_bytes / stored >= 1.8            # the capacity headroom


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quant_insert_reconstruction_error_bounded(kv_dtype):
    """insert + gather round-trips within half a quantization step of each
    page's own scale (int8: |err| <= scale/2 elementwise)."""
    shape, ba, sa = _quant_shape()
    pool = pages.make_pool(shape, ba, sa, num_pages=7, page_size=4,
                           kv_dtype=kv_dtype)
    rng = np.random.default_rng(0)
    cache = jnp.asarray(rng.standard_normal((3, 1, 2, 24, 16)), jnp.bfloat16)
    table = jnp.arange(1, 7, dtype=jnp.int32)
    pool_k = pages.insert_tree(pool["k"], cache, table, jnp.int32(0),
                               ba["k"], sa["k"], n_tokens=jnp.int32(24))
    view = pages.gather_view(pool_k, table[None, :], ba["k"], sa["k"],
                             cache.shape[-1])
    err = jnp.abs(view.astype(jnp.float32) - cache.astype(jnp.float32))
    # per-(L, page, Hkv) bound, broadcast back over (ps, hd)
    sc = pool_k.scales[:, table]                  # (L, P, Hkv)
    sc = jnp.repeat(sc, 4, axis=1)                # (L, S, Hkv)
    bound = jnp.moveaxis(sc, 1, 2)[:, None, :, :, None]     # (L, 1, Hkv, S, 1)
    # int8: half a quantization step.  fp8 e4m3: half-ulp <= |code|/16 with
    # |code| <= 448, so 28 scale-units bounds it uniformly.
    half = 0.5 if kv_dtype == "int8" else 28.0
    assert bool(jnp.all(err <= bound * half + 1e-6))


def test_quant_fresh_page_resets_stale_scale():
    """A freed/evicted page reused by a new sequence must NOT inherit the
    old tenant's coarse scale: the off==0 append zeroes the stale scale
    and re-encodes from the fresh content alone."""
    # a one-layer stack: (L, N, ps, Hkv, hd) codes, (L, N, Hkv) scales
    codes = jnp.zeros((1, 3, 4, 2, 8), jnp.int8)
    scales = jnp.zeros((1, 3, 2), jnp.float32)
    big = 512.0 * jnp.ones((1, 2, 8), jnp.bfloat16)
    from repro.models.layers import quant_page_append

    def append(codes, scales, tok, off):
        return quant_page_append(codes, scales, tok, 0, jnp.array([1]),
                                 jnp.array([off]), "int8")
    codes, scales = append(codes, scales, big, 0)
    coarse = float(scales[0, 1, 0])
    assert coarse >= 512.0 / 127
    # page 1 is recycled: a small token appended at offset 0 starts over
    small = 0.25 * jnp.ones((1, 2, 8), jnp.bfloat16)
    codes, scales = append(codes, scales, small, 0)
    assert float(scales[0, 1, 0]) < coarse / 100
    deq = codes[0, 1, 0].astype(jnp.float32) * scales[0, 1][:, None]
    np.testing.assert_allclose(np.asarray(deq), 0.25, atol=1e-3)
    # within a page lifetime the scale is monotone: a later, larger token
    # recoarsens, an offset>0 smaller one never shrinks it
    codes, scales = append(codes, scales, big, 1)
    grown = float(scales[0, 1, 0])
    assert grown >= coarse
    codes, scales = append(codes, scales, small, 2)
    assert float(scales[0, 1, 0]) == grown


class _QuantStub(pages.PagedEngineMixin):
    """Minimal Mixin host: just enough state for apply_cow_copies and the
    _kv_bytes accounting helper."""
    def __init__(self, pager, kv_quant_tok_bytes, kv_tok_bytes):
        from repro.core.splitbrain import TrafficMeter
        self._pager = pager
        self._paging_active = True
        self.meter = TrafficMeter()
        self._kv_quant_tok_bytes = kv_quant_tok_bytes
        self._kv_tok_bytes = kv_tok_bytes
        self._kv_dtype = "int8"


def test_quant_scales_follow_pages_through_cow_copy():
    """apply_cow_copies moves codes AND scales src -> dst: the private
    copy dequantizes to exactly the shared page's values, and the metered
    copy bytes are the quantized page figure."""
    shape, ba, sa = _quant_shape()
    pool = pages.make_pool(shape, ba, sa, num_pages=7, page_size=4,
                           kv_dtype="int8")
    rng = np.random.default_rng(1)
    cache = jnp.asarray(rng.standard_normal((3, 1, 2, 24, 16)), jnp.bfloat16)
    table = jnp.arange(1, 7, dtype=jnp.int32)
    pool = dict(pool, k=pages.insert_tree(pool["k"], cache, table,
                                          jnp.int32(0), ba["k"], sa["k"],
                                          n_tokens=jnp.int32(24)))
    stored = pages.kv_token_bytes_quant(shape, ba, sa, 4, "int8")
    pager = pages.HostPager(page_size=4, num_pages=7, max_len=24)
    eng = _QuantStub(pager, stored, pages.kv_token_bytes(shape, ba, sa))
    out = eng.apply_cow_copies(pool, [(2, 5)], ba, sa)
    np.testing.assert_array_equal(np.asarray(out["k"].codes[:, 5]),
                                  np.asarray(out["k"].codes[:, 2]))
    np.testing.assert_array_equal(np.asarray(out["k"].scales[:, 5]),
                                  np.asarray(out["k"].scales[:, 2]))
    # the copy is metered in STORAGE bytes (quantized), not dense bytes
    assert eng.meter.host_channel_bytes("page_cow_copy") == \
        int(round(4 * stored))


def test_check_kv_dtype_validation():
    assert pages.check_kv_dtype("bf16", None) == "bf16"
    assert pages.check_kv_dtype("int8", 8) == "int8"
    assert pages.check_kv_dtype("fp8", 8) == "fp8"
    with pytest.raises(ValueError, match="kv_dtype"):
        pages.check_kv_dtype("int4", 8)
    with pytest.raises(ValueError, match="page_size"):
        pages.check_kv_dtype("int8", None)


# ------------------------------------- the lane-dense stored layout (§5)
@pytest.mark.parametrize("hkv,hd,stored", [
    (4, 64, (2, 128)),    # two heads share a 128-lane row
    (8, 16, (1, 128)),    # eight heads of 16
    (3, 64, (3, 64)),     # odd Hkv: no fold
    (2, 96, (2, 96)),     # 96 does not divide 128: no fold
    (2, 128, (2, 128)),   # already lane-dense
])
def test_pool_stores_heads_lane_dense(hkv, hd, stored):
    """The pool's trailing (Hkv, hd) pair is stored (Hkv/f, f*hd) with
    f = kv_fold(Hkv, hd); the scales of a quantized pool stay one per kv
    head, and the bytes per token are the dense figure either way."""
    from repro.models.layers import kv_fold
    shape, ba, sa = _quant_shape(B=2, Hkv=hkv, S=16, hd=hd)
    assert kv_fold(hkv, hd) == hkv // stored[0]
    pool = pages.make_pool(shape, ba, sa, num_pages=7, page_size=4)
    assert pool["k"].shape == (3, 7, 4) + stored
    qpool = pages.make_pool(shape, ba, sa, num_pages=7, page_size=4,
                            kv_dtype="int8")
    assert qpool["k"].codes.shape == (3, 7, 4) + stored
    assert qpool["k"].scales.shape == (3, 7, hkv)
    assert pages.page_token_bytes(pool, sa, 7, 4) == \
        pages.kv_token_bytes(shape, ba, sa)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("hkv,hd", [(4, 64), (8, 16)], ids=["fold2", "fold8"])
def test_folded_pool_insert_seed_cow_scatter_roundtrip(hkv, hd, kv_dtype):
    """On a lane-folded pool: insert scatters a B=1 cache onto the slot's
    pages, the prefix seed gathers it back (dequantized values equal the
    page quantizer's round trip), a CoW copy carries a page and its scales,
    and a decode writeback lands each token at its (page, offset) — all at
    the page axis where it sits."""
    from repro.models.layers import fake_quant_pages
    L, S, ps = 3, 16, 4
    shape, ba, sa = _quant_shape(B=2, Hkv=hkv, S=S, hd=hd, L=L)
    b1 = jax.eval_shape(lambda: jax.tree.map(
        lambda a: jnp.zeros((a.shape[0], 1) + a.shape[2:] if a.ndim > 1
                            else (1,), a.dtype), shape))
    pool = pages.make_pool(shape, ba, sa, num_pages=9, page_size=ps,
                           kv_dtype=kv_dtype)
    rng = np.random.default_rng(hkv)
    dense = jnp.asarray(rng.standard_normal((L, 1, hkv, S, hd)), jnp.bfloat16)
    want = dense if kv_dtype == "bf16" else fake_quant_pages(
        dense, 3, S, ps, kv_dtype)
    row = jnp.asarray([3, 5, 2, 8], jnp.int32)
    single = {"k": dense, "len": jnp.asarray([S], jnp.int32)}
    pool = pages.make_insert(ba, sa)(pool, single, row, jnp.int32(1),
                                     jnp.int32(S))
    seed = pages.make_seed(ba, sa, b1)(pool, row, jnp.int32(9))
    np.testing.assert_array_equal(np.asarray(seed["k"], np.float32),
                                  np.asarray(want, np.float32))
    assert int(seed["len"][0]) == 9
    # CoW: page 5 (logical 1) onto the free page 7, scales travelling along
    pool = pages.copy_pages(pool, jnp.int32(5), jnp.int32(7), ba, sa)
    moved = pages.gather_view(pool["k"], jnp.asarray([[3, 7, 2, 8]]),
                              ba["k"], sa["k"], hd)
    np.testing.assert_array_equal(np.asarray(moved, np.float32),
                                  np.asarray(want, np.float32))
    # a decode writeback of slot 1 at position 6 (page 5, offset 2); slot 0
    # is frozen, its write goes to scratch
    table = jnp.asarray([[1, 4, 6, 0], [3, 5, 2, 8]], jnp.int32)
    new_k = jnp.asarray(rng.standard_normal((L, 2, hkv, S, hd)), jnp.bfloat16)
    pos = jnp.asarray([0, 6], jnp.int32)
    out = pages.scatter_token_tree(
        pool, {"k": new_k, "len": pos}, table, pos,
        jnp.asarray([False, True]), ba, sa)
    view = pages.gather_view(out["k"], table[1:], ba["k"], sa["k"], hd)
    tok = np.asarray(view, np.float32)[:, 0, :, 6]
    ref_tok = np.asarray(new_k, np.float32)[:, 1, :, 6]
    if kv_dtype == "bf16":
        np.testing.assert_array_equal(tok, ref_tok)
        others = np.delete(np.asarray(view, np.float32), 6, axis=3)
        np.testing.assert_array_equal(
            others, np.delete(np.asarray(want, np.float32), 6, axis=3))
    else:   # requantized under the page's per-head scale: half a step
        sc = np.asarray(out["k"].scales)[:, 5]            # (L, Hkv)
        assert np.all(np.abs(tok - ref_tok) <= sc[..., None] * 0.5 + 1e-6)
