"""Tensor-parallel mesh-sharded serving (DESIGN.md §11).

Subprocess multi-device tests (forced host devices, see conftest.run_multidev):
for each slot-servable family the masked decode step runs on a (1, tp) mesh
and must produce TOKEN-IDENTICAL greedy output to the 1-device engine, with
byte-identical traffic totals (per-shard entries sum exactly) and ZERO
steady-state recompiles.  Also exercises the TP paged-attention kernel
dispatch: the head-cut grid (Hkv % tp == 0, no collective) and the
page-split + LSE-merge fallback (Hkv < tp).
"""
import pytest

from conftest import run_multidev

_SCRIPT = """
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.models import api
    from repro.serve import slots as slots_mod
    from repro.serve.engine import ServeEngine
    from repro.serve.splitbrain_engine import SplitBrainEngine

    TP = {tp}
    STEPS = 8
    assert jax.device_count() == TP, jax.devices()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 127, size=n).astype(np.int32) for n in (7, 12)]

    def slot_run(eng):
        # admit -> prefill -> insert -> masked decode loop (slot protocol)
        cache = eng.init_slot_cache(2)
        toks = np.zeros((2,), np.int32)
        for i, p in enumerate(prompts):
            assert eng.reserve_slot(i, len(p), STEPS + 2)
            c1, tok = eng.prefill_slot(p)
            cache = eng.insert_slot(cache, c1, i)
            toks[i] = tok
        active = np.array([True, True])
        outs, c0 = [], None
        for k in range(STEPS):
            if k == 2:   # steps 0-1 may compile; after that: never again
                c0 = slots_mod.CompileCounter.instance().count
            nxt, ok, cache = eng.decode_slots(cache, toks, active)
            assert bool(np.asarray(ok).all()), "finite-logits sentinel"
            eng.meter_tokens(2)
            toks = np.asarray(nxt)
            outs.append(toks.copy())
        recompiles = slots_mod.CompileCounter.instance().count - c0
        if hasattr(eng, "measured_bytes_per_token"):
            nbytes = eng.measured_bytes_per_token()
        else:
            nbytes = eng.measured_bytes()
        return np.stack(outs), nbytes, eng.cache_stats(cache), recompiles

    def check_family(name, ctor, kv_shards=None):
        base = ctor(make_test_mesh(devices=jax.devices()[:1]))
        o1, b1, _, r1 = slot_run(base)
        eng = ctor(make_test_mesh(shape=(1, TP)))
        o2, b2, stats, r2 = slot_run(eng)
        assert np.array_equal(o1, o2), (name, o1, o2)
        assert b1 == b2, (name, b1, b2)   # per-shard entries sum exactly
        assert r1 == 0 and r2 == 0, (name, r1, r2)
        if kv_shards is not None:
            assert stats["kv_shards"] == kv_shards, (name, stats)
        print("FAMILY_{{}}_OK kv_shards={{}} traffic_shards={{}}".format(
            name, stats.get("kv_shards"), eng.traffic_shards))

    def serve(cfg):
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        return lambda mesh: ServeEngine(cfg, params, mesh=mesh, max_len=48,
                                        page_size=8, paged_attn="inplace")

    # llama2 reduced: Hkv=4 — the pool head-cuts at every tested tp
    lm_cfg = get_config("llama2-7b").reduced(vocab_size=128)
    check_family("lm", serve(lm_cfg), kv_shards=TP)
    # gemma2 reduced: GQA Hkv=2 — replicates at tp=4 (fallback), parity holds
    check_family("gemma2", serve(get_config("gemma2-27b").reduced(
        vocab_size=128)))
    check_family("hymba", serve(get_config("hymba-1.5b").reduced(
        vocab_size=128)))
    check_family("rwkv", serve(get_config("rwkv6-7b").reduced(
        vocab_size=128)))

    sb_cfg = get_config("llama2-7b").reduced(vocab_size=128)
    sb_params = api.init_params(sb_cfg, jax.random.PRNGKey(1))
    check_family("splitbrain",
                 lambda mesh: SplitBrainEngine(sb_cfg, sb_params, max_len=48,
                                               page_size=8,
                                               paged_attn="inplace",
                                               mesh=mesh),
                 kv_shards=TP)

    # ---- TP paged-attention kernels (interpret-mode Pallas) ----------------
    # The per-shard collectives are driven with the concrete mesh: the
    # interpreter's host callbacks do not partition under the abstract mesh
    # a traced serve step sees (compiled kernels do: the routing through ops
    # is compiled for a described four-chip mesh in test_tpu_compile.py and
    # run by the chip smoke test's --tp path).
    from repro.distributed import collectives
    from repro.kernels import paged_attention as _pa
    mesh = make_test_mesh(shape=(1, TP))

    def kernel_case(Hq, Hkv, name, quant=False):
        B, D, ps, N, Pg = 3, 16, 8, 12, 4
        q = jnp.asarray(rng.standard_normal((B, Hq, 1, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((N, ps, Hkv, D)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((N, ps, Hkv, D)), jnp.float32)
        table = jnp.asarray(
            rng.permutation(N)[: B * Pg].reshape(B, Pg), jnp.int32)
        lens = jnp.asarray([1, 9, 30], jnp.int32)
        scales = dict()
        if quant:   # int8 codes, per-page per-kv-head scales
            kp, vp = (jnp.asarray(rng.integers(-127, 128, (N, ps, Hkv, D)),
                                  jnp.int8) for _ in range(2))
            scales = dict((n, jnp.asarray(rng.uniform(0.01, 0.1, (N, Hkv)),
                                          jnp.float32))
                          for n in ("k_scale", "v_scale"))
        # one-layer stacks: the pool (and its scales) as the step passes it
        kp, vp = kp[None], vp[None]
        scales = {{n: sc[None] for n, sc in scales.items()}}
        want = _pa.paged_decode_attention(q, kp, vp, table, lens, softcap=2.0,
                                          layer=0, interpret=True, **scales)
        build = (collectives.tp_paged_decode_attention if Hkv % TP == 0
                 else collectives.tp_paged_decode_attention_merge)
        fn = build(mesh, "model", softcap=2.0, interpret=True, quant=quant)
        got = fn(q, kp, vp, table, lens, jnp.int32(0),
                 *(scales[n] for n in ("k_scale", "v_scale") if quant))
        err = float(jnp.max(jnp.abs(want - got)))
        assert err < 1e-5, (name, err)

    kernel_case(4, TP, "head_cut")   # Hkv % tp == 0: per-shard grid
    kernel_case(4, 1, "merge")       # Hkv < tp: page split + LSE merge
    kernel_case(4, TP, "head_cut_int8", quant=True)  # scales cut on heads
    kernel_case(4, 1, "merge_int8", quant=True)

    from repro.kernels import flash_attention as _fa

    def flash_case(Hq, Hkv, name):   # chunked prefill at a nonzero start
        B, W, S, D = 1, 16, 64, 16
        q = jnp.asarray(rng.standard_normal((B, Hq, W, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        off = jnp.asarray([32], jnp.int32)
        want = _fa.flash_attention(q, k, v, kv_offset=off, interpret=True)
        fn = collectives.tp_flash_attention(
            mesh, "model", interpret=True,
            cut_heads=Hq % TP == 0 and Hkv % TP == 0)
        err = float(jnp.max(jnp.abs(want - fn(q, k, v, off))))
        assert err < 1e-5, (name, err)

    flash_case(4, TP, "flash_head_cut")
    flash_case(4, 1, "flash_replicated")
    print("KERNEL_TP_OK")
    print("MESH_SERVE_OK")
"""

FAMILY_MARKERS = [f"FAMILY_{n}_OK" for n in
                  ("lm", "gemma2", "hymba", "rwkv", "splitbrain")]


@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 4], ids=["tp2", "tp4"])
def test_mesh_serve_token_parity(tp):
    run_multidev(_SCRIPT.format(tp=tp), devices=tp,
                 markers=FAMILY_MARKERS + ["KERNEL_TP_OK", "MESH_SERVE_OK"],
                 timeout=1800)


_FOLDED_SCRIPT = """
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import get_config
    from repro.distributed import collectives
    from repro.kernels import paged_attention as _pa
    from repro.launch.mesh import make_test_mesh
    from repro.models import api
    from repro.models.layers import kv_fold
    from repro.serve.engine import ServeEngine

    TP, KV_DTYPE = {tp}, {kv_dtype!r}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 127, size=n).astype(np.int32) for n in (7, 12)]
    # head_dim 64, Hkv 4: at tp 2 two heads per lane row, two rows -> one
    # per shard; at tp 4 the fold gives way to the head cut, one head each
    cfg = get_config("llama2-7b").reduced(vocab_size=128, num_heads=4,
                                          num_kv_heads=4, head_dim=64)
    assert kv_fold(4, 64, TP) == (2 if TP == 2 else 1)
    params = api.init_params(cfg, jax.random.PRNGKey(0))

    def run(mesh):
        eng = ServeEngine(cfg, params, mesh=mesh, max_len=48, page_size=8,
                          kv_dtype=KV_DTYPE)
        cache = eng.init_slot_cache(2)
        toks = np.zeros((2,), np.int32)
        for i, p in enumerate(prompts):
            assert eng.reserve_slot(i, len(p), 10)
            c1, toks[i] = eng.prefill_slot(p)
            cache = eng.insert_slot(cache, c1, i)
        outs = []
        for _ in range(6):
            nxt, ok, cache = eng.decode_slots(cache, toks,
                                              np.array([True, True]))
            toks = np.asarray(nxt)
            outs.append(toks.copy())
        return np.stack(outs), eng.cache_stats(cache)

    o1, _ = run(make_test_mesh(devices=jax.devices()[:1]))
    o2, stats = run(make_test_mesh(shape=(1, TP)))
    assert np.array_equal(o1, o2), (o1, o2)
    assert stats["kv_shards"] == TP, stats
    print("ENGINE_FOLDED_TP_OK")

    # the per-shard kernels on a stacked, folded pool read at a layer
    mesh = make_test_mesh(shape=(1, TP))
    L, B, D, ps, N, Pg = 3, 3, 64, 8, 12, 4
    for hkv in (4, 2):
        f = kv_fold(hkv, D, TP)
        build = (collectives.tp_paged_decode_attention if (hkv // f) % TP == 0
                 else collectives.tp_paged_decode_attention_merge)
        q = jnp.asarray(rng.standard_normal((B, 4, 1, D)), jnp.float32)
        kp, vp = (jnp.asarray(rng.standard_normal((L, N, ps, hkv // f, f * D)),
                              jnp.float32) for _ in range(2))
        scales = ()
        if KV_DTYPE != "bf16":
            kp, vp = (jnp.round(p * 20).astype(jnp.int8) for p in (kp, vp))
            scales = tuple(jnp.asarray(rng.uniform(0.01, 0.1, (L, N, hkv)),
                                       jnp.float32) for _ in range(2))
        table = jnp.asarray(rng.permutation(N)[:B * Pg].reshape(B, Pg),
                            jnp.int32)
        lens = jnp.asarray([1, 9, 30], jnp.int32)
        layer = jnp.int32(1)
        kw = dict(zip(("k_scale", "v_scale"), scales))
        want = _pa.paged_decode_attention(q, kp, vp, table, lens, layer=layer,
                                          interpret=True, **kw)
        fn = build(mesh, "model", interpret=True, quant=bool(scales))
        got = fn(q, kp, vp, table, lens, layer, *scales)
        err = float(jnp.max(jnp.abs(want - got)))
        assert err < 1e-5, (hkv, err)
    print("KERNEL_FOLDED_TP_OK")
"""


@pytest.mark.parametrize("tp,kv_dtype", [
    pytest.param(2, "bf16", id="bf16"), pytest.param(2, "int8", id="int8"),
    pytest.param(4, "bf16", id="tp4-bf16")])
def test_folded_pool_tp_token_parity(tp, kv_dtype):
    """A lane-folded pool (head_dim 64, two heads per lane row) cut on its
    head rows over a (1, 2) mesh serves the tokens of one device, and the
    per-shard kernels read a layer of the stacked, folded pool: the head
    cut without a collective, the page-split merge where the rows do not
    divide.  At tp 4 the fold never takes the head cut away: Hkv 4 stays
    unfolded and cuts four ways (``kv_shards == 4``), not replicated."""
    run_multidev(_FOLDED_SCRIPT.format(tp=tp, kv_dtype=kv_dtype), devices=tp,
                 markers=["ENGINE_FOLDED_TP_OK", "KERNEL_FOLDED_TP_OK"],
                 timeout=900)
