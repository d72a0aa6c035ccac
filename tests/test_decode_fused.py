"""Jit-fused decode paths: scan-over-stacked-layers SplitBrainEngine and the
fused ServeEngine prefill/generate must match their eager/stepwise references
token-for-token, with byte-identical TrafficMeter accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serve.engine import ServeEngine
from repro.serve.splitbrain_engine import SplitBrainEngine, traffic_model_for


def _lm(arch, **overrides):
    cfg = get_config(arch).reduced(vocab_size=128, **overrides)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "llama2-7b"])
def test_jit_scan_matches_eager_loop(arch):
    """The stacked-layer lax.scan decode must produce the same tokens and the
    same measured interface bytes as the pre-refactor per-layer loop."""
    cfg, params = _lm(arch)
    eng_e = SplitBrainEngine(cfg, params, max_len=16, quantize=False, jit=False)
    eng_j = SplitBrainEngine(cfg, params, max_len=16, quantize=False, jit=True)
    tok = jnp.asarray([3, 5], jnp.int32)
    cache_e, cache_j = eng_e.init_cache(2), eng_j.init_cache(2)
    for _ in range(4):
        te, le, cache_e = eng_e.decode_token(cache_e, tok)
        tj, lj, cache_j = eng_j.decode_token(cache_j, tok)
        np.testing.assert_array_equal(np.asarray(te), np.asarray(tj))
        np.testing.assert_allclose(np.asarray(le, np.float32),
                                   np.asarray(lj, np.float32),
                                   rtol=2e-2, atol=2e-2)
        tok = tj
    # byte-identical accounting: trace-time replay == runtime log
    assert eng_e.measured_bytes_per_token(2) == eng_j.measured_bytes_per_token(2)
    assert [e for e in eng_e.meter.log] == [e for e in eng_j.meter.log]
    assert eng_j.measured_bytes_per_token(2)["total"] == \
        4 * traffic_model_for(cfg).bytes_per_token()


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "llama2-7b"])
def test_fused_generate_matches_stepwise(arch):
    """One-dispatch generate == token-at-a-time eager generation."""
    cfg, params = _lm(arch)
    prompts = np.random.default_rng(0).integers(
        1, cfg.vocab_size, (2, 4)).astype(np.int32)
    eng_j = SplitBrainEngine(cfg, params, max_len=32, quantize=False, jit=True)
    eng_e = SplitBrainEngine(cfg, params, max_len=32, quantize=False, jit=False)
    out_f = eng_j.generate(prompts, max_new=6)
    out_s = eng_e.generate(prompts, max_new=6)
    np.testing.assert_array_equal(out_f["tokens"], out_s["tokens"])
    assert eng_j.measured_bytes_per_token(2) == eng_e.measured_bytes_per_token(2)


def test_pallas_device_ops_match_reference():
    """kernels="interpret" routes the quantized device projections through
    the w4a8 Pallas kernel (interpret mode on CPU) — integer path
    bit-exact."""
    cfg, params = _lm("llama2-7b")
    eng_r = SplitBrainEngine(cfg, params, max_len=16, quantize=True)
    eng_p = SplitBrainEngine(cfg, params, max_len=16, quantize=True,
                             kernels="interpret")
    tok = jnp.asarray([3, 5], jnp.int32)
    tr, lr, _ = eng_r.decode_token(eng_r.init_cache(2), tok)
    tp, lp, _ = eng_p.decode_token(eng_p.init_cache(2), tok)
    np.testing.assert_array_equal(np.asarray(tr), np.asarray(tp))
    np.testing.assert_allclose(np.asarray(lr, np.float32),
                               np.asarray(lp, np.float32), rtol=1e-3, atol=1e-3)


def test_bucketed_generate_returns_exact_cache():
    """The fused scan may run a bucketed step count past the request, but
    the returned cache must be EXACTLY the prompt+max_new state (no len
    overrun, no clamp-writes past max_len): continuing to decode from it
    matches a longer generate."""
    cfg, params = _lm("tinyllama-1.1b")
    eng = SplitBrainEngine(cfg, params, max_len=8, quantize=False)
    prompts = np.random.default_rng(5).integers(
        1, cfg.vocab_size, (1, 3)).astype(np.int32)
    out = eng.generate(prompts, max_new=5)   # step bucket 16 > max_len 8
    assert int(out["cache"]["len"][0]) == 2 + 5
    nxt, _, _ = eng.decode_token(out["cache"],
                                 jnp.asarray(out["tokens"][:, -1]))
    ref = eng.generate(prompts, max_new=6)
    assert int(nxt[0]) == int(ref["tokens"][0, 5])


def test_decode_token_donates_cache():
    """The jitted path donates the KV buffers: the returned cache is live,
    the input cache is consumed (on backends implementing donation)."""
    cfg, params = _lm("tinyllama-1.1b")
    eng = SplitBrainEngine(cfg, params, max_len=8, quantize=False)
    cache = eng.init_cache(1)
    _, _, new_cache = eng.decode_token(cache, jnp.zeros((1,), jnp.int32))
    assert new_cache["k"].shape == (cfg.num_layers, 1, cfg.num_kv_heads, 8,
                                    cfg.resolved_head_dim)
    assert int(new_cache["len"][0]) == 1


@pytest.mark.parametrize("arch", ["granite-8b", "stablelm-1.6b", "rwkv6-7b"])
def test_serve_fused_prefill_matches_stepwise(arch):
    """ServeEngine: fused prefill + one-dispatch decode loop == the legacy
    per-token loop, across the lm fast path and the scan-of-decode fallback
    (rwkv)."""
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=24)
    prompts = np.random.default_rng(1).integers(
        1, cfg.vocab_size, (3, 4)).astype(np.int32)
    out_f = eng.generate(prompts, max_new=5, fused=True)
    out_s = eng.generate(prompts, max_new=5, fused=False)
    np.testing.assert_array_equal(out_f["tokens"], out_s["tokens"])


def test_serve_prefill_single_token_prompt():
    """T0=1 prompts skip prefill entirely and still decode."""
    cfg = get_config("stablelm-1.6b").reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=16)
    out = eng.generate(np.full((2, 1), 7, np.int32), max_new=4)
    assert out["tokens"].shape == (2, 4)
