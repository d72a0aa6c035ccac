"""Production serving engine: batched generate over multiple families."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serve.engine import ServeEngine


@pytest.mark.parametrize("arch", ["granite-8b", "rwkv6-7b"])
def test_generate_batched(arch):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=24)
    prompts = np.random.default_rng(0).integers(
        1, cfg.vocab_size, (3, 4)).astype(np.int32)
    out = eng.generate(prompts, max_new=5)
    assert out["tokens"].shape == (3, 5)
    assert (out["tokens"] >= 0).all() and (out["tokens"] < cfg.vocab_size).all()
    assert out["tokens_per_s"] > 0


def test_generate_deterministic():
    cfg = get_config("stablelm-1.6b").reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=16)
    prompts = np.full((2, 3), 7, np.int32)
    a = eng.generate(prompts, max_new=4)["tokens"]
    b = eng.generate(prompts, max_new=4)["tokens"]
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a[0], a[1])  # identical prompts, greedy


def _make_engine(arch="stablelm-1.6b", max_len=64):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, ServeEngine(cfg, params, max_len=max_len)


def test_jit_caches_are_bucketed():
    """The prefill/loop jit caches are keyed by power-of-two buckets, not by
    exact prompt_len/steps: O(log max_len) compiled programs, not O(#shapes)."""
    cfg, eng = _make_engine()
    rng = np.random.default_rng(0)
    for T0 in (2, 3, 4, 5, 6, 7, 9, 12, 17):
        eng.generate(rng.integers(1, cfg.vocab_size, (1, T0)).astype(np.int32),
                     max_new=4)
    for max_new in (3, 5, 6, 9):
        eng.generate(rng.integers(1, cfg.vocab_size, (1, 4)).astype(np.int32),
                     max_new=max_new)
    sizes = eng.jit_cache_sizes()
    # prompt bodies 1..16 -> buckets {1,2,4,8,16}; steps {3,4,5,6,9} -> {4,8,16}
    assert sizes["prefill_buckets"] <= 5, sizes
    assert sizes["loop_buckets"] <= 3, sizes


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "rwkv6-7b"])
def test_bucketed_prompt_matches_exact(arch):
    """Right-padded bucketed prefill must not change a single token: prompt
    lengths landing mid-bucket equal an unpadded power-of-two prompt run."""
    cfg, eng = _make_engine(arch)
    rng = np.random.default_rng(2)
    full = rng.integers(1, cfg.vocab_size, (2, 9)).astype(np.int32)
    out_mid = eng.generate(full, max_new=5)             # body 8 -> bucket 8
    out_sub = eng.generate(full[:, :6], max_new=5)      # body 5 -> bucket 8
    # same engine, same bucket, different true_len: both must equal stepwise
    ref_mid = eng.generate(full, max_new=5, fused=False)
    ref_sub = eng.generate(full[:, :6], max_new=5, fused=False)
    np.testing.assert_array_equal(out_mid["tokens"], ref_mid["tokens"])
    np.testing.assert_array_equal(out_sub["tokens"], ref_sub["tokens"])


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "hymba-1.5b"])
@pytest.mark.parametrize("fused", [True, False])
def test_generate_eos_per_request(arch, fused):
    """Per-request stop tokens: rows pad with eos_id past their stop, gen_len
    reports exact generated length, pre-stop prefixes untouched.

    Each path is compared against its OWN no-eos probe: the fused and
    stepwise prefills have different f32 reduction orders, so their
    trajectories may split at argmax near-ties on long horizons (a property
    the max_new=5 cross-path parity tests bound) — eos must not change
    either trajectory before the stop.
    """
    cfg, eng = _make_engine(arch, max_len=32)
    rng = np.random.default_rng(3)
    prompts = rng.integers(1, cfg.vocab_size, (3, 4)).astype(np.int32)
    probe = eng.generate(prompts, max_new=8, fused=fused)
    eos = int(probe["tokens"][0, 1])   # token the model emits at step 2
    out = eng.generate(prompts, max_new=8, eos_id=eos, fused=fused)
    g0 = int(out["gen_len"][0])
    assert g0 <= 2   # the probe emits eos at step 2 (or step 1 on repeats)
    row = out["tokens"][0]
    assert row[g0 - 1] == eos and (row[g0:] == eos).all()
    assert int((out["gen_len"] < 8).sum()) >= 1
    for b in range(3):
        g = int(out["gen_len"][b])
        if g < 8:
            assert out["tokens"][b, g - 1] == eos
            assert (out["tokens"][b, g:] == eos).all()
        # the stop token must not perturb the pre-stop trajectory
        np.testing.assert_array_equal(
            out["tokens"][b, :g], probe["tokens"][b, :g])


def test_generate_batch_bucketing_pads_and_slices():
    """Odd batch sizes are padded to the next power of two internally and
    sliced back: outputs identical to the unpadded references — the
    stepwise loop and each row served alone (batch 1 needs no padding).
    The prompts hold no near-tie: the stepwise loop prefills token by
    token, and its rounding flips one-bf16-ULP argmax ties of this
    random-weight model (``default_rng(4)`` has one, in row 1)."""
    cfg, eng = _make_engine(max_len=16)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab_size, (3, 4)).astype(np.int32)
    out = eng.generate(prompts, max_new=4)
    ref = eng.generate(prompts, max_new=4, fused=False)
    rows = np.concatenate([eng.generate(prompts[i:i + 1], max_new=4)["tokens"]
                           for i in range(3)])
    assert out["tokens"].shape == (3, 4)
    np.testing.assert_array_equal(out["tokens"], ref["tokens"])
    np.testing.assert_array_equal(out["tokens"], rows)
