"""Per-architecture smoke tests (assignment requirement): a REDUCED config of
each family runs one forward + one train step + one decode step on CPU,
asserting output shapes and no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config
from repro.distributed import runtime
from repro.launch.mesh import make_test_mesh
from repro.models import api
from repro.train import optimizer as opt_mod
from repro.train import step as step_mod

B, T = 2, 24


def _inputs(cfg, key):
    tokens = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens,
             "mask": jnp.ones((B, T), jnp.float32)}
    if cfg.frontend_tokens:
        batch["frontend"] = jnp.ones((B, cfg.frontend_tokens, cfg.d_model),
                                     jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED + ["tinyllama-1.1b", "llama2-7b"])
def test_smoke_forward_shapes_no_nans(arch):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    batch = _inputs(cfg, jax.random.PRNGKey(1))
    logits, aux = api.forward(params, batch["tokens"], cfg,
                              frontend=batch.get("frontend"))
    assert logits.shape == (B, T, cfg.vocab_size)
    assert not np.isnan(np.asarray(logits)).any()


# the enc-dec/vision train-step smokes compile the heaviest graphs (~10s
# each); their forward and decode smokes keep covering those archs in
# tier-1, the grad-step variant rides in the slow job
_HEAVY_TRAIN_SMOKE = {"llama-3.2-vision-11b", "seamless-m4t-medium"}


@pytest.mark.parametrize(
    "arch", [pytest.param(a, marks=pytest.mark.slow)
             if a in _HEAVY_TRAIN_SMOKE else a for a in ASSIGNED])
def test_smoke_train_step(arch):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    mesh = make_test_mesh()
    optcfg = opt_mod.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    with runtime.use_mesh(mesh):
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        opt_state = opt_mod.init_state(params, optcfg)
        step = step_mod.make_train_step(cfg, optcfg, mesh, params, opt_state,
                                        donate=False)
        batch = _inputs(cfg, jax.random.PRNGKey(1))
        p2, o2, metrics = step(params, opt_state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.sum(jnp.abs(a.astype(jnp.float32)
                                                        - b.astype(jnp.float32)))),
                     params, p2))
    assert delta > 0
    assert int(o2["step"]) == 1


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_decode_step(arch):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    fe = (jnp.ones((B, cfg.frontend_tokens, cfg.d_model), jnp.float32)
          if cfg.frontend_tokens else None)
    cache = api.init_cache(cfg, B, 16, frontend=fe, params=params)
    tok = jnp.zeros((B,), jnp.int32)
    for _ in range(3):
        logits, cache = api.decode_step(params, cache, tok, cfg)
        assert logits.shape == (B, cfg.vocab_size)
        assert not np.isnan(np.asarray(logits)).any()
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    assert int(np.asarray(cache["len"])[0]) == 3


def test_decode_matches_forward_prefix():
    """Incremental decode must reproduce teacher-forced forward logits."""
    cfg = get_config("granite-8b").reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 6), 0, cfg.vocab_size)
    full_logits, _ = api.forward(params, toks, cfg)
    cache = api.init_cache(cfg, B, 8)
    for t in range(6):
        step_logits, cache = api.decode_step(params, cache, toks[:, t], cfg)
        np.testing.assert_allclose(np.asarray(step_logits),
                                   np.asarray(full_logits[:, t]),
                                   rtol=3e-2, atol=3e-2)


def test_decode_matches_forward_prefix_gemma_pattern():
    """Same equivalence through the local/global alternating + softcap path
    (ring-buffer cache correctness)."""
    cfg = get_config("gemma2-27b").reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    n = 20  # exceeds the reduced 16-wide window -> exercises the ring buffer
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, n), 0, cfg.vocab_size)
    full_logits, _ = api.forward(params, toks, cfg)
    cache = api.init_cache(cfg, B, n)
    for t in range(n):
        step_logits, cache = api.decode_step(params, cache, toks[:, t], cfg)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full_logits[:, -1]),
                               rtol=4e-2, atol=4e-2)


def test_decode_matches_forward_rwkv():
    cfg = get_config("rwkv6-7b").reduced()
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, remat="none"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, 6), 0, cfg.vocab_size)
    full_logits, _ = api.forward(params, toks, cfg)
    cache = api.init_cache(cfg, B, 8)
    for t in range(6):
        step_logits, cache = api.decode_step(params, cache, toks[:, t], cfg)
        np.testing.assert_allclose(np.asarray(step_logits),
                                   np.asarray(full_logits[:, t]),
                                   rtol=3e-2, atol=3e-2)
