"""Multi-device distribution tests.

These run in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count
(the main test process must keep exactly 1 device — the shared runner in
conftest.py owns that boilerplate), exercising:
  * sharding-rules partitioning of a real train step on a 2x4 mesh,
  * int8-compressed gradient all-reduce vs exact psum,
  * distributed flash-decode (seq-sharded KV) vs the single-device oracle,
  * GPipe pipeline vs sequential stage application.
"""
import pytest

from conftest import run_multidev

_SCRIPT = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import runtime
    from repro.launch.mesh import make_test_mesh

    def auto_mesh(shape, names):
        return jax.make_mesh(shape, names,
                             axis_types=(jax.sharding.AxisType.Auto,) * len(shape))

    # ---------------- 1. train step partitions on a 2x4 mesh ----------------
    import dataclasses
    from repro.configs import get_config
    from repro.models import api
    from repro.train import optimizer as opt_mod, step as step_mod

    mesh = make_test_mesh(shape=(2, 4))
    cfg = get_config("granite-8b").reduced()
    cfg = dataclasses.replace(cfg, parallel=dataclasses.replace(
        cfg.parallel, remat="none", batch_axes=("data",)))
    optcfg = opt_mod.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=5)
    with runtime.use_mesh(mesh):
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        opt_state = opt_mod.init_state(params, optcfg)
        step = step_mod.make_train_step(cfg, optcfg, mesh, params, opt_state,
                                        donate=False)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks,
                 "mask": jnp.ones((4, 16), jnp.float32)}
        p2, o2, m = step(params, opt_state, batch)
        assert np.isfinite(float(m["loss"]))
        # verify a TP-ruled weight is actually sharded over "model"
        w = p2["blocks"]["mlp"]["w1"]
        assert "model" in str(w.sharding.spec), w.sharding
    print("TRAIN_STEP_OK")

    # ---------------- 2. compressed psum vs exact ----------------------------
    from repro.distributed.collectives import compressed_psum_mean
    gmesh = auto_mesh((8,), ("data",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)).astype(np.float32))

    def red(x):
        return compressed_psum_mean({"g": x}, "data")["g"]

    got = jax.shard_map(red, mesh=gmesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=False)(x)
    want = jnp.mean(x, axis=0)
    err = float(jnp.max(jnp.abs(got[0] - want)))
    scale_bound = float(jnp.max(jnp.abs(x)) / 127.0) * 2
    assert err <= scale_bound, (err, scale_bound)
    print("COMPRESSED_PSUM_OK", err)

    # ---------------- 3. distributed decode attention ------------------------
    from repro.distributed.collectives import distributed_decode_attention
    from repro.kernels import ref
    dmesh = auto_mesh((8,), ("model",))
    rng = np.random.default_rng(1)
    B, Hq, Hkv, S, D = 2, 4, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    lens = jnp.asarray([40, 64], jnp.int32)
    valid = jnp.arange(S)[None, :] < lens[:, None]
    fn = distributed_decode_attention(dmesh, "model")
    with runtime.use_mesh(dmesh):
        got = fn(q, k, v, valid)
    want = ref.decode_attention(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    print("DIST_DECODE_OK")

    # ---------------- 4. pipeline parallel vs sequential ---------------------
    from repro.distributed.pipeline import pipeline_apply
    pmesh = auto_mesh((8,), ("pipe",))
    Sstages, M, mb, dim = 8, 16, 4, 32
    Ws = jnp.asarray(rng.normal(size=(Sstages, dim, dim)).astype(np.float32) * 0.3)
    xs = jnp.asarray(rng.normal(size=(M, mb, dim)).astype(np.float32))

    piped = pipeline_apply(pmesh, lambda p, x: jnp.tanh(x @ p),
                           num_microbatches=M, axis_name="pipe")
    with runtime.use_mesh(pmesh):
        got = piped(Ws, xs)
    want = xs
    for s in range(Sstages):
        want = jnp.tanh(want @ Ws[s])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    print("PIPELINE_OK")
"""


@pytest.mark.slow
def test_multidevice_distribution():
    run_multidev(_SCRIPT, devices=8,
                 markers=("TRAIN_STEP_OK", "COMPRESSED_PSUM_OK",
                          "DIST_DECODE_OK", "PIPELINE_OK"))
