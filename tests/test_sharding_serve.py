"""Serve-path sharding rules (DESIGN.md §11): head-cut KV slot caches and
page pools across the slot-servable families.

Pure PartitionSpec unit tests — TP degrees > 1 are exercised against an
``AbstractMesh`` (no extra devices needed), so this file is tier-1.  The
multi-device execution parity lives in tests/test_mesh_serve.py.
"""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_test_mesh
from repro.models import api
from repro.serve import pages as pages_mod
from repro.serve import slots as slots_mod

MAX_LEN, PS = 32, 8

# one config per slot-cache family: paged KV (lm), ring window (gemma2),
# KV + SSM recurrent mix (hymba), pure recurrent wkv state (rwkv)
FAMILIES = ["llama2-7b", "gemma2-27b", "hymba-1.5b", "rwkv6-7b"]


def tp_mesh(tp: int) -> AbstractMesh:
    return AbstractMesh((1, tp), ("data", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    cfg = get_config(request.param).reduced(vocab_size=128)
    cache = jax.eval_shape(lambda: api.init_cache(cfg, 2, MAX_LEN))
    grown = jax.eval_shape(lambda: api.init_cache(cfg, 2, MAX_LEN + PS))
    b1 = jax.eval_shape(lambda: api.init_cache(cfg, 1, MAX_LEN))
    ba = slots_mod.batch_axes(b1, cache)
    sa = pages_mod.seq_axes(cache, grown, PS)
    return cfg, cache, ba, sa


def _leaves_with_paths(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


@pytest.mark.parametrize("tp", [2, 4])
def test_indivisible_dims_never_shard(family, tp):
    """_fit drops any axis whose size does not divide the dim: every
    'model' occurrence in a serve/pool spec must divide exactly."""
    cfg, cache, ba, sa = family
    mesh = tp_mesh(tp)
    specs = shd.serve_cache_pspecs(cache, cfg, mesh)
    pshape = pages_mod.pool_shape(cache, ba, sa, num_pages=16, page_size=PS)
    pool_specs = shd.pool_pspecs(pshape, cfg, mesh, sa)
    for tree, shapes in ((specs, cache), (pool_specs, pshape)):
        for (path, spec), (_, leaf) in zip(_leaves_with_paths(tree),
                                           _leaves_with_paths(shapes)):
            for i, axis in enumerate(tuple(spec)):
                if axis == "model":
                    assert leaf.shape[i] % tp == 0, (path, spec, leaf.shape)


def test_lm_kv_head_cut_and_fallback():
    """llama2 reduced has Hkv=2: tp=2 cuts the KV head axis, tp=4 (which
    does not divide it) replicates — the Hkv < tp fallback is the rules
    engine itself, not a special case."""
    cfg = get_config("llama2-7b").reduced(vocab_size=128, num_kv_heads=2)
    assert cfg.num_kv_heads == 2
    cache = jax.eval_shape(lambda: api.init_cache(cfg, 2, MAX_LEN))
    kv = [(p, s) for p, s in _leaves_with_paths(
        shd.serve_cache_pspecs(cfg=cfg, mesh=tp_mesh(2), cache=cache))
        if shd._path_str(p).split("/")[-1] in ("k", "v")
        or shd._path_str(p).split("/")[-2:-1] in (["k"], ["v"])]
    assert kv, "no KV leaves found"
    assert all("model" in tuple(s) for _, s in kv), kv
    kv4 = _leaves_with_paths(
        shd.serve_cache_pspecs(cfg=cfg, mesh=tp_mesh(4), cache=cache))
    assert all("model" not in tuple(s) for _, s in kv4)


@pytest.mark.parametrize("name,leaf_suffix,overrides", [
    # rwkv heads are d_model/64: widen so the head axis is tp-divisible
    ("rwkv6-7b", "wkv", {"d_model": 128}),   # (L, B, H, D, D): heads cut
    ("hymba-1.5b", "ssm", {}),               # (L, B, d, N): inner dim cut
])
def test_recurrent_state_cuts_on_model(name, leaf_suffix, overrides):
    cfg = get_config(name).reduced(vocab_size=128, **overrides)
    cache = jax.eval_shape(lambda: api.init_cache(cfg, 2, MAX_LEN))
    specs = shd.serve_cache_pspecs(cache, cfg, tp_mesh(2))
    hits = [(shd._path_str(p), s) for p, s in _leaves_with_paths(specs)
            if shd._path_str(p).endswith(leaf_suffix)]
    assert hits, f"no {leaf_suffix} leaves in {name} cache"
    for path, spec in hits:
        assert "model" in tuple(spec), (path, spec)


def test_pool_leaves_cut_on_kv_heads(family):
    """Paged leaves (s_ax >= 0) take the pool layout rule — 'model' lands
    on the Hkv axis (ndim-2) — while non-paging leaves keep serve rules."""
    cfg, cache, ba, sa = family
    mesh = tp_mesh(2)
    pshape = pages_mod.pool_shape(cache, ba, sa, num_pages=16, page_size=PS)
    specs = shd.pool_pspecs(pshape, cfg, mesh, sa)
    for (path, spec), (_, leaf), (_, s_ax) in zip(
            _leaves_with_paths(specs), _leaves_with_paths(pshape),
            _leaves_with_paths(sa)):
        if s_ax >= 0 and "model" in tuple(spec):
            assert tuple(spec)[leaf.ndim - 2] == "model", (path, spec)


def test_pool_kv_cut():
    cfg = get_config("llama2-7b").reduced(vocab_size=128, num_kv_heads=2)
    cache = jax.eval_shape(lambda: api.init_cache(cfg, 2, MAX_LEN))
    b1 = jax.eval_shape(lambda: api.init_cache(cfg, 1, MAX_LEN))
    grown = jax.eval_shape(lambda: api.init_cache(cfg, 2, MAX_LEN + PS))
    ba = slots_mod.batch_axes(b1, cache)
    sa = pages_mod.seq_axes(cache, grown, PS)
    pshape = pages_mod.pool_shape(cache, ba, sa, num_pages=16, page_size=PS)
    for tp, want in ((1, 1), (2, 2), (4, 1)):   # Hkv=2
        specs = shd.pool_pspecs(pshape, cfg, tp_mesh(tp), sa)
        assert shd.pool_kv_cut(specs, sa, tp, "model") == want, tp
    # an Hkv=2 cut at tp=2 halves the per-shard token bytes exactly
    full = pages_mod.kv_token_bytes(cache, ba, sa)
    assert pages_mod.kv_token_bytes(cache, ba, sa, kv_shards=2) == full // 2
    with pytest.raises(ValueError):
        pages_mod.kv_token_bytes(cache, ba, sa, kv_shards=3)


def test_one_device_mesh_placements_work(family):
    """The 1-device test mesh must accept every serve placement (specs may
    name size-1 axes; that is still a valid, trivially-replicated layout)."""
    cfg, cache, ba, sa = family
    mesh = make_test_mesh()
    sh = shd.with_sharding(mesh, shd.serve_cache_pspecs(cache, cfg, mesh))
    zeros = jax.tree.map(lambda a, s: jax.device_put(
        np.zeros(a.shape, a.dtype), s), cache, sh)
    for leaf in jax.tree.leaves(zeros):
        assert leaf.sharding.mesh == mesh


def test_mesh_shape_validation():
    """launch.mesh refuses shapes that do not factor the device count and
    says how to fix it (satellite: explicit (dp, tp) validation)."""
    from repro.launch import mesh as mesh_mod
    with pytest.raises(ValueError, match=r"dp\*tp|devices"):
        mesh_mod.make_test_mesh(shape=(3, 5))
    with pytest.raises(ValueError):
        mesh_mod.make_test_mesh(shape=(0, 1))
    m = mesh_mod.make_test_mesh(shape=(1, 1))
    assert m.axis_names == ("data", "model")
