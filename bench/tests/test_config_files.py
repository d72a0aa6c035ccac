"""The configuration files the benchmark runs build what they built before
their mechanisms came from the file: the same ModelConfig, numbers and
page counts; and a file's ``kv_dtype`` reaches the pool and its count."""
import dataclasses
import json
from pathlib import Path

import jax
import pytest

from harness import costs, model, spec
from harness.traffic import Traffic

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ["stablelm-1.6b", "granite-8b-pp2"]
# the keys and values ``model.arch`` gave every file when it copied a
# fixed list of dense keys
DENSE_KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "vocab_size", "rope_theta")


def conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def serving(traffic):
    mix = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json").read_text())
    return Traffic(mix, 1, 1000).serving


def fixed_list_arch(c, rehearse):
    a = {k: c[k] for k in DENSE_KEYS}
    a["norm_eps"] = model.norm_eps(c)
    if rehearse:
        group = c["num_attention_heads"] // c["num_key_value_heads"]
        a.update(model.REHEARSAL_WIDTHS)
        a["num_key_value_heads"] = max(1, a["num_attention_heads"] // group)
    return a


def registry_config(c, rehearse):
    """The construction the harness used when the registry entry decided
    every mechanism: the entry with the file's dense numbers."""
    from repro.configs import get_config
    base = get_config(c["program_config"])
    a = fixed_list_arch(c, rehearse)
    return dataclasses.replace(
        base, num_layers=a["num_hidden_layers"], d_model=a["hidden_size"],
        d_ff=a["intermediate_size"], num_heads=a["num_attention_heads"],
        num_kv_heads=a["num_key_value_heads"], head_dim=a["head_dim"],
        vocab_size=a["vocab_size"], rope_theta=float(a["rope_theta"]),
        norm_eps=a["norm_eps"], dtype=c["weights"]["dtype"],
        parallel=dataclasses.replace(base.parallel, remat="none"))


@pytest.mark.parametrize("rehearse", [False, True])
@pytest.mark.parametrize("name", CONFIGS)
def test_model_config_is_the_registry_construction(name, rehearse):
    c = conf(name)
    got, want = model.model_config(c, rehearse), registry_config(c, rehearse)
    for f in dataclasses.fields(got):
        assert getattr(got, f.name) == getattr(want, f.name), f.name
        assert type(getattr(got, f.name)) is type(getattr(want, f.name)), f.name
    assert got == want


@pytest.mark.parametrize("rehearse", [False, True])
@pytest.mark.parametrize("name", CONFIGS)
def test_arch_keeps_the_dense_keys_and_values(name, rehearse):
    c = conf(name)
    a = model.arch(c, rehearse)
    want = fixed_list_arch(c, rehearse)
    assert {k: a[k] for k in want} == want
    assert not set(a) & model.BOOKKEEPING
    assert "kv_dtype" not in a


@pytest.mark.parametrize("name,traffic,pages", [
    ("stablelm-1.6b", "chat", 1641), ("granite-8b-pp2", "batch", 2967),
    ("stablelm-1.6b", "longctx", 1613)])
def test_page_counts_are_unchanged(name, traffic, pages):
    c = conf(name)
    cost = spec.costs(c)
    assert cost is costs
    assert model.num_pages(c, serving(traffic), model.model_config(c),
                           cost) == pages


def test_the_files_name_the_dense_reference():
    for name in CONFIGS:
        ref = spec.reference(conf(name))
        assert Path(ref.__file__).name == "lm.py"


def test_int8_file_counts_its_pages_by_the_int8_pool():
    """stablelm-1.6b longctx with int8 K/V: 1-byte codes and a float32 scale
    per page, layer, K or V and kv head, so a 16-token page holds
    16 * 2*24*32*64 + 2*24*32*4 = 1579008 bytes (3145728 in bf16), while
    the B=1 prefill caches stay bf16."""
    c = dict(conf("stablelm-1.6b"), kv_dtype="int8")
    a = model.arch(c, False)
    assert costs.page_token_bytes(a, 16) == 98304 + 2 * 24 * 32 * 4 / 16
    free = 16909336064 - 3288535040 - 2 * 4096 * 196608 - 600000000
    want = int(free // (1579008 * 2.25)) + 1
    assert want == 3212
    assert model.num_pages(c, serving("longctx"), model.model_config(c),
                           costs) == want


def test_int8_file_builds_an_int8_pool_of_that_size():
    """At rehearsal size: the engine gets the file's kv_dtype, its pool
    leaves are int8 codes with float32 scales, and the pool's bytes are the
    cost module's page bytes times its pages."""
    from repro.launch.mesh import make_test_mesh
    from repro.serve import pages as pages_mod
    c = dict(conf("stablelm-1.6b"), kv_dtype="int8")
    cfg = model.model_config(c, True)
    srv = dict(serving("chat"), slots=2, max_len=64, page_size=16)
    pages = model.num_pages(c, srv, cfg, costs, True)
    mesh = make_test_mesh(jax.devices()[:1])
    eng, sched = model.build_server(cfg, model.make_weights(cfg, 5, mesh),
                                    mesh, srv, pages, c["kv_dtype"])
    sched.begin()
    leaves = jax.tree.leaves(sched.cache, is_leaf=lambda x: isinstance(
        x, pages_mod.QuantizedLeaf))
    quant = [x for x in leaves if isinstance(x, pages_mod.QuantizedLeaf)]
    assert quant and all(q.codes.dtype == jax.numpy.int8 for q in quant)
    assert all(q.scales.dtype == jax.numpy.float32 for q in quant)
    a = model.arch(c, True)
    assert pages_mod.pool_bytes(sched.cache, eng._slot_seq_axes()) == \
        pages * 16 * costs.page_token_bytes(a, 16)
