"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

Each test compiles one kernel at the published widths of the model that
serves through it, for a v5e chip DESCRIBED by ``v5e:2x2`` (no chip is
attached: nothing runs), and asserts the compiled program holds the Mosaic
kernel (``tpu_custom_call``).  This catches what interpret mode cannot —
block shapes the TPU lowering refuses, operand types Mosaic rejects, VMEM
overruns — at no chip time.  The topology is described inside a fixture so
that importing this module never loads the TPU runtime.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.quant import QuantizedLeaf
from repro.distributed import runtime
from repro.kernels import (flash_attention, ops, paged_attention, rwkv_scan,
                           w4a8_matmul)

# stablelm-1.6b attention widths (configs/stablelm_1_6b.py)
HQ = HKV = 32
HEAD_DIM = 64
SLOTS, PAGES_PER_SLOT, NUM_PAGES = 8, 64, 513


@pytest.fixture(scope="module")
def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module")
def tp_mesh(topology):
    """The (1, 4) ("data", "model") serving mesh over the four chips."""
    return Mesh(np.array(topology.devices[:4]).reshape(1, 4),
                ("data", "model"))


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _named_kernel(text, name):
    """The kernel's custom call carries the ``name`` its ``pallas_call``
    gives, while its target stays ``tpu_custom_call``."""
    return re.search(rf"%{name}(\.\d+)? = .*"
                     r'custom_call_target="tpu_custom_call"', text)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8", "float8_e4m3fn"])
@pytest.mark.parametrize("page_size", [16, 32])
def test_paged_decode_compiles(one_chip, kv_dtype, page_size):
    """Flash-decode over the page pool, bf16 and quantized pools (a
    one-layer stack read at a traced layer, as the decode step passes it)."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((1, NUM_PAGES, page_size, HKV, HEAD_DIM), jnp.dtype(kv_dtype))
    args = [S((SLOTS, HQ, 1, HEAD_DIM), jnp.bfloat16), pool, pool,
            S((SLOTS, PAGES_PER_SLOT), jnp.int32), S((SLOTS,), jnp.int32),
            S((), jnp.int32)]
    if kv_dtype != "bfloat16":
        args += [S((1, NUM_PAGES, HKV), jnp.float32)] * 2

    def fn(q, k, v, table, lens, layer, ks=None, vs=None):
        return paged_attention.paged_decode_attention(
            q, k, v, table, lens, k_scale=ks, v_scale=vs, layer=layer)
    assert _named_kernel(_compiled_text(fn, *args), "paged_flash_decode")


def test_paged_decode_lse_compiles(one_chip):
    """The log-sum-exp variant the TP page-split merge runs per shard."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((1, NUM_PAGES, 16, 8, HEAD_DIM), jnp.bfloat16)

    def fn(q, k, v, table, lens):
        return paged_attention.paged_decode_attention(
            q, k, v, table, lens, return_lse=True, softcap=50.0, layer=0)
    text = _compiled_text(fn, S((SLOTS, HQ, 1, HEAD_DIM), jnp.bfloat16),
                          pool, pool, S((SLOTS, PAGES_PER_SLOT), jnp.int32),
                          S((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tq,tk", [(2048, 2048), (128, 1024)])
def test_flash_attention_compiles(one_chip, tq, tk):
    """Prefill (T=2048) and a 128-token prefill chunk over a 1024-slot
    cache at a traced offset — the chunked-prefill call."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = S((1, HQ, tq, HEAD_DIM), jnp.bfloat16)
    kv = S((1, HKV, tk, HEAD_DIM), jnp.bfloat16)

    def fn(q, k, v, off):
        return flash_attention.flash_attention(q, k, v, causal=True,
                                               kv_offset=off)
    text = _compiled_text(fn, q, kv, kv, S((1,), jnp.int32))
    assert _named_kernel(text, "flash_prefill")


def test_w4a8_matmul_compiles(one_chip):
    """stablelm-1.6b's up-projection, (8 x 2048) . (2048 x 5632)."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(w4a8_matmul.w4a8_matmul,
                          S((8, 2048), jnp.int8), S((8, 1), jnp.float32),
                          S((2048, 5632), jnp.int8), S((5632,), jnp.float32))
    assert "tpu_custom_call" in text


def test_rwkv6_scan_compiles(one_chip):
    """The WKV recurrence at rwkv6-7b widths: 64 heads of 64."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    x = S((1, 64, 256, 64), jnp.float32)
    text = _compiled_text(rwkv_scan.rwkv6_scan, x, x, x, x,
                          S((64, 64), jnp.float32))
    assert "tpu_custom_call" in text


# ---- routing through kernels/ops.py on a tensor-parallel mesh -------------
# The serving engines call ``ops`` with ``kernels="pallas"`` and
# ``model_axis="model"`` inside ``runtime.use_mesh``; these compiles check
# that ops picks the per-shard collective the head counts call for, with
# the kernel still inside it (a Mosaic call is never partitioned by XLA).
TP = 4


def _routed_text(mesh, fn, *shapes):
    rep = NamedSharding(mesh, PartitionSpec())
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep)
            for s in shapes]
    with runtime.use_mesh(mesh):
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("hkv,kv_dtype", [
    (TP, "bfloat16"), (TP, "int8"), (1, "bfloat16"), (1, "int8")],
    ids=["head_cut", "head_cut_int8", "merge", "merge_int8"])
def test_ops_paged_decode_routes_on_tp_mesh(tp_mesh, hkv, kv_dtype):
    """Hkv % tp == 0 runs the kernel on each shard's head slice of the pool
    with no reduction; Hkv < tp splits the page axis and merges partials
    with all-reduces.  Quantized pools bring their scales along."""
    B, Hq, D, ps, N, Pg = 8, 8, HEAD_DIM, 16, 64, 8
    S = jax.ShapeDtypeStruct
    pool = S((1, N, ps, hkv, D), jnp.dtype(kv_dtype))
    shapes = [S((B, Hq, 1, D), jnp.bfloat16), pool, pool,
              S((B, Pg), jnp.int32), S((B,), jnp.int32)]
    quant = kv_dtype != "bfloat16"
    if quant:
        shapes += [S((1, N, hkv), jnp.float32)] * 2

    def fn(q, k, v, table, lens, *scales):
        if quant:
            k, v = QuantizedLeaf(k, scales[0]), QuantizedLeaf(v, scales[1])
        return ops.paged_decode_attention(q, k, v, table, lens,
                                          kernels="pallas", model_axis="model",
                                          layer=0)
    text = _routed_text(tp_mesh, fn, *shapes)
    assert "tpu_custom_call" in text
    if hkv % TP == 0:
        # each shard's kernel reads its own KV head of the pool
        assert f"[1,{N},{ps},{hkv // TP},{D}]" in text
        assert "all-reduce" not in text
    else:
        assert "all-reduce" in text


@pytest.mark.parametrize("hkv,kv_dtype", [
    (2 * TP, "bfloat16"), (2 * TP, "int8"), (TP, "bfloat16")],
    ids=["head_cut", "head_cut_int8", "merge"])
def test_ops_paged_decode_routes_stacked_folded_pool_on_tp_mesh(
        tp_mesh, hkv, kv_dtype):
    """The decode step's call: the whole stacked pool in its stored layout
    (two heads of 64 per lane row) read at a traced layer.  Head rows that
    divide tp are cut with no reduction, each shard's kernel reading its
    rows of every layer; rows that do not divide take the page split."""
    B, Hq, D, ps, N, Pg, L = 8, 8, HEAD_DIM, 16, 64, 8, 3
    rows = hkv // 2
    S = jax.ShapeDtypeStruct
    pool = S((L, N, ps, rows, 2 * D), jnp.dtype(kv_dtype))
    shapes = [S((B, Hq, 1, D), jnp.bfloat16), pool, pool,
              S((B, Pg), jnp.int32), S((B,), jnp.int32), S((), jnp.int32)]
    quant = kv_dtype != "bfloat16"
    if quant:
        shapes += [S((L, N, hkv), jnp.float32)] * 2

    def fn(q, k, v, table, lens, layer, *scales):
        if quant:
            k, v = QuantizedLeaf(k, scales[0]), QuantizedLeaf(v, scales[1])
        return ops.paged_decode_attention(q, k, v, table, lens,
                                          kernels="pallas", model_axis="model",
                                          layer=layer)
    text = _routed_text(tp_mesh, fn, *shapes)
    assert _named_kernel(text, "paged_flash_decode")
    if rows % TP == 0:
        assert f"[{L},{N},{ps},{rows // TP},{2 * D}]" in text
        assert "all-reduce" not in text
    else:
        assert "all-reduce" in text


@pytest.mark.parametrize("hkv", [TP, 2], ids=["head_cut", "replicated"])
def test_ops_chunk_attention_routes_on_tp_mesh(tp_mesh, hkv):
    """A 128-token prefill chunk over a 1024-slot cache: flash attention per
    shard, on a head slice when both head counts divide tp, else on all
    heads."""
    B, Hq, W, Sk, D = 1, 8, 128, 1024, HEAD_DIM
    S = jax.ShapeDtypeStruct
    kv = S((B, hkv, Sk, D), jnp.bfloat16)

    def fn(q, k, v, q_pos):
        return ops.chunk_attention(q, k, v, q_pos, kernels="pallas",
                                   model_axis="model")
    text = _routed_text(tp_mesh, fn, S((B, Hq, W, D), jnp.bfloat16), kv, kv,
                        S((B, W), jnp.int32))
    assert "tpu_custom_call" in text
    local_heads = hkv // TP if hkv % TP == 0 else hkv
    assert f"[{B},{local_heads},{Sk},{D}]" in text


# ---- the served programs touch the page pool in place ----------------------
# The engines' decode step, admission insert and prefix seed at two layers
# of each benchmarked width set, over a 257-page pool.  A pool leaf whose
# minor dimension is not lane-dense is stored with another axis in the
# lanes, and each program that hands pages to the kernel then re-lays the
# whole pool out; a pool sliced per layer as a scan input and restacked as
# its output is copied out and back in.  Either shows up in the optimized
# HLO as a copy, transpose or (dynamic-)slice whose result has the pool's
# page count in its shape.
GUARD_PAGES, GUARD_SLOTS, GUARD_LEN, GUARD_PS = 257, 8, 512, 16
POOL_OPS = ("copy", "transpose", "dynamic-slice", "dynamic-update-slice")


def _served_programs(cfg_name, one_chip):
    import dataclasses

    from repro.configs import get_config
    from repro.models import api
    from repro.serve import pages, slots
    from repro.serve.engine import make_paged_step

    base = get_config(cfg_name)
    cfg = dataclasses.replace(
        base, num_layers=2, dtype="bfloat16", kernels="pallas",
        parallel=dataclasses.replace(base.parallel, remat="none",
                                     aligned_decode=False))
    N, ps, B, T = GUARD_PAGES, GUARD_PS, GUARD_SLOTS, GUARD_LEN
    cache = lambda b, t: jax.eval_shape(lambda: api.init_cache(cfg, b, t))
    b1 = cache(1, T)
    ba = slots.batch_axes(b1, cache(2, T))
    sa = pages.seq_axes(cache(2, T), cache(2, T + ps), ps)
    on_chip = lambda a, dt=None: jax.ShapeDtypeStruct(
        a.shape, dt or a.dtype, sharding=one_chip)
    pool = jax.tree.map(on_chip, pages.pool_shape(cache(B, T), ba, sa, N, ps))
    params = jax.tree.map(
        lambda a: on_chip(a, jnp.bfloat16),
        jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0))))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    flags = jax.ShapeDtypeStruct((B,), bool, sharding=one_chip)
    row = i32(T // ps)
    return pool, {
        "paged_step": (jax.jit(make_paged_step(cfg, ba, sa),
                               donate_argnums=(1,)),
                       (params, pool, i32(B, T // ps), i32(B), flags, flags)),
        "insert": (jax.jit(pages.make_insert(ba, sa), donate_argnums=(0,)),
                   (pool, jax.tree.map(on_chip, b1), row, i32(), i32())),
        "seed": (jax.jit(pages.make_seed(ba, sa, b1)), (pool, row, i32())),
    }


@pytest.mark.parametrize("program", ["paged_step", "insert", "seed"])
@pytest.mark.parametrize("cfg_name,stored", [
    ("stablelm-1.6b", (16, 128)),     # Hkv 32 x D 64: two heads per lane row
    ("granite-8b", (8, 128)),         # Hkv 8 x D 128: already lane-dense
], ids=["stablelm", "granite"])
def test_served_programs_touch_pool_in_place(one_chip, cfg_name, stored,
                                             program):
    pool, progs = _served_programs(cfg_name, one_chip)
    leaf = pool["k"][0]
    assert leaf.shape[-2:] == stored, leaf.shape
    fn, args = progs[program]
    text = fn.lower(*args).compile().as_text()
    n = GUARD_PAGES
    pool_sized = re.compile(
        rf"^\s*(?:ROOT )?%\S+ = \w+\[[^\]]*\b{n}\b[^\]]*\]\S* "
        rf"({'|'.join(POOL_OPS)})\(")
    offenders = [ln.strip()[:160] for ln in text.splitlines()
                 if pool_sized.match(ln)]
    assert not offenders, offenders
    # the pool parameters are taken as stored: row-major, minor dim last
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->",
                      text).group(1)
    layouts = re.findall(rf"\w+\[([\d,]*\b{n}\b[\d,]*)\]\{{([\d,]+)", entry)
    assert layouts, entry[:400]
    for dims, layout in layouts:
        rank = len(dims.split(","))
        assert layout == ",".join(str(i) for i in range(rank - 1, -1, -1)), (
            dims, layout)
    if program == "paged_step":
        # the kernel reads the whole stacked pool at the folded block: its
        # two layers as one run of 2·N pages, a bitcast of the stored pool
        call = _named_kernel(text, "paged_flash_decode")
        assert call
        merged = ",".join(str(d) for d in (2 * n, GUARD_PS) + stored)
        line = text[call.start():text.index("\n", call.start())]
        assert f"[{merged}]" in line, line[:600]
        operands = re.search(r"custom-call\(([^)]*)\)", line).group(1)
        assert "%bitcast" in operands, line[:600]
