"""Sharding-rules engine: pytree paths -> PartitionSpecs on (pod, data, model).

Strategy (DESIGN.md §4):
  * batch            -> ("pod", "data")     (DP across pods and within)
  * TP (heads/ffn)   -> "model"             (Megatron column/row pattern)
  * EP (experts)     -> "model"
  * FSDP (ZeRO-3)    -> "data"              (weights/opt-state sharded; XLA
                                             inserts all-gather at use)
  * decode KV seq    -> "model"             (context parallelism for caches)

Every rule is *shape-checked*: an axis is only applied when the dim is
divisible by the mesh axis size (e.g. 4 KV heads never shard over 16-way
"model"; a batch of 1 never shards).  This keeps one rule set valid for all
10 archs x 4 shapes.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.quant import QuantizedLeaf

# param-name -> logical spec on the trailing dims (stacked leading dims get None)
_COL = ("fsdp", "model")     # (d_in, out): out split over TP
_ROW = ("model", "fsdp")     # (in, d_out): in split over TP
_PARAM_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    # EP: experts over "model"; the per-expert matrices FSDP-shard over "data"
    # (both expert dims on "model" would double-map the axis)
    (r".*moe/w[13]$", ("expert", "fsdp", None)),
    (r".*moe/w2$", ("expert", None, "fsdp")),
    (r".*moe/router$", ("fsdp", None)),
    (r".*/(wq|wk|wv|w1|w3|cm_k|w_in|w_delta|wg|wr|w_lora_a|w_B|w_C)$", _COL),
    (r".*/(wo|w2|cm_v|w_out|w_delta_up|w_lora_b)$", _ROW),
    (r".*/A_log$", ("model", None)),
    (r"^embed$", ("model", "fsdp")),
    (r"^lm_head$", ("fsdp", "model")),
    # split-brain stacked weights name the unembedding "head" (not lm_head)
    (r"(^|.*/)head$", ("fsdp", "model")),
    (r".*/u$", (None, None)),
)

# cache-entry rules keyed by leaf name; trailing-dim specs (leading dims None-padded)
_CACHE_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    # transformer/encdec KV: (..., B, kv_heads, S, hd)
    (r".*(^|/)(k|v|cross_k|cross_v)(/\d+)?$", ("batch", None, "seq", None)),
    (r".*wkv$", ("batch", "model", None, None)),      # rwkv state (L,B,H,D,D)
    (r".*x_(tm|cm)$", ("batch", "model")),             # rwkv shift state (L,B,d)
    (r".*ssm$", ("batch", "model", None)),             # hymba ssm (L,B,d,N)
    (r".*len$", ("batch",)),
)

# Serve-path slot-cache rules (DESIGN.md §11): the TP serving mesh cuts the
# KV cache on HEADS, not sequence — each model shard owns the Hkv/tp heads
# its column-sharded wk/wv produce, so decode attention needs no KV
# collective at all.  Shape-checking (`_fit`) still applies: an indivisible
# Hkv (or head count) silently replicates, which IS the Hkv < tp fallback.
_SERVE_CACHE_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    # dense / ring KV: (..., B, kv_heads, S|W, hd) — heads over "model"
    (r".*(^|/)(k|v|cross_k|cross_v)(/\d+)?$", ("batch", "model", None, None)),
    (r".*wkv$", ("batch", "model", None, None)),      # rwkv state (L,B,H,D,D)
    (r".*x_(tm|cm)$", ("batch", "model")),             # rwkv shift state (L,B,d)
    (r".*ssm$", ("batch", "model", None)),             # hymba ssm (L,B,d,N)
    (r".*len$", ("batch",)),
)

# Page-pool leaf rules: trailing (num_pages, page_size, Hkv/f, f*hd) — the
# stored, possibly lane-folded layout (layers.kv_fold) — the pool is cut on
# its head rows so each shard owns a (N, ps, Hkv/(f*tp), f*hd) slice, i.e.
# Hkv/tp contiguous kv heads, and the paged flash-decode grid is unchanged
# per shard.  Page ids stay global
# (tables replicated), so HostPager/CoW/prefix logic needs no distribution
# awareness.
_POOL_CACHE_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    (r".*(^|/)(k|v|cross_k|cross_v)(/\d+)?$", (None, None, "model", None)),
)


class MeshAxes:
    """Resolve logical axes against a concrete mesh."""

    def __init__(self, mesh: Mesh, cfg: ModelConfig):
        names = mesh.axis_names
        self.mesh = mesh
        self.batch: Tuple[str, ...] = tuple(
            a for a in cfg.parallel.batch_axes if a in names)
        self.model: Optional[str] = (
            cfg.parallel.model_axis if cfg.parallel.model_axis in names else None)
        self.fsdp: Optional[str] = (
            cfg.parallel.fsdp_axis if (cfg.parallel.fsdp_axis or "") in names else None)
        self.seq: Optional[str] = (
            cfg.parallel.seq_axis if (cfg.parallel.seq_axis or "") in names else None)

    def resolve(self, logical: Optional[str]):
        return {
            None: None,
            "batch": self.batch if self.batch else None,
            "model": self.model,
            "expert": self.model,
            "fsdp": self.fsdp,
            "seq": self.seq,
        }[logical]

    def size(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        return int(np.prod([self.mesh.shape[a] for a in axes]))


def _fit(spec_tail: Tuple[Optional[str], ...], shape: Tuple[int, ...],
         ax: MeshAxes) -> P:
    """Pad spec to ndim and drop axes that don't divide the dim."""
    ndim = len(shape)
    tail = list(spec_tail[-ndim:]) if len(spec_tail) > ndim else list(spec_tail)
    full = [None] * (ndim - len(tail)) + tail
    out = []
    for dim, logical in zip(shape, full):
        resolved = ax.resolve(logical)
        if resolved is None or dim % ax.size(resolved) != 0:
            out.append(None)
        else:
            out.append(resolved)
    return P(*out)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def _match(rules, key: str):
    for pattern, spec in rules:
        if re.match(pattern, key):
            return spec
    return None


def _param_pspecs_impl(params, cfg: ModelConfig, mesh: Mesh, transform=None):
    ax = MeshAxes(mesh, cfg)

    def spec(path, leaf):
        key = _path_str(path)
        # QuantizedLinear leaves: codes shard like the weight; scales like out dim
        key = re.sub(r"/(codes)$", "", key)
        is_scales = key.endswith("/scales")
        key = re.sub(r"/scales$", "", key)
        # optimizer moment trees mirror params under m/ and v/ prefixes
        key = re.sub(r"^(m|v)/", "", key)
        key = re.sub(r"/(q|scale)$", "", key)  # int8 moment codec leaves
        matched = _match(_PARAM_RULES, key)
        if matched is None:
            return P()
        if is_scales:
            matched = matched[-1:]  # per-out-channel scales
        if not hasattr(leaf, "shape"):
            return P()
        if transform is not None:
            matched = transform(matched)
        return _fit(matched, leaf.shape, ax)

    return jax.tree_util.tree_map_with_path(spec, params)


def param_pspecs(params, cfg: ModelConfig, mesh: Mesh):
    """PartitionSpec pytree for params (works for raw or LAQ-quantized trees
    and for AdamW moment trees that mirror them)."""
    return _param_pspecs_impl(params, cfg, mesh)


def serve_param_pspecs(params, cfg: ModelConfig, mesh: Mesh):
    """Column-ONLY tensor parallelism for FLOAT serving params
    (DESIGN.md §11): the model axis survives only on a weight's OUTPUT
    (last) dim; row-parallel (contraction-dim) cuts are dropped.

    Why: a row cut splits the contraction, so XLA psums partial float sums
    in a different association than the single-device dot — a ~1-ULP
    perturbation that bf16 rounding turns into KV-cache bit flips, and the
    serve contract is TOKEN IDENTITY with single-device greedy, not
    allclose.  Column cuts only ever all-gather exact per-shard results
    (no arithmetic collectives), so the math is bitwise unchanged.  The
    quantized split-brain path keeps the full Megatron column/row rules:
    its matmuls accumulate in int32, where partial-sum order cannot change
    the result."""
    def column_only(matched):
        last = len(matched) - 1
        return tuple(
            None if (log in ("model", "expert") and i != last) else log
            for i, log in enumerate(matched))

    return _param_pspecs_impl(params, cfg, mesh, transform=column_only)


def cache_pspecs(cache, cfg: ModelConfig, mesh: Mesh):
    ax = MeshAxes(mesh, cfg)

    def spec(path, leaf):
        key = _path_str(path)
        matched = _match(_CACHE_RULES, key)
        if matched is None or not hasattr(leaf, "shape"):
            return P()
        return _fit(matched, leaf.shape, ax)

    return jax.tree_util.tree_map_with_path(spec, cache)


def serve_cache_pspecs(cache, cfg: ModelConfig, mesh: Mesh):
    """PartitionSpec pytree for a DENSE serve slot cache (head-cut TP
    layout).  Works on arrays or ShapeDtypeStructs."""
    ax = MeshAxes(mesh, cfg)

    def spec(path, leaf):
        key = _path_str(path)
        matched = _match(_SERVE_CACHE_RULES, key)
        if matched is None or not hasattr(leaf, "shape"):
            return P()
        return _fit(matched, leaf.shape, ax)

    return jax.tree_util.tree_map_with_path(spec, cache)


def pool_pspecs(pcache, cfg: ModelConfig, mesh: Mesh, sa):
    """PartitionSpec pytree for a PAGED serve slot cache.

    ``sa`` is the per-leaf sequence-axis tree (``serve.pages.seq_axes``):
    leaves with ``s_ax >= 0`` are in pool layout (trailing
    ``(num_pages, page_size, Hkv/f, f·hd)``, ``layers.kv_fold``) and cut on
    their head rows; the rest keep
    their dense slot layout and take the serve rules.  Shape-checked like
    every rule here — an Hkv that ``tp`` does not divide replicates.

    Quantized pools (``QuantizedLeaf`` leaves, DESIGN.md §13) get a
    QuantizedLeaf of specs back: codes shard like the dense pool leaf and
    the per-page scales — trailing ``(num_pages, Hkv)`` — put the model
    axis on their own Hkv dim so they follow the KV-head cut (and
    replicate where the codes do).

    ``sa`` is mapped FIRST so QuantizedLeaf subtrees arrive whole at the
    leaf fn instead of being flattened into codes/scales.
    """
    ax = MeshAxes(mesh, cfg)

    def spec(path, s_ax, leaf):
        key = _path_str(path)
        paged = s_ax is not None and s_ax >= 0
        matched = _match(_POOL_CACHE_RULES if paged else _SERVE_CACHE_RULES,
                         key)
        if isinstance(leaf, QuantizedLeaf):
            if matched is None:
                return QuantizedLeaf(P(), P(), leaf.kv_dtype, leaf.out_dtype)
            codes = _fit(matched, leaf.codes.shape, ax)
            # scales follow the codes' head cut: a folded pool whose head
            # rows do not divide replicates, and its scales with it
            cut = any(a is not None for a in tuple(codes))
            return QuantizedLeaf(
                codes,
                _fit((None, "model"), leaf.scales.shape, ax) if cut
                else P(), leaf.kv_dtype, leaf.out_dtype)
        if matched is None or not hasattr(leaf, "shape"):
            return P()
        return _fit(matched, leaf.shape, ax)

    return jax.tree_util.tree_map_with_path(spec, sa, pcache)


def pool_kv_cut(pool_specs, sa, tp: int, model_axis: str) -> int:
    """The pool's effective KV head cut: ``tp`` when EVERY paged leaf
    actually sharded over the model axis (divisible Hkv), else 1 — a
    replicated leaf would break per-shard byte exactness."""
    if tp <= 1:
        return 1

    def cut(s_ax, sp):
        if s_ax < 0:
            return True
        if isinstance(sp, QuantizedLeaf):
            return (model_axis in tuple(sp.codes)
                    and model_axis in tuple(sp.scales))
        return model_axis in tuple(sp)

    flags = jax.tree.map(cut, sa, pool_specs,
                         is_leaf=lambda x: isinstance(x, P))
    return tp if all(jax.tree.leaves(flags)) else 1


def batch_pspecs(cfg: ModelConfig, mesh: Mesh, kind: str):
    ax = MeshAxes(mesh, cfg)
    b = ax.resolve("batch")
    if kind == "decode":
        specs = {"tokens": P(b)}
    else:
        specs = {"tokens": P(b, None)}
        if kind == "train":
            specs["labels"] = P(b, None)
            specs["mask"] = P(b, None)
    if cfg.frontend_tokens:
        specs["frontend"] = P(b, None, None)
    return specs


def with_sharding(mesh: Mesh, pspec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def logits_pspec(cfg: ModelConfig, mesh: Mesh, kind: str) -> P:
    ax = MeshAxes(mesh, cfg)
    b = ax.resolve("batch")
    v = ax.resolve("model") if cfg.vocab_size % ax.size(ax.resolve("model")) == 0 else None
    if kind == "decode":
        return P(b, v)
    return P(b, None, v)


def gather_fsdp(tree, cfg: ModelConfig):
    """ZeRO-3 weight gather (§Perf H4): constrain per-layer weights to their
    no-FSDP sharding before use, so XLA all-gathers the (small) weight shard
    over "data" and keeps the batch sharded — instead of its fallback of
    un-sharding the batch to run contraction-parallel dots with multi-GB f32
    partial-sum all-reduces (measured 6 TB/chip/step on gemma2-27b train).
    The constraint's transpose makes weight grads reduce-scatter back to the
    FSDP shard — exactly the ZeRO-3 dataflow.
    """
    import dataclasses as _dc

    from repro.distributed import runtime

    mesh = runtime.ambient_mesh()
    if mesh is None or not cfg.parallel.fsdp_axis             or cfg.parallel.fsdp_axis not in mesh.axis_names:
        return tree
    cfg_nofsdp = _dc.replace(
        cfg, parallel=_dc.replace(cfg.parallel, fsdp_axis=None))
    specs = param_pspecs(tree, cfg_nofsdp, mesh)
    fsdp_specs = param_pspecs(tree, cfg, mesh)

    def constrain(path, a, sp, fsp):
        if not hasattr(a, "ndim") or a.ndim < 2:
            return a
        # MoE experts stay FSDP-sharded: they are already EP-split over
        # "model" and gathering the (huge) expert stack per layer costs more
        # all-gather than the contraction-parallel dots it avoids (measured:
        # qwen3 train went collective-bound).  Batch pinning still applies.
        if "moe/" in _path_str(path):
            return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, fsp))
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, sp))

    return jax.tree_util.tree_map_with_path(constrain, tree, specs, fsdp_specs)


def pin_tp_exact(x, cfg: ModelConfig):
    """All-gather a model-axis-sharded activation (DESIGN.md §11).

    Applied to the INPUT of every down-projection (``o @ wo``, ``h @ w2``,
    recurrent out-projections) when ``cfg.parallel.exact_tp`` is set: the
    activation is column-cut output (attention heads / d_ff), and letting
    XLA run the following dot contraction-parallel would psum float partial
    sums in a different association than the single-device matmul — a
    1-ULP perturbation that bf16 KV rounding amplifies into greedy-token
    flips.  Constraining to replicated forces an ALL-GATHER (exact bit
    movement, no arithmetic) and a redundant but bitwise-single-device dot
    on every shard.  Up-projections and attention stay genuinely
    tensor-parallel; only the cheap (d_model-output) dots are replicated.
    No-op outside a mesh context or when ``exact_tp`` is False (training
    keeps the Megatron row-parallel dataflow)."""
    from repro.distributed import runtime

    if not cfg.parallel.exact_tp:
        return x
    mesh = runtime.ambient_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*([None] * x.ndim))))


def pin_batch(x, cfg: ModelConfig):
    """Pin the residual stream's batch sharding (§Perf H4b): without this,
    XLA's sharding propagation may flip the layer-scan carry to a
    replicated-batch / head-sharded layout (observed on gemma2 train:
    (256, H_local, ...) attention buffers, 6 TB/chip partial-sum
    all-reduces).  One constraint per scan body keeps DP batch parallelism
    through the whole stack."""
    from repro.distributed import runtime

    mesh = runtime.ambient_mesh()
    if mesh is None:
        return x
    ax = MeshAxes(mesh, cfg)
    b = ax.resolve("batch")
    if b is None or x.shape[0] % ax.size(b) != 0:
        return x
    spec = P(b, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
