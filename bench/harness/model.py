"""The system under test, built from a configuration file: the program's
``ModelConfig``, weights drawn on the device from the seed, the page pool's
size, and the serving engine and scheduler."""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

# what a CPU rehearsal keeps of a configuration: the family and the ratio
# of query to key/value heads, at widths a test run holds
REHEARSAL_WIDTHS = {"num_hidden_layers": 2, "hidden_size": 64,
                    "intermediate_size": 128, "num_attention_heads": 4,
                    "head_dim": 16, "vocab_size": 512}


def norm_eps(c: Dict[str, Any]) -> float:
    return float(c.get("rms_norm_eps", c.get("layer_norm_eps")))


def arch(c: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """The numbers a run uses: the file's, or the rehearsal's widths."""
    a = {k: c[k] for k in ("num_hidden_layers", "hidden_size",
                           "intermediate_size", "num_attention_heads",
                           "num_key_value_heads", "head_dim", "vocab_size",
                           "rope_theta")}
    a["norm_eps"] = norm_eps(c)
    if rehearse:
        group = c["num_attention_heads"] // c["num_key_value_heads"]
        a.update(REHEARSAL_WIDTHS)
        a["num_key_value_heads"] = max(1, a["num_attention_heads"] // group)
    return a


def model_config(c: Dict[str, Any], rehearse: bool = False):
    """The program's ModelConfig for this file: its registry entry with the
    file's depth, widths, rope base and norm epsilon, and no remat (serving
    never differentiates)."""
    from repro.configs import get_config
    base = get_config(c["program_config"])
    if base.family != c["family"]:
        raise ValueError(f"{c['name']}: family {c['family']!r} but the "
                         f"program's {c['program_config']!r} is {base.family!r}")
    a = arch(c, rehearse)
    return dataclasses.replace(
        base, num_layers=a["num_hidden_layers"], d_model=a["hidden_size"],
        d_ff=a["intermediate_size"], num_heads=a["num_attention_heads"],
        num_kv_heads=a["num_key_value_heads"], head_dim=a["head_dim"],
        vocab_size=a["vocab_size"], rope_theta=float(a["rope_theta"]),
        norm_eps=a["norm_eps"], dtype=c["weights"]["dtype"],
        parallel=dataclasses.replace(base.parallel, remat="none"))


def kv_token_bytes(a: Dict[str, Any]) -> int:
    """K and V of one token over every layer, in bf16."""
    return 2 * a["num_hidden_layers"] * a["num_key_value_heads"] * a["head_dim"] * 2


def param_shapes(cfg):
    import jax

    from repro.models import api
    return jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))


def weight_bytes(cfg, dtype_bytes: int = 2) -> int:
    import jax
    return sum(math.prod(s.shape) for s in jax.tree.leaves(param_shapes(cfg))
               ) * dtype_bytes


def num_pages(c: Dict[str, Any], serving: Dict[str, Any], cfg,
              rehearse: bool = False) -> int:
    """Pages of the pool: what is left of the chip's memory after the
    weights, the B=1 caches of the prefill jobs in flight and the reserve
    for the step programs' other temporaries (the file's ``memory`` entry),
    in whole pages, plus the scratch page.  Each page also needs
    ``pool_copy_share`` of itself again: the decode step, the prefix seed and
    the insert programs each hold a copy of the pool as a temporary."""
    ps, max_len = serving["page_size"], serving["max_len"]
    if rehearse:
        return serving["slots"] * (max_len // ps) + 1
    kv = kv_token_bytes(arch(c, False))
    m = c["memory"]
    free = (m["hbm_bytes"] - weight_bytes(cfg)
            - serving["max_prefill_jobs"] * max_len * kv - m["reserve_bytes"])
    return int(free // (ps * kv * (1 + m["pool_copy_share"]))) + 1


def seed_key(seed: int):
    """A PRNG key from any whole number, also one past 32 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31), seed >> 31)


def serve_shardings(cfg, mesh, shapes):
    """The placement ServeEngine gives its parameters, so that handing it
    weights made there copies nothing."""
    from repro.distributed import sharding as shd
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, exact_tp=True))
    return shd.with_sharding(mesh, shd.serve_param_pspecs(shapes, cfg, mesh))


def make_weights(cfg, seed: int, mesh):
    """Every weight, drawn on the device from ``seed`` in one jitted call,
    in the type it is served in.  The program's own initialisation is kept
    leaf by leaf: norms' gains start at 0 (the norm scales by 1 + gain),
    the embedding is N(0, 0.02), every projection U(-1/sqrt(in), 1/sqrt(in))."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = jnp.dtype(cfg.dtype)

    def build(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for (path, sd), k in zip(flat, keys):
            name = str(getattr(path[-1], "key", path[-1]))
            if name.startswith("ln"):
                a = jnp.zeros(sd.shape, dtype)
            elif name == "embed":
                a = (jax.random.normal(k, sd.shape, jnp.float32) * 0.02
                     ).astype(dtype)
            else:
                lim = 1.0 / math.sqrt(sd.shape[-2])
                a = jax.random.uniform(k, sd.shape, jnp.float32, -lim, lim
                                       ).astype(dtype)
            out.append(a)
        return jax.tree_util.tree_unflatten(tree, out)

    sh = serve_shardings(cfg, mesh, shapes)
    return jax.block_until_ready(jax.jit(build, out_shardings=sh)(seed_key(seed)))


def build_server(cfg, params, mesh, serving: Dict[str, Any], pages: int):
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import ContinuousBatchingScheduler
    eng = ServeEngine(cfg, params, mesh=mesh, max_len=serving["max_len"],
                      page_size=serving["page_size"], num_pages=pages,
                      prefix_cache=serving["prefix_cache"])
    sched = ContinuousBatchingScheduler(
        eng, max_slots=serving["slots"],
        prefill_chunk=serving["prefill_chunk"],
        max_prefill_jobs=serving["max_prefill_jobs"])
    return eng, sched
