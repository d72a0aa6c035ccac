"""The system under test, built from a configuration file: the program's
``ModelConfig``, weights drawn on the device from the seed, the page pool's
size, and the serving engine and scheduler."""
from __future__ import annotations

import dataclasses
import math
import types
import typing
from typing import Any, Dict

from harness.spec import SpecError

# what a CPU rehearsal keeps of a configuration: the family and the ratio
# of query to key/value heads, at widths a test run holds (the file's
# ``rehearsal.arch`` is applied over these, ``rehearsal.program`` over its
# ``program``)
REHEARSAL_WIDTHS = {"num_hidden_layers": 2, "hidden_size": 64,
                    "intermediate_size": 128, "num_attention_heads": 4,
                    "head_dim": 16, "vocab_size": 512}

# the keys of a configuration file that are not the model's architecture
BOOKKEEPING = frozenset((
    "name", "source", "family", "program_config", "program_keys",
    "reference", "costs", "program", "reduced", "cut", "departures", "notes",
    "weights", "memory", "check", "rehearsal"))

# the program's dense fields, each set from the published key named here;
# a file's ``program_keys`` names another key for a field (a block whose
# experts are narrower than its dense FFN reads ``d_ff`` from
# ``moe_intermediate_size``)
PUBLISHED = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
             "d_ff": "intermediate_size", "num_heads": "num_attention_heads",
             "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
             "vocab_size": "vocab_size", "rope_theta": "rope_theta",
             "norm_eps": "norm_eps"}
# fields the harness or the serving engine sets, never a file
HARNESS = ("name", "source", "family", "dtype", "kernels", "ita", "parallel",
           "supports_long_context")


def norm_eps(c: Dict[str, Any]) -> float:
    return float(c.get("rms_norm_eps", c.get("layer_norm_eps")))


def arch(c: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """The numbers a run uses: every architecture key of the file, with
    the norm's epsilon as ``norm_eps``, or the rehearsal's widths."""
    a = {k: v for k, v in c.items() if k not in BOOKKEEPING}
    a["norm_eps"] = norm_eps(c)
    if rehearse:
        group = c["num_attention_heads"] // c["num_key_value_heads"]
        a.update(REHEARSAL_WIDTHS)
        a["num_key_value_heads"] = max(1, a["num_attention_heads"] // group)
        a.update(c["rehearsal"].get("arch", {}))
    return a


def _value(tp, v, where: str):
    """``v`` from JSON as a value of the annotated type ``tp``: a nested
    config dataclass from an object, a tuple from a list, None where the
    type is optional."""
    if typing.get_origin(tp) in (typing.Union, types.UnionType):
        args = [t for t in typing.get_args(tp) if t is not type(None)]
        if v is None and len(args) < len(typing.get_args(tp)):
            return None
        tp = args[0]
    if dataclasses.is_dataclass(tp):
        if not isinstance(v, dict):
            raise SpecError(f"{where}: wants an object, got {v!r}")
        hints = typing.get_type_hints(tp)
        unknown = sorted(set(v) - set(hints))
        if unknown:
            raise SpecError(f"{where}: {tp.__name__} has no field {unknown}")
        try:
            return tp(**{k: _value(hints[k], x, f"{where}.{k}")
                         for k, x in v.items()})
        except TypeError as e:
            raise SpecError(f"{where}: {e}") from None
    if typing.get_origin(tp) is tuple:
        if not isinstance(v, list):
            raise SpecError(f"{where}: wants a list, got {v!r}")
        inner = typing.get_args(tp)[0]
        return tuple(_value(inner, x, f"{where}[{i}]") for i, x in enumerate(v))
    if tp is float and isinstance(v, (int, float)) and not isinstance(v, bool):
        return float(v)
    if isinstance(tp, type) and isinstance(v, tp) and not (
            tp is int and isinstance(v, bool)):
        return v
    raise SpecError(f"{where}: wants {getattr(tp, '__name__', tp)}, got {v!r}")


def _default(f: dataclasses.Field):
    if f.default is not dataclasses.MISSING:
        return f.default
    return f.default_factory()


def model_config(c: Dict[str, Any], rehearse: bool = False):
    """The program's ModelConfig for this file: its registry entry with the
    file's depth, widths, rope base and norm epsilon, every field of its
    ``program`` object, and no remat (serving never differentiates).

    The registry decides nothing on its own: an entry that sets a field
    which shapes the model (anything but the dense fields and the harness's
    own) away from ModelConfig's default, where the file does not state it,
    is refused.  Whether ``program`` and the published keys describe one
    model is for the check to judge."""
    from repro.configs import get_config
    from repro.configs.base import ModelConfig
    base = get_config(c["program_config"])
    if base.family != c["family"]:
        raise SpecError(f"{c['name']}: family {c['family']!r} but the "
                        f"program's {c['program_config']!r} is {base.family!r}")
    hints = typing.get_type_hints(ModelConfig)
    fields = {f.name: f for f in dataclasses.fields(ModelConfig)}
    a = arch(c, rehearse)
    keys = dict(PUBLISHED)
    for f, k in c.get("program_keys", {}).items():
        if f not in PUBLISHED:
            raise SpecError(f"{c['name']}: program_keys names {f!r}, which "
                            f"no published key sets; have {sorted(PUBLISHED)}")
        keys[f] = k
    missing = sorted(k for k in keys.values() if k not in a)
    if missing:
        raise SpecError(f"{c['name']}: no published key {missing} for the "
                        f"program's dense fields")
    dense = {f: _value(hints[f], a[k], f"{c['name']}: {k}")
             for f, k in keys.items()}
    prog = dict(c.get("program", {}))
    if rehearse:
        prog.update(c["rehearsal"].get("program", {}))
    for f in prog:
        if f not in fields:
            raise SpecError(f"{c['name']}: program field {f!r} is not a "
                            f"ModelConfig field")
        if f in PUBLISHED:
            raise SpecError(f"{c['name']}: program field {f!r} is set by "
                            f"the published keys ({keys[f]!r})")
        if f in HARNESS:
            raise SpecError(f"{c['name']}: program field {f!r} is set by "
                            f"the harness")
    for f in fields.values():
        if f.name in PUBLISHED or f.name in HARNESS or f.name in prog:
            continue
        if getattr(base, f.name) != _default(f):
            raise SpecError(
                f"{c['name']}: the program's {c['program_config']!r} sets "
                f"{f.name} = {getattr(base, f.name)!r}, which the file's "
                f"program object does not state")
    stated = {f: _value(hints[f], v, f"{c['name']}: program.{f}")
              for f, v in prog.items()}
    return dataclasses.replace(
        base, **dense, **stated, dtype=c["weights"]["dtype"],
        parallel=dataclasses.replace(base.parallel, remat="none"))


def param_shapes(cfg):
    import jax

    from repro.models import api
    return jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))


def weight_bytes(cfg, dtype_bytes: int = 2) -> int:
    import jax
    return sum(math.prod(s.shape) for s in jax.tree.leaves(param_shapes(cfg))
               ) * dtype_bytes


def num_pages(c: Dict[str, Any], serving: Dict[str, Any], cfg, costs,
              rehearse: bool = False) -> int:
    """Pages of the pool: what is left of the chip's memory after the
    weights, the B=1 caches of the prefill jobs in flight, each slot's
    state outside the pool, and the reserve for the step programs' other
    temporaries (the file's ``memory`` entry), in whole pages, plus the
    scratch page.  The bytes come from the configuration's cost module.
    Each page also needs ``pool_copy_share`` of itself again: the decode
    step, the prefix seed and the insert programs each held a copy of the
    pool as a temporary."""
    ps, max_len = serving["page_size"], serving["max_len"]
    if rehearse:
        return serving["slots"] * (max_len // ps) + 1
    a = arch(c, False)
    m = c["memory"]
    free = (m["hbm_bytes"] - weight_bytes(cfg)
            - serving["max_prefill_jobs"] * costs.request_cache_bytes(a, max_len)
            - serving["slots"] * costs.slot_state_bytes(a, max_len)
            - m["reserve_bytes"])
    page = ps * costs.page_token_bytes(a, ps)
    return int(free // (page * (1 + m["pool_copy_share"]))) + 1


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


def build_server(cfg, params, mesh, serving: Dict[str, Any], pages: int,
                 kv_dtype: str = "bf16"):
    """The engine over ``pages`` pages stored as ``kv_dtype`` (the file's
    key of that name), and its scheduler."""
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import ContinuousBatchingScheduler
    eng = ServeEngine(cfg, params, mesh=mesh, max_len=serving["max_len"],
                      page_size=serving["page_size"], num_pages=pages,
                      prefix_cache=serving["prefix_cache"], kv_dtype=kv_dtype)
    sched = ContinuousBatchingScheduler(
        eng, max_slots=serving["slots"],
        prefill_chunk=serving["prefill_chunk"],
        max_prefill_jobs=serving["max_prefill_jobs"])
    return eng, sched
