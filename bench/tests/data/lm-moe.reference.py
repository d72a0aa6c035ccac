"""Plain float32 reference of an ``lm`` block with routed experts: the
dense block of ``lm.py`` with its FFN replaced by ``num_experts`` gated
experts of width ``moe_intermediate_size``.  A softmax router over all of
them; the ``num_experts_per_tok`` largest probabilities kept and
renormalised to sum to 1; each token's output the weighted sum of its
chosen experts'.  Every expert runs over every token, weighted 0 where it
was not chosen, so no token is dropped.  ``fp8=True`` is ``lm.py``'s
control, the router and the experts included.

A test copies this file to ``bench/configs/lm-moe.py`` beside ``lm.py``,
whose helpers it loads."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_reference_lm_for_moe", Path(__file__).with_name("lm.py"))
lm = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(lm)


def _experts(y, m, a, fp8):
    probs = jax.nn.softmax(lm._mm(y, m["router"], fp8), axis=-1)
    gate, ids = jax.lax.top_k(probs, a["num_experts_per_tok"])
    gate = gate / gate.sum(-1, keepdims=True)

    def one(e, w1, w3, w2):
        weight = jnp.sum(jnp.where(ids == e, gate, 0.0), axis=-1)
        h = jax.nn.silu(lm._mm(y, w1, fp8)) * lm._mm(y, w3, fp8)
        return weight[:, None] * lm._mm(h, w2, fp8)

    experts = jnp.arange(a["num_experts"])
    return jax.vmap(one)(experts, m["w1"], m["w3"], m["w2"]).sum(0)


def logits_at(params, tokens, read, a, fp8=False):
    """``lm.logits_at`` with the experts in the FFN's place."""
    T = tokens.shape[0]
    H, Hkv, D = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    eps, theta = a["norm_eps"], float(a["rope_theta"])
    x = params["embed"][tokens].astype(lm.F32)
    if fp8:
        x = lm._fp8(x, None)
    pos = jnp.arange(T)
    blocks = jax.tree.map(lambda b: b.reshape((-1,) + b.shape[2:]),
                          params["blocks"])

    def layer(x, p):
        at = p["attn"]
        h = lm._rms(x, p["ln_attn"], eps)
        q = lm._rope(lm._mm(h, at["wq"], fp8).reshape(T, H, D), pos, theta)
        k = lm._rope(lm._mm(h, at["wk"], fp8).reshape(T, Hkv, D), pos, theta)
        v = lm._mm(h, at["wv"], fp8).reshape(T, Hkv, D)
        o = lm._attention(q, k, v, fp8)
        x = x + lm._mm(o.reshape(T, H * D), at["wo"], fp8)
        y = lm._rms(x, p["ln_mlp"], eps)
        return x + _experts(y, p["moe"], a, fp8), None

    x, _ = jax.lax.scan(layer, x, blocks)
    h = lm._rms(x[read], params["ln_final"], eps)
    return lm._mm(h, params["lm_head"], fp8)
