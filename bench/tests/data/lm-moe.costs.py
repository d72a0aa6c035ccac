"""Work counts of an ``lm`` block with routed experts: the dense block's
(``harness/costs.py``) with a router of ``num_experts`` outputs and
``num_experts_per_tok`` gated experts of width ``moe_intermediate_size``
in the FFN's place.  Each token computes its own experts, and a step reads
at least that many experts' weights in each layer: that is what is
counted, so no share of a roofline can pass 100%.

A test copies this file to ``bench/costs/lm-moe.py``."""
from harness import costs as dense
from harness.costs import (flash_prefill_kernel, kv_bytes,  # noqa: F401
                           page_token_bytes, paged_decode_kernel,
                           request_cache_bytes, slot_state_bytes)


def _layer(a):
    """(parameters every token uses, parameters of one expert)."""
    d, H, Hkv, D = (a["hidden_size"], a["num_attention_heads"],
                    a["num_key_value_heads"], a["head_dim"])
    return (2 * d * H * D + 2 * d * Hkv * D + d * a["num_experts"],
            3 * d * a["moe_intermediate_size"])


def _weight_bytes(a):
    shared, expert = _layer(a)
    k, d = a["num_experts_per_tok"], a["hidden_size"]
    return a["num_hidden_layers"] * (shared + k * expert + 2 * d) * dense.BF16


def _matmul_flops(a, tokens):
    shared, expert = _layer(a)
    return 2 * tokens * a["num_hidden_layers"] * (
        shared + a["num_experts_per_tok"] * expert)


def decode_step(a, n, read, counters=None):
    flops = (_matmul_flops(a, n) + 2 * n * a["hidden_size"] * a["vocab_size"]
             + dense.attn_flops(a, read))
    nbytes = (_weight_bytes(a) + dense.head_weight_bytes(a)
              + kv_bytes(a, read) + kv_bytes(a, n))
    return flops, nbytes


def prefill_chunk(a, s, w):
    flops = _matmul_flops(a, w) + dense.attn_flops(a, dense.causal_pairs(s, w))
    nbytes = (_weight_bytes(a) + dense.dense_kv_bytes(a, s + w)
              + dense.dense_kv_bytes(a, w))
    return flops, nbytes
