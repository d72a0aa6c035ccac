"""Operations and bytes that the algorithm needs, from shapes alone: the
work counts of a dense ``lm`` block, which a configuration uses unless its
file names a module of its own (``"costs"``: ``bench/costs/<name>.py``,
which provides the functions marked *module* below).

``a`` is a configuration's numbers (``model.arch``): layers ``L``, width
``d``, query heads ``H``, key/value heads ``Hkv``, head size ``D``, FFN
width ``F``, vocabulary ``V``.  Weights and activations are bf16 (2
bytes); K/V are stored as the file's ``kv_dtype`` says (bf16 by default;
int8 and fp8 codes take 1 byte, and each page keeps a float32 scale per
layer, K or V and kv head).  A decode step counts only active slots;
``read`` is the sum over them of the positions each attends to (its length
plus the new token), and ``counters`` is what the program counted in that
step (its ``sched.step`` span), which dense work does not need.  A prefill
chunk of ``w`` tokens starts at position ``s`` and attends causally over
everything before it.

The least time the chip could take for a piece of work is the larger of its
operations over the peak FLOP/s and its bytes over the peak bandwidth
(``roofline_s``); that bound is what every roofline share here divides by
the measured device time.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

BF16 = 2
# bytes of one stored K/V element, by the file's ``kv_dtype``
KV_ITEMSIZE = {"bf16": 2, "int8": 1, "fp8": 1}
SCALE = 4          # a quantized page's float32 scale per kv head
# what a cost module provides (this one included)
MODULE = ("decode_step", "prefill_chunk", "paged_decode_kernel",
          "flash_prefill_kernel", "kv_bytes", "page_token_bytes",
          "slot_state_bytes", "request_cache_bytes")


def _dims(a: Dict[str, Any]):
    return (a["num_hidden_layers"], a["hidden_size"], a["num_attention_heads"],
            a["num_key_value_heads"], a["head_dim"], a["intermediate_size"],
            a["vocab_size"])


def layer_matmul_params(a: Dict[str, Any]) -> int:
    """q, k, v and output projections plus the gated FFN of one layer."""
    L, d, H, Hkv, D, F, V = _dims(a)
    return d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * F


def layer_weight_bytes(a: Dict[str, Any]) -> int:
    L, d, *_ = _dims(a)
    return L * (layer_matmul_params(a) + 2 * d) * BF16


def head_weight_bytes(a: Dict[str, Any]) -> int:
    L, d, H, Hkv, D, F, V = _dims(a)
    return (V * d + d) * BF16


def kv_dtype(a: Dict[str, Any]) -> str:
    return a.get("kv_dtype", "bf16")


def dense_kv_bytes(a: Dict[str, Any], tokens: int) -> int:
    """bf16 K and V of ``tokens`` positions over every layer: a prefill
    job's B=1 cache, whatever the pool stores."""
    L, d, H, Hkv, D, F, V = _dims(a)
    return L * 2 * Hkv * D * BF16 * tokens


def kv_bytes(a: Dict[str, Any], tokens: int) -> int:
    """*module* K and V codes of ``tokens`` positions over every layer, as
    the pool stores them."""
    L, d, H, Hkv, D, F, V = _dims(a)
    return L * 2 * Hkv * D * KV_ITEMSIZE[kv_dtype(a)] * tokens


def page_token_bytes(a: Dict[str, Any], page_size: int) -> float:
    """*module* Pool bytes of one token's place in a page: its K/V codes,
    and a quantized page's scales spread over the page's tokens."""
    L, d, H, Hkv, D, F, V = _dims(a)
    scales = 0 if kv_dtype(a) == "bf16" else L * 2 * Hkv * SCALE / page_size
    return kv_bytes(a, 1) + scales


def slot_state_bytes(a: Dict[str, Any], max_len: int) -> int:
    """*module* Bytes a slot keeps outside the pool, whatever its length
    (a window layer's ring, a recurrent state): none in a dense block."""
    return 0


def request_cache_bytes(a: Dict[str, Any], max_len: int) -> int:
    """*module* The dense B=1 cache of one prefill job."""
    return dense_kv_bytes(a, max_len) + slot_state_bytes(a, max_len)


def causal_pairs(s: int, w: int) -> int:
    """Query-key pairs of a causal chunk: query i at position s+i sees
    s+i+1 keys."""
    return w * s + w * (w + 1) // 2


def attn_flops(a: Dict[str, Any], pairs: int) -> int:
    """Scores and the weighted sum of values: 4 H D operations per pair,
    per layer."""
    L, d, H, Hkv, D, F, V = _dims(a)
    return L * 4 * H * D * pairs


def decode_step(a: Dict[str, Any], n: int, read: int,
                counters: Optional[Dict[str, Any]] = None) -> Tuple[int, int]:
    """*module* (FLOPs, bytes) of one decode step over ``n`` active
    slots."""
    L, d, H, Hkv, D, F, V = _dims(a)
    flops = 2 * n * (L * layer_matmul_params(a) + d * V) + attn_flops(a, read)
    nbytes = (layer_weight_bytes(a) + head_weight_bytes(a)
              + kv_bytes(a, read) + kv_bytes(a, n))
    return flops, nbytes


def prefill_chunk(a: Dict[str, Any], s: int, w: int) -> Tuple[int, int]:
    """*module* (FLOPs, bytes) of one prefill chunk (no logits: the decode
    step computes the prompt's last position)."""
    L, d, H, Hkv, D, F, V = _dims(a)
    flops = 2 * w * L * layer_matmul_params(a) + attn_flops(a, causal_pairs(s, w))
    nbytes = (layer_weight_bytes(a) + dense_kv_bytes(a, s + w)
              + dense_kv_bytes(a, w))
    return flops, nbytes


def paged_decode_kernel(a: Dict[str, Any], n: int, read: int,
                        counters: Optional[Dict[str, Any]] = None
                        ) -> Tuple[int, int]:
    """*module* (FLOPs, bytes) of the paged flash-decode kernel over every
    layer of one step: the live K/V pages, each query and each output."""
    L, d, H, Hkv, D, F, V = _dims(a)
    return attn_flops(a, read), kv_bytes(a, read) + L * 2 * n * H * D * BF16


def flash_prefill_kernel(a: Dict[str, Any], s: int, w: int
                         ) -> Tuple[int, int]:
    """*module* (FLOPs, bytes) of the flash kernel over every layer of one
    chunk: queries and outputs of the chunk, K/V of every position it
    sees."""
    L, d, H, Hkv, D, F, V = _dims(a)
    return (attn_flops(a, causal_pairs(s, w)),
            dense_kv_bytes(a, s + w) + L * 2 * w * H * D * BF16)


def roofline_s(flops: float, nbytes: float, peaks: Dict[str, Any]
               ) -> Tuple[float, str]:
    """Least time for the work, and which bound sets it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "hbm")
