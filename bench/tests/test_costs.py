"""FLOP and byte counts against shapes worked by hand."""
import pytest

from harness import costs

# stablelm-1.6b: 24 layers, d 2048, 32 heads of 64 (MHA), FFN 5632, V 100352
LM = {"num_hidden_layers": 24, "hidden_size": 2048, "num_attention_heads": 32,
      "num_key_value_heads": 32, "head_dim": 64, "intermediate_size": 5632,
      "vocab_size": 100352}
# granite-8b, one stage: 18 layers, d 4096, 32 heads of 128, 8 kv heads
GQA = {"num_hidden_layers": 18, "hidden_size": 4096, "num_attention_heads": 32,
       "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 14336,
       "vocab_size": 49152}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_layer_params_by_hand():
    # q and o: 2048*2048 each; k and v: 2048*2048 each (MHA); FFN 3*2048*5632
    assert costs.layer_matmul_params(LM) == 4 * 2048 * 2048 + 3 * 2048 * 5632
    # GQA: q, o 4096*4096; k, v 4096*1024; FFN 3*4096*14336 = 218.1 M
    assert costs.layer_matmul_params(GQA) == (2 * 4096 * 4096 + 2 * 4096 * 1024
                                              + 3 * 4096 * 14336)
    assert costs.layer_matmul_params(GQA) == 218103808


def test_kv_bytes_per_token():
    assert costs.kv_bytes(LM, 1) == 196608       # 2*24*32*64*2
    assert costs.kv_bytes(GQA, 1) == 73728       # 2*18*8*128*2


def test_paged_decode_kernel():
    # 2 active slots at lengths 99 and 199 read 100 and 200 positions
    f, b = costs.paged_decode_kernel(LM, 2, 300)
    assert f == 24 * 4 * 32 * 64 * 300
    assert b == 300 * 196608 + 24 * 2 * 2 * 32 * 64 * 2


def test_flash_prefill_kernel():
    # chunk of 256 at start 1024: query i sees 1024 + i + 1 keys
    pairs = sum(1024 + i + 1 for i in range(256))
    assert costs.causal_pairs(1024, 256) == pairs
    f, b = costs.flash_prefill_kernel(GQA, 1024, 256)
    assert f == 18 * 4 * 32 * 128 * pairs
    assert b == (1024 + 256) * 73728 + 18 * 2 * 256 * 32 * 128 * 2


def test_decode_step_whole():
    f, b = costs.decode_step(GQA, 64, 64 * 500)
    weights = (18 * (218103808 + 2 * 4096) + 49152 * 4096 + 4096) * 2
    assert b == weights + 64 * 500 * 73728 + 64 * 73728
    assert f == (2 * 64 * (18 * 218103808 + 4096 * 49152)
                 + 18 * 4 * 32 * 128 * 64 * 500)


def test_prefill_chunk_whole():
    f, b = costs.prefill_chunk(LM, 0, 256)
    assert f == (2 * 256 * 24 * costs.layer_matmul_params(LM)
                 + 24 * 4 * 32 * 64 * (256 * 257 // 2))
    assert b == (24 * (costs.layer_matmul_params(LM) + 2 * 2048) * 2
                 + 256 * 196608 * 2)


@pytest.mark.parametrize("flops,nbytes,bound", [(197e12, 1.0, "compute"),
                                                 (1.0, 819e9, "hbm")])
def test_roofline_names_its_bound(flops, nbytes, bound):
    t, which = costs.roofline_s(flops, nbytes, PEAKS)
    assert which == bound and t == pytest.approx(1.0)


def test_decode_is_hbm_bound_at_these_sizes():
    f, b = costs.decode_step(GQA, 64, 64 * 500)
    assert costs.roofline_s(f, b, PEAKS)[1] == "hbm"
