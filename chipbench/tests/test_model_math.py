"""``model_math.py`` against counts worked by hand for Mistral-7B-v0.3."""

import pytest

from chipbench import model_math as mm

M7B = dict(hidden_size=4096, num_hidden_layers=32, num_attention_heads=32,
           num_key_value_heads=8, head_dim=128, intermediate_size=14336,
           vocab_size=32768, tie_word_embeddings=False)


def test_parameters_by_hand():
    # q and o: 4096*4096 each; k and v: 4096*1024 each; three of 4096*14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert mm.layer_matmul_params(M7B) == layer
    head = 4096 * 32768
    assert mm.matmul_params(M7B) == 32 * layer + head
    # the published size: 7.248 B with embedding, head and norm vectors
    total = 32 * (layer + 2 * 4096) + 2 * head + 4096
    assert mm.total_params(M7B) == total == 7_248_023_552
    # the embedding lookup is what the program's 6 * num_params counts on top
    assert mm.total_params(M7B) - mm.matmul_params(M7B) == head + 65 * 4096


def test_flops_by_hand():
    d6 = dict(M7B, num_hidden_layers=6)
    matmul = 6 * 218_103_808 + 4096 * 32768
    # attention, forward, per token at mean context 1024.5:
    # 4 * layers * heads * head_dim * context
    attn = 4 * 6 * 32 * 128 * 1024.5
    assert mm.prefill_flops_per_token(d6, 2048) == pytest.approx(2 * matmul + attn)
    assert mm.train_flops_per_token(d6, 2048) == pytest.approx(
        3 * (2 * matmul + attn))
    # about 8.7 GFLOP in the multiplies and 0.3 in attention a token
    assert 8.6e9 < 6 * matmul < 8.7e9
    assert mm.flash_attention_flops(d6, 8, 2048) == pytest.approx(
        3 * attn * 8 * 2048)


def test_bytes_by_hand():
    d16 = dict(M7B, num_hidden_layers=16)
    assert mm.kv_bytes_per_token(d16) == 64 * 1024      # 4 KiB a layer
    assert mm.kv_bytes_per_token(M7B) == 128 * 1024
    assert mm.weight_bytes(d16) == 2 * (16 * 218_103_808 + 4096 * 32768)
    # a decode token-step at 819 GB/s cannot take less than about 8.9 ms
    floor_ms = mm.weight_bytes(d16) / 819e9 * 1e3
    assert 8.5 < floor_ms < 9.3
    assert mm.decode_step_bytes(d16, 1000) == mm.weight_bytes(d16) + 1000 * 65536


def test_unknown_device_is_an_error():
    assert mm.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert mm.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        mm.peaks("cpu")
