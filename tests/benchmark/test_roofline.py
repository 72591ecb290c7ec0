"""roofline.py against hand-worked operations and bytes."""

import pytest

from benchmark import roofline


def test_ragged_paged_attention_decode_lane_by_hand():
    # one decode lane at position 1,000 (kv_len 1,001), Mistral-7B heads:
    # pairs = 1,001; ops = 4 * 32 heads * 128 * 1,001 = 16,400,384
    # bytes = K and V: 2 * 8 * 1,001 * 128 * 2 = 4,100,096; q and o: 2 * 32 * 1 * 128 * 2 = 16,384
    cost = roofline.ragged_attention_cost(q_len=1, kv_len=1001, n_q_heads=32,
                                          n_kv_heads=8, head_dim=128)
    assert cost == {"ops": 16_400_384.0, "bytes": 4_116_480.0}
    least = roofline.roofline_seconds(cost, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(4_116_480 / 819e9)


def test_ragged_paged_attention_prefill_chunk_by_hand():
    # a 256-token chunk at offset 512: rows see 513..768 keys
    # pairs = 256 * 512 + 256 * 257 / 2 = 131,072 + 32,896 = 163,968
    assert roofline.causal_pairs(256, 768) == 163_968
    cost = roofline.ragged_attention_cost(q_len=256, kv_len=768, n_q_heads=32,
                                          n_kv_heads=8, head_dim=128)
    assert cost["ops"] == 4 * 32 * 128 * 163_968
    assert cost["bytes"] == 2 * 8 * 768 * 128 * 2 + 2 * 32 * 256 * 128 * 2
    assert roofline.roofline_seconds(cost, "TPU v5 lite")["bound"] == "compute"


def test_flash_fwd_by_hand():
    # gpt2-small's call: (24, 12, 1024, 64) bf16, causal
    # pairs per head = 1024 * 1025 / 2 = 524,800; ops = 4 * 24 * 12 * 64 * 524,800
    # bytes = q, k, v, o: 4 * 24 * 12 * 1024 * 64 * 2; lse: 24 * 12 * 1024 * 4
    cost = roofline.flash_fwd_cost(batch=24, seq=1024, n_q_heads=12, n_kv_heads=12, head_dim=64)
    assert cost["ops"] == 4 * 24 * 12 * 64 * 524_800
    assert cost["bytes"] == 4 * 24 * 12 * 1024 * 64 * 2 + 24 * 12 * 1024 * 4
    least = roofline.roofline_seconds(cost, "TPU v5 lite")
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(cost["ops"] / 197e12)


def test_train_flops_per_token_by_hand():
    # gpt2-small: per layer 4 * 768 * 768 attention weights + 2 * 768 * 3072 MLP weights
    weights = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 50257
    attention = 12 * 4 * 12 * 64 * 1025 / 2
    got = roofline.train_flops_per_token(n_layers=12, d_model=768, n_q_heads=12, n_kv_heads=12,
                                         head_dim=64, d_ff=3072, vocab=50257, seq=1024,
                                         gated_mlp=False)
    assert got == pytest.approx(3 * (2 * weights + attention))
    # Mistral widths: GQA projections and a gated MLP
    got = roofline.train_flops_per_token(n_layers=1, d_model=4096, n_q_heads=32, n_kv_heads=8,
                                         head_dim=128, d_ff=14336, vocab=32768, seq=1024,
                                         gated_mlp=True)
    weights = 4096 * 128 * (2 * 32 + 2 * 8) + 3 * 4096 * 14336 + 4096 * 32768
    assert got == pytest.approx(3 * (2 * weights + 4 * 32 * 128 * 1025 / 2))
