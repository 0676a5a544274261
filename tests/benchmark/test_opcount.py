"""The yardstick's arithmetic against hand counts at one small shape."""

import pytest

from benchmark import opcount


def test_peaks_v5e_and_unknown_kind_raises():
    p = opcount.peaks_for("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 819e9, 2**34)
    assert "v5e" in p["source"]
    with pytest.raises(KeyError):
        opcount.peaks_for("cpu")
    with pytest.raises(KeyError):
        opcount.peaks_for("TPU v9")


def test_roofline_names_the_bound():
    p = opcount.peaks_for("TPU v5 lite")
    assert opcount.roofline_seconds(197e12, 1, p) == (1.0, "compute")
    assert opcount.roofline_seconds(1, 819e9, p) == (1.0, "memory")


def test_paged_attention_hand_count():
    # two sequences, 4 query heads, 2 kv heads, head_dim 8, blocks of 16, bf16.
    # seq A: one query over 20 cached positions -> 2 blocks live
    # seq B: two queries (a 2-token chunk) over 3 and 4 positions -> 1 block
    flops, nbytes = opcount.paged_attention([[20], [3, 4]], 4, 2, 8, 16)
    # QK^T + PV: 2 + 2 flops per (query, key, head, dim)
    assert flops == 4 * 4 * 8 * (20 + 3 + 4)
    kv = 2 * (2 + 1) * 16 * 2 * 8 * 2          # K and V, whole blocks, bf16
    q_and_out = 2 * 3 * 4 * 8 * 2              # 3 query tokens in, 3 out
    assert nbytes == kv + q_and_out
    assert opcount.paged_attention([[]], 4, 2, 8, 16) == (0, 0)


@pytest.mark.parametrize("fn,matmuls,heads_rw,kv_rw,rows", [
    (opcount.flash_fwd, 2, 2, 2, 1),       # q,o | k,v | lse
    (opcount.flash_bwd_dkv, 4, 2, 4, 2),   # q,dO | k,v,dK,dV | lse,delta
    (opcount.flash_bwd_dq, 3, 3, 2, 2),    # q,dO,dQ | k,v | lse,delta
])
def test_flash_kernels_hand_count(fn, matmuls, heads_rw, kv_rw, rows):
    B, S, H, KVH, D = 2, 8, 4, 2, 16
    flops, nbytes = fn(B, S, H, KVH, D, causal=True)
    pairs = 8 * 9 // 2  # unmasked (query, key) pairs of a causal 8 x 8
    assert flops == matmuls * 2 * B * H * D * pairs
    assert nbytes == B * S * D * 2 * (heads_rw * H + kv_rw * KVH) + rows * B * S * H * 4
    full, _ = fn(B, S, H, KVH, D, causal=False)
    assert full == matmuls * 2 * B * H * D * 64


def test_train_flops_per_token_is_the_palm_convention():
    # 100 parameters of which a 10 x 4 embedding; 3 layers, sequence 5, hidden 4
    assert opcount.train_flops_per_token(100, 10, 4, 3, 5) == 6 * 60 + 12 * 3 * 5 * 4
