"""The FLOP and byte functions against counts made by hand."""

import pytest

from benchmarks import flops, peaks

DENSE = {"dim": 8, "hidden": 16, "n_layers": 2, "n_heads": 2,
         "vocab_size": 32}


def test_dense_matmul_params_by_hand():
    # per layer: q, k, v, o = 4 * 8*8 = 256; SwiGLU = 3 * 8*16 = 384
    # two layers = 1280; head = 32*8 = 256; the embedding is a gather: 0
    assert flops.matmul_params(DENSE) == 1536


def test_moe_counts_active_parameters_only():
    # 8 experts, 2 a token: 2 * 384 = 768 of 8 * 384 = 3072; router 8*8 = 64
    moe = dict(DENSE, num_experts=8, top_k=2)
    assert flops.matmul_params(moe) == 2 * (256 + 768 + 64) + 256 == 2432
    assert flops.matmul_params(dict(moe, num_experts=0)) == 1536


def test_train_flops_by_hand():
    # 12 tokens * 6 * 1536 = 110592
    # attention, one layer: QK^T and PV are 2*B*H*T*T*D = 2*3*2*4*4*4 = 768
    # each, causal needs half: 768 forward, twice that backward: 2304;
    # two layers: 4608
    assert flops.causal_attention_flops(3, 2, 4, 4, backward=False) == 768
    assert flops.causal_attention_flops(3, 2, 4, 4) == 2304
    assert flops.lm_train_flops(DENSE, batch=3, seq=4) == 110592 + 4608


def test_attention_share_of_the_cells():
    """The cells' ``why`` quotes these shares."""
    model = {"dim": 2048, "hidden": 5632, "n_layers": 16, "n_heads": 16,
             "vocab_size": 49152}

    def share(batch, seq):
        attn = 16 * flops.causal_attention_flops(batch, 16, seq, 128)
        return attn / flops.lm_train_flops(model, batch, seq)

    assert 0.06 < share(4, 2048) < 0.08      # 6.8%
    assert 0.21 < share(1, 8192) < 0.24      # 22.5%


def test_byte_counts_by_hand():
    # q, k, v, o ... of 1*1*4*2 bf16 = 16 bytes, row statistics 4 f32 = 16:
    # forward 4 tensors + stats = 80, backward 8 tensors + stats = 144
    assert flops.flash_kernel_bytes(1, 1, 4, 2) == 224
    # 2 pairs * (centre + context + 3 negatives) = 10 rows of 5 f32, each
    # gathered once and read and written by the scatter: 3 * 10 * 20
    assert flops.sgns_step_bytes(2, 3, 5) == 600


def test_roofline_says_which_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert flops.roofline_seconds(1.97e14, 0.0, v5e) == (1.0, "compute")
    assert flops.roofline_seconds(1.0, 2 * 8.19e11, v5e) == (2.0, "memory")


def test_peaks_table_refuses_an_unknown_device():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and "source" in v5e
    with pytest.raises(peaks.UnknownDevice, match="cpu"):
        peaks.peaks_for("cpu")
