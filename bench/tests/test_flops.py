"""Operation and byte counts against hand counts."""

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import flops

PHI3 = dict(n_layers=32, d_model=3072, n_heads=32, n_kv_heads=32,
            head_dim=96, d_ff=8192, vocab_size=32064, norm="rmsnorm",
            act="swiglu", tie_embeddings=False)
SC2 = dict(n_layers=32, d_model=4608, n_heads=36, n_kv_heads=4,
           head_dim=128, d_ff=18432, vocab_size=49152, norm="layernorm",
           act="gelu", tie_embeddings=False)


def test_phi3_non_embedding_parameters():
    # attention 4 x 3072^2, SwiGLU 3 x 3072 x 8192, two RMSNorm gammas
    per_layer = 4 * 3072 ** 2 + 3 * 3072 * 8192 + 2 * 3072
    assert flops.param_counts(PHI3)["layers"] == 32 * per_layer
    assert flops.param_counts(PHI3)["layers"] == 3_624_075_264  # 3.62 B


def test_starcoder2_layer_parameters():
    # q and o 4608^2 each, k and v 4608 x 512, GELU MLP 2 x 4608 x 18432,
    # two LayerNorms of gamma and beta
    per_layer = 2 * 4608 ** 2 + 2 * 4608 * 512 + 2 * 4608 * 18432 + 4 * 4608
    assert flops.param_counts(SC2)["per_layer"] == per_layer == 217_073_664


def test_flops_per_token_at_position_zero():
    matmul = 32 * (4 * 3072 ** 2 + 3 * 3072 * 8192) + 3072 * 32064
    attn = 4 * 32 * 32 * 96          # one key: Q.K and P.V
    assert flops.flops_per_token(PHI3, 0) == 2 * matmul + attn


def test_decode_bytes_by_hand():
    cfg = dict(SC2, n_layers=16)
    weights = 2 * (16 * 217_073_664 + 4608 * 49152 + 2 * 4608)
    row = 16 * 2 * 4 * 128 * 2        # K and V of one position, 16 layers
    # two lanes at positions 9 and 99: 10 + 100 keys read, 2 rows written,
    # 2 embedding rows looked up
    want = weights + 2 * 2 * 4608 + row * (10 + 100 + 2)
    assert flops.decode_bytes(cfg, [9, 99]) == want


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.load_peaks("TPU v9 imaginary")
    assert flops.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
