"""The ops-and-bytes functions on hand-worked cases, and the peaks table."""
import pytest

from benchmark.harness import device
from benchmark.models import dense_decoder as fam

D = {"V": 32768, "D": 4096, "F": 14336, "L": 16, "H": 32, "KV": 8,
     "hd": 128, "theta": 1e6, "eps": 1e-5}


def test_decode_batch_32_context_1000():
    # K and V read once: 2 x 32 seqs x 1000 keys x 8 heads x 128 x 2 B
    kv = 2 * 32 * 1000 * 8 * 128 * 2
    # q in and out out: 2 x 32 x 32 heads x 128 x 2 B
    qo = 2 * 32 * 32 * 128 * 2
    c = fam.decode_attention_cost(D, [1000] * 32)
    assert c["bytes"] == kv + qo == 131_596_288
    # QK^T and PV: 2 x 2 x 32,000 keys x 32 heads x 128
    assert c["flops"] == 4 * 32000 * 32 * 128 == 524_288_000
    t, bound = fam.roofline_seconds(c, device.peaks("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(131_596_288 / 819e9)
    assert fam.decode_attention_cost(D, [1000] * 32, layers=16)["bytes"] \
        == 16 * c["bytes"]


def test_causal_attention_8_by_2048():
    c = fam.causal_attention_cost(D, 8, 2048)
    # half of 2 matmuls x 2 flops x 8 x 32 heads x 2048^2 x 128
    assert c["flops"] == 4 * 8 * 32 * 128 * 2048 * 2048 / 2
    # q, out (32 heads) and k, v (8 heads), bf16
    assert c["bytes"] == 8 * 2048 * (64 + 16) * 128 * 2
    b = fam.causal_attention_cost(D, 8, 2048, backward=True)
    assert b["flops"] == 2.5 * c["flops"] and b["bytes"] == 2 * c["bytes"]
    _, bound = fam.roofline_seconds(c, device.peaks("TPU v5 lite"))
    assert bound == "compute"


def test_train_flops_per_token_matches_the_programs():
    from paddle_tpu.nlp import llama
    cfg = fam.program_config({"model": {
        "vocab_size": 32768, "hidden_size": 4096, "intermediate_size": 14336,
        "num_hidden_layers": 8, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 32768, "rms_norm_eps": 1e-5,
        "rope_theta": 1e6, "torch_dtype": "bfloat16"}})
    d = {**D, "L": 8}
    assert fam.train_flops_per_token(d, 2048) == \
        llama.flops_per_token(cfg, 2048)
    assert fam.num_params(d) == llama.num_params(cfg)
    # 8 layers x 218.1 M + 2 x 134.2 M
    assert abs(fam.num_params(d) - 2.013e9) < 2e6


def test_peaks_table_refuses_an_unknown_device():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9 \
        and p["int8_ops"] == 393e12
    with pytest.raises(ValueError, match="no published peaks"):
        device.peaks("cpu")
