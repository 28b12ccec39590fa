"""The yardstick's counts: parameters, operations, kernel bytes, peaks."""

import json
import os

import pytest

from bench import flops

BENCH = os.path.dirname(os.path.abspath(__file__))


# granite-3.0-3b-a800m's published widths, cut to 4 of its 32 layers
GRANITE_L4 = {"n_layers": 4, "d_model": 1536, "n_heads": 24, "n_kv_heads": 8,
              "head_dim": 64, "vocab": 49155,
              "pattern": [{"mixer": "attn", "mlp": "moe"}],
              "moe": {"n_experts": 40, "top_k": 8, "d_ff": 512}}


def _model(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_granite_l4_matmul_active_params_per_token():
    # per layer: q,k,v,o 6,291,456 + router 61,440 + 8 experts x 2,359,296;
    # head over the published 49,155-token vocabulary, not the padded 53,248
    assert flops.matmul_params_per_token(GRANITE_L4) == \
        4 * 25_227_264 + 49_155 * 1_536 == 176_411_136


def test_mamba2_matmul_params_per_token_hand_count():
    # per layer: in_proj 768 x 3,352 + depthwise conv 4 x 1,792 + out_proj
    # 1,536 x 768 = 3,761,152; 24 layers + head 50,280 x 768
    assert flops.matmul_params_per_token(_model("mamba2-130m")) == \
        24 * 3_761_152 + 50_280 * 768 == 128_882_688


@pytest.mark.parametrize("name,count", [("mamba2-130m", 131_262_912)])
def test_param_counts_as_held(name, count):
    assert flops.param_count(_model(name)) == count


def test_sequence_terms():
    g = GRANITE_L4
    # causal attention: QK^T and PV, 2 * H * Dh * (S + 1) a token a layer
    assert flops.sequence_flops_per_token(g, 4096) == 4 * 2 * 24 * 64 * 4097
    m = _model("mamba2-130m")
    q = 256
    per_layer = 1 * 128 * (q + 1) + 24 * 64 * (q + 1) + 4 * 24 * 128 * 64
    assert flops.sequence_flops_per_token(m, 4096) == 24 * per_layer
    assert flops.train_flops_per_token(m, 4096) == 3 * (2 * 128_882_688 + 24 * per_layer)


def test_kernel_bytes_by_shape():
    b = flops.diana_kernel_bytes(1_000_000, 4, 2048)
    payload = 1e6 / 4 + 4e6 / 2048
    assert b["encode"] == pytest.approx(4e6 + payload)
    assert b["decode_sum_apply"] == pytest.approx(4 * payload + 12e6)
    assert b["total"] == pytest.approx(b["encode"] + b["decode_sum_apply"])


def test_peaks_known_and_unknown_kind():
    v5e = flops.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        flops.peaks_for("TPU v9 imaginary")
