"""Operation and byte counts against values worked out by hand."""
import json
import os

import pytest

import counts

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smollm_close_c256_counts_match_hand_values():
    d = 361_821_120                    # smollm-360m at its published widths
    # 2 operations (multiply, add) per regenerated element per client
    assert counts.close_flops(d, 256) == 185_252_413_440
    # bf16 parameters read and written, 256 uint32 seeds, 256 float32 scalars
    assert counts.close_bytes(d, 256, 2) == 1_447_284_480 + 2_048


@pytest.mark.parametrize("k", [1, 4])
def test_close_counts_scale_with_block_scalars(k):
    assert counts.close_flops(1990, 256, k) == 2 * 256 * k * 1990
    assert counts.close_bytes(1990, 256, 4, k) == 2 * 1990 * 4 + 256 * (4 + 4 * k)


def test_smollm_parameter_count_matches_configuration():
    with open(os.path.join(CHIP, "configs", "smollm-360m.json")) as f:
        cfg = json.load(f)
    v, h, f_, L = (cfg["vocab_size"], cfg["hidden_size"],
                   cfg["intermediate_size"], cfg["num_hidden_layers"])
    kv = h // cfg["num_attention_heads"] * cfg["num_key_value_heads"]
    per_layer = 2 * h * h + 2 * h * kv + 3 * h * f_ + 2 * h
    assert v * h + h + L * per_layer == cfg["parameters"] == 361_821_120


def test_paper_mlp_parameter_count_matches_configuration():
    with open(os.path.join(CHIP, "configs", "paper-mlp.json")) as f:
        cfg = json.load(f)
    s = cfg["layer_sizes"]
    assert sum(a * b + b for a, b in zip(s[:-1], s[1:])) == cfg["parameters"] == 1990


def test_close_roofline_bound_is_hbm_at_c256():
    p = counts.load_peaks("TPU v5 lite")
    t_flops = counts.close_flops(361_821_120, 256) / p["bf16_flops_per_s"]
    t_bytes = counts.close_bytes(361_821_120, 256, 2) / p["hbm_bytes_per_s"]
    assert t_bytes > t_flops
    assert t_bytes == pytest.approx(1.7671e-3, rel=1e-3)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        counts.load_peaks("TPU v9 imaginary")
