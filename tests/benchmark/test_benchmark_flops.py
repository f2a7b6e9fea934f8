"""flops.py: operations and bytes from shapes, at the published widths."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import flops  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_olmo_1b_d4_has_371_m_parameters():
    p = flops.lm_parameters(_config("olmo-1b-d4"))
    assert p["per_layer_matmul"] == 67_108_864          # 4 E^2 + 3 E F
    assert p["head"] == 103_022_592                      # 50304 x 2048, tied
    assert p["matmul"] == 371_458_048
    assert p["total"] == 371_476_480                     # + 9 norm scales of 2048


def test_whole_model_is_the_published_1_18_b():
    whole = dict(_config("olmo-1b-d4"), num_hidden_layers=16)
    assert flops.lm_parameters(whole)["total"] == pytest.approx(1.177e9, rel=1e-3)


@pytest.mark.parametrize("config,batch,seq,tflop", [
    ("olmo-1b-d4", 4, 2048, 19.08), ("olmo-1b-0724-d4", 2, 4096, 19.91)])
def test_train_step_operations(config, batch, seq, tflop):
    assert flops.train_step_flops(_config(config), batch, seq) / 1e12 == pytest.approx(tflop, abs=0.01)


def test_attention_is_six_l_t_e_per_token():
    assert flops.attention_flops_per_token(_config("olmo-1b-d4"), 2048) == 6 * 4 * 2048 * 2048


def test_flash_cost_and_its_bound():
    cost = flops.flash_attention_cost(4, 2048, 16, 128)
    product = 4 * 16 * 2048 * 2048 * 128
    assert cost["forward"] == (2 * product, 4 * 4 * 2048 * 16 * 128 * 2)
    assert cost["backward_each"][0] == 2.5 * product
    seconds, bound = flops.roofline_seconds(*cost["forward"], PEAKS)
    assert bound == "compute" and seconds == pytest.approx(2 * product / 197e12)
    # a long row of few operations is bound by memory
    assert flops.roofline_seconds(1e6, 1e9, PEAKS) == (pytest.approx(1e9 / 819e9), "memory")
