"""Operation and byte counts against a hand count, and the peaks table."""

import json

import pytest

import tiny  # noqa: F401
from harness import counts, spec


def _dims(name):
    return spec.dims(tiny.ROOT, json.loads(
        (tiny.BENCH / "configs" / f"{name}.json").read_text()))


def test_qwen3_layer_by_hand():
    m = _dims("qwen3-0.6b")
    # q 1024x2048, k and v 1024x1024 each, o 2048x1024, gate/up/down 1024x3072
    by_hand = (1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
               + 3 * 1024 * 3072)
    assert m.layer_matrix_params == by_hand
    # at 100 positions: 2 per weight, plus q.k and p.v over 16 heads of 128
    assert counts.layer_token_flops(m, 100) == 2 * by_hand + 4 * 16 * 128 * 100
    # keys and values, 28 layers, 8 heads of 128, bf16
    assert m.state_bytes_per_token == 2 * 28 * 8 * 128 * 2
    assert m.fixed_state_bytes == 0
    # the whole model: 0.44e9 in layers plus the tied embedding
    total = 28 * by_hand + 151936 * 1024
    assert abs(total - 596e6) < 1e6


def test_mamba2_layer_by_hand():
    m = _dims("mamba2-2.7b")
    # in_proj 2560 -> z 5120, x 5120, B 128, C 128, dt 80; out_proj 5120 -> 2560
    in_proj = 2560 * (5120 + 5120 + 128 + 128 + 80)
    assert m.layer_matrix_params == in_proj + 5120 * 2560
    conv = 2 * 4 * (5120 + 256)
    recurrence = 5 * 80 * 64 * 128
    assert counts.layer_token_flops(m, 7) == 2 * (in_proj + 5120 * 2560) + conv + recurrence
    # conv window (3 x 5376) and SSM state (80 x 64 x 128), 64 layers, bf16
    assert m.fixed_state_bytes == 64 * 2 * (3 * 5376 + 80 * 64 * 128)
    assert m.state_bytes_per_token == 0


def test_step_cost_counts_weights_once():
    m = _dims("qwen3-0.6b")
    one = counts.step_cost(m, [(10, True)])
    two = counts.step_cost(m, [(10, True), (10, True)])
    assert two[0] == 2 * one[0]
    extra = two[1] - one[1]
    assert extra == m.d * m.item + 10 * m.state_bytes_per_token
    fill = counts.step_cost(m, [(10, False)])
    assert one[1] - fill[1] == counts.head_bytes(m)


def test_unknown_device_kind_is_an_error():
    assert spec.peaks(tiny.ROOT, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks(tiny.ROOT, "TPU v99")
