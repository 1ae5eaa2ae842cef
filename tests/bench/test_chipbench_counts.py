"""The benchmark's FLOP and byte counts against hand counts."""
import json

import pytest

import _chipbench_tiny as tiny
from chipbench import counts

CONFIGS = tiny.BENCH / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_paper_mlp_flops_params_and_round_bytes_by_hand():
    cfg = _cfg("paper-mlp")
    assert counts.mlp_param_count(cfg) == 784 * 32 + 32 + 32 * 10 + 10 == 25_450
    # forward 2(784*32 + 32*10), weight gradients the same, hidden gradient 2*32*10
    assert counts.mlp_train_flops_per_sample(cfg) == 4 * (25_088 + 320) + 640 == 102_272
    state = counts.pisco_round_state_bytes(4096, 4 * 25_450)
    assert state == 6 * 4096 * 101_800 == 2_501_836_800
    batch = counts.round_batch_bytes(4096, 2, 16, 4 * 784 + 4)
    assert batch == 3 * 4096 * 16 * 3140 == 617_349_120


def test_roofline_takes_the_binding_peak():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops = 4096 * 3 * 16 * 102_272
    nbytes = 2_501_836_800 + 617_349_120
    assert counts.roofline_seconds(flops, nbytes, peaks) == pytest.approx(nbytes / 819e9)
    assert counts.roofline_seconds(1e15, 1.0, peaks) == pytest.approx(1e15 / 197e12)


def test_family_round_counts_follow_the_traffic():
    from chipbench import registry

    def family(name):
        return registry.load_module([tiny.BENCH], "families", name)

    def traffic(name):
        return json.loads((tiny.BENCH / "traffic" / f"{name}.json").read_text())

    mlp, ring4096 = family("mlp"), traffic("ring4096")
    assert mlp.bytes_per_round(_cfg("paper-mlp"), ring4096) == 3_119_185_920
    assert mlp.flops_per_round(_cfg("paper-mlp"), ring4096) == 4096 * 3 * 16 * 102_272
