"""The FLOP count from shapes and the table of peaks."""

import json
import os

import pytest

from benchmark import flops, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def test_resnet50_check_values():
    macs = flops.resnet50_forward_macs()
    assert abs(macs / 1e9 - 4.09) < 0.01            # 4.09 GMAC forward
    assert abs(flops.train_flops(macs) / 1e9 - 24.5) < 0.1


def test_yolov3_check_values():
    macs = flops.yolov3_forward_macs()
    assert abs(2 * macs / 1e9 - 65.9) < 0.1         # 65.9 GFLOP forward
    assert abs(flops.train_flops(macs) / 1e9 - 197.6) < 0.2


@pytest.mark.parametrize("name,fn", [
    ("resnet50", flops.resnet50_forward_macs),
    ("yolov3-416", flops.yolov3_forward_macs)])
def test_config_files_hold_the_count(name, fn):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        config = json.load(f)
    assert config["forward_macs_per_image"] == fn()
    assert config["train_flops_per_image"] == flops.train_flops(fn())


def test_v5e_row_and_unknown_device():
    row = peaks.lookup("tpu", "TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["hbm_bytes"] == 16e9
    with pytest.raises(LookupError):
        peaks.lookup("cpu", "cpu")
    with pytest.raises(LookupError):
        peaks.lookup("tpu", "TPU v9 imaginary")
