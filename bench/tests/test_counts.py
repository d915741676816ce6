"""The operation and byte counts the roofline and MFU metrics divide by."""
import pytest

import roofline
import traffic
from common import load_json, load_module


def _counts(cell):
    w = load_json("workloads", cell + ".json")
    c = load_json("configs", w["config"] + ".json")
    gen = traffic.make(w["traffic"], c, 0)
    return c, gen, load_module("configs", w["config"]).counts(
        c, gen, w["chips"])


def test_qwen3_two_layer_step_is_154_5_g_macs():
    _, _, k = _counts("qwen3-1.7b.train-lut20")
    # 50.3 M weights a layer x 512 tokens x 3 products x 2 layers.
    assert k["macs"] == 512 * 3 * 2 * (2048 * (2048 + 1024 + 1024) +
                                      2048 * 2048 + 3 * 2048 * 6144)
    assert round(k["macs"] / 1e9, 1) == 154.6
    assert len(k["mac_calls"]) == 2 * 7 * 3
    head = 3 * 2 * 512 * 2048 * 151936
    assert k["model_ops"] == 2 * k["macs"] + head + 2 * 3 * 4 * 4 * 16 * \
        128 * 128 * 128
    assert k["items_per_step"] == 512


@pytest.mark.parametrize("cell,rows,chips", [("paper-mlp.online-b5", 5, 1),
                                             ("paper-mlp.dp4-b256", 256, 4)])
def test_paper_mlp_counts(cell, rows, chips):
    _, _, k = _counts(cell)
    m = rows // chips
    per_sample = 784 * 100 * 2 + 100 * 10 * 3
    assert k["macs"] == per_sample * rows
    assert k["model_ops"] == 2 * per_sample * rows
    assert sum(a * b * c for a, b, c in k["mac_calls"]) == per_sample * m


def test_mac_roofline_bound_and_peaks():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    least, by = roofline.mac_least_s([(512, 2048, 6144)], pk)
    assert by == "compute"
    assert least == pytest.approx(2 * 512 * 2048 * 6144 / 197e12)
    least, by = roofline.mac_least_s([(5, 784, 100)], pk)
    assert by == "memory"
    assert least == pytest.approx(2 * (5 * 784 + 784 * 100 + 500) / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v4")
    assert roofline.mfu(197e12, 3, 3.0, 1, pk) == pytest.approx(100.0)
