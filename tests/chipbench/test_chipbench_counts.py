"""The benchmark's own operation counts and peaks table."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import bench, counts, device  # noqa: E402


def _layers(name):
    cfg = bench.load_config(name)
    ref = bench.family(cfg, "ref")
    return ref.conv_layers(cfg, (cfg["image"], cfg["image"])), \
        ref.classifier(cfg)


@pytest.mark.parametrize("name,gflop,convs", [("resnet50", 8.18, 53),
                                              ("resnet101", 15.60, 104)])
def test_forward_flops_per_image(name, gflop, convs):
    layers, fc = _layers(name)
    assert len(layers) == convs
    assert round(counts.forward_flops(layers, fc) / 1e9, 2) == gflop
    assert sum(counts.on_kernel(l) for l in layers) == convs - 1


def test_training_is_three_forwards_less_the_stem_backward_data():
    layers, fc = _layers("resnet50")
    fwd = counts.forward_flops(layers, fc)
    stem = counts.conv_flops(layers[0])
    assert layers[0]["c"] == 3
    assert counts.train_flops(layers, fc) == pytest.approx(3 * fwd - stem)


def test_kernel_launches_match_the_compiled_programs():
    """52 Mosaic kernels per ResNet-50 forward and 165 per train step, as
    compiled for a v5e."""
    layers, _ = _layers("resnet50")
    assert counts.kernel_launches(layers, ("fwd",)) == 52
    assert counts.kernel_launches(layers, ("fwd_train", "bwd", "wu")) == 165


def test_ideal_time_is_the_larger_bound_per_pass():
    lay = dict(h=56, w=56, c=64, k=64, r=3, s=3, stride=1, padding=1, p=56,
               q=56, residual=False)
    peak = {"flops": 1e12, "hbm_bw": 1e9}
    flops = counts.conv_flops(lay) * 2
    bytes_ = counts.pass_bytes(lay, "fwd", 2)
    assert bytes_ == 4 * (2 * (56 * 56 * 64 * 2) + 9 * 64 * 64)
    assert counts.kernel_ideal_s([lay], ("fwd",), 2, peak) == pytest.approx(
        max(flops / 1e12, bytes_ / 1e9))


def test_unknown_device_kind_raises():
    assert device.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("TPU v99")
