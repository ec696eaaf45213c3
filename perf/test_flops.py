"""The operation and byte counts of the yardstick, and its peak table."""
import pytest

from perf import flops as F
from perf.peaks import peaks_for


def test_cnn_conv_flops_by_hand():
    # c1: 28x28 outputs x 16 channels, each 3x3x1 multiply-adds.
    assert F.conv2d_same_flops(28, 28, 3, 1, 16) == 2 * 28 * 28 * 16 * 9
    assert F.conv2d_same_flops(28, 28, 3, 1, 16) == 225_792
    # c2 runs on the 14x14 map left by the first 2x2 pool.
    assert F.conv2d_same_flops(14, 14, 3, 16, 32) == 2 * 14 * 14 * 32 * 144
    head = 2 * 1568 * 128 + 2 * 128 * 10
    assert F.cnn_forward_flops(28, (16, 32), 3, 128, 10) == (
        225_792 + 1_806_336 + head)


def test_cnn_train_flops_of_the_config():
    import json
    import os
    from perf.configs import cnn_fmnist_ref as ref
    here = os.path.dirname(os.path.abspath(__file__))
    conf = json.load(open(os.path.join(here, "configs", "cnn_fmnist.json")))
    assert ref.train_flops_per_row(conf, {}) == 3 * 2_436_096


@pytest.mark.parametrize("c,f,g", [(256, 206_874, 1), (2, 361_821_120, 1),
                                   (20, 1000, 20)])
def test_mix_aggregate_cost(c, f, g):
    flops, nbytes = F.mix_aggregate_cost(c, f, g)
    assert flops == 2 * g * c * f
    # the fleet (C, F), the output (G, F) and the weights (G, C), fp32
    assert nbytes == 4 * c * f + 4 * g * f + 4 * g * c


def test_peaks_of_v5e():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError):
        peaks_for(kind)
