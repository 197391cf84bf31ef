"""The shape-derived counts and the peaks table."""
import json

import pytest

import peaks
from conftest import BENCH
from counts import train_flops_paper_cnn as flops
from counts import wire_bytes


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, per_sample, per_round", [
    ("cnn-cifar10-table3", 5_879_040, 37_625_856_000),
    ("cnn-fmnist-table2", 4_416_768, 21_200_486_400),
])
def test_training_flops(name, per_sample, per_round):
    c = cfg(name)
    assert flops.per_sample(c) == per_sample
    assert flops.per_round(c) == per_round


@pytest.mark.parametrize("name", ["cnn-cifar10-table3",
                                  "cnn-fmnist-table2"])
def test_config_params_match_shapes(name):
    import numpy as np

    from models import paper_cnn

    c = cfg(name)
    n = sum(int(np.prod(s)) for s in paper_cnn.cnn_shapes(c).values())
    assert n == c["params"]


@pytest.mark.parametrize("b, bw", [(2, 2), (3, 4), (4, 4), (8, 8),
                                   (10, 16), (16, 16)])
def test_code_width(b, bw):
    assert wire_bytes.code_width(b) == bw


def test_code_width_refuses_wide_codes():
    with pytest.raises(ValueError):
        wire_bytes.code_width(17)


def test_wire_bytes_from_shapes():
    # d = 128 elements, b = 4: one row; planes 4 + 4 + 16 words, 8-float
    # header
    assert wire_bytes.plane_bytes(128, 4) == 4 * (4 + 4 + 16 + 8)
    assert wire_bytes.encode(2, 128, 4) == 2 * 128 * 4 + 2 * 128
    assert wire_bytes.reduce(2, 128, 4) == 2 * 128 + 2 * 4 + 128 * 4
    # the paper's CIFAR-10 point: 40 users read 74.0 MB of deltas
    assert wire_bytes.encode(40, 462_410, 4) - 40 * \
        wire_bytes.plane_bytes(462_410, 4) == 4 * 40 * 462_410


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
