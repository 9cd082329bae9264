"""Least time of the DBB linears against hand-computed granite-3-8b
numbers."""

import json

import pytest

from bench import cells, counts, linear_counts


@pytest.fixture(scope="module")
def granite():
    return json.loads((cells.CONFIG_DIR / "granite-3-8b.json").read_text())["model"]


def test_shapes_are_the_dense_count(granite):
    shapes = linear_counts.linear_shapes(granite)
    assert len(shapes) == 40 * 7 + 1
    head = shapes[-1]
    assert head == (4096, 49408)  # the stored, padded vocabulary
    macs = sum(k * n for k, n in shapes[:-1]) + 4096 * granite["vocab"]
    assert macs == counts.dense_macs_per_token(granite)


def test_granite_packed_bytes(granite):
    # int8 values of 4 in 8 rows, one mask byte per 8 rows, f32 scales:
    # q and o 10,502,144; k and v 2,625,536; gate and up 32,819,200;
    # down 32,784,384; head 126,682,112
    layer = 2 * 10_502_144 + 2 * 2_625_536 + 2 * 32_819_200 + 32_784_384
    assert linear_counts.packed_weight_bytes(granite) == 40 * layer + 126_682_112
    assert linear_counts.packed_weight_bytes(granite) == 5_113_807_872


def test_least_time_takes_the_longer_bound(granite):
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    weights_s = 5_113_807_872 / 819e9
    # a decode iteration of 16 rows reads every weight: bandwidth-bound
    assert linear_counts.least_linear_s(granite, 16, peaks) == pytest.approx(weights_s)
    # a full mixed step of 16 x 64 positions computes longer than it reads
    ops_s = 2 * 1024 * 8_170_516_480 / 393e12
    assert ops_s > weights_s
    assert linear_counts.least_linear_s(granite, 1024, peaks) == pytest.approx(ops_s)
