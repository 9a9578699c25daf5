"""FLOP and useful-byte counts of the benchmark, against hand counts."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import counts  # noqa: E402

TINY = {"table_sizes": [10, 20, 30], "n_dense_features": 4, "embed_dim": 8,
        "bottom_mlp": [16, 8], "top_mlp": [12, 1], "dtype": "float32"}


def test_dense_flops_by_hand():
    # bottom 4->16->8: 2*(4*16 + 16*8) = 384
    # interaction: F = 4 features, 6 pairs of 8-wide dots: 6 * 8 * 2 = 96
    # top (6 + 8)->12->1: 2*(14*12 + 12*1) = 360
    assert counts.dense_flops(TINY) == 384 + 96 + 360


def test_request_flops_add_one_add_per_pooled_element():
    got = counts.request_flops(TINY, np.array([0, 5]))
    np.testing.assert_array_equal(got, [840, 840 + 5 * 8])


def test_pooling_bytes_by_hand():
    # 5 valid indices: 5 rows of 8 f32 (160 B) + 5 ids + 5 weights (40 B);
    # 3 pooled rows of 8 f32 written (96 B)
    np.testing.assert_array_equal(counts.pooling_bytes(TINY, [5]), [296])


def test_kaggle_request_flops():
    cfg = dict(TINY, table_sizes=[1] * 26, n_dense_features=13,
               embed_dim=64, bottom_mlp=[512, 256, 64], top_mlp=[512, 256, 1])
    bottom = 2 * (13 * 512 + 512 * 256 + 256 * 64)
    inter = 27 * 26 // 2 * 64 * 2
    top = 2 * ((351 + 64) * 512 + 512 * 256 + 256 * 1)
    assert counts.dense_flops(cfg) == bottom + inter + top
