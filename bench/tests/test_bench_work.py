"""Work functions against hand counts at small shapes."""
from types import SimpleNamespace

import numpy as np

from bench import spec


def test_distance_topk_hand_count():
    w = spec.module("work", "distance_topk")
    # N=4 rows, d=2, Q=3 queries, k=1
    # cross 2*4*2*3=48, row norms 2*4*2=16, query norms 2*3*2=12,
    # combine 3*4*3=36; bytes 4*(4*2 + 3*2) + 8*3*1
    assert w.work({"N": 4, "d": 2, "Q": 3, "k": 1}) == (112, 80)


def test_adc_topk_hand_count():
    w = spec.module("work", "adc_topk")
    # 10 valid candidates, m=2: 20 adds; bytes: codes+ids 10*(2+4),
    # tables 3*2*4*4, outputs 8*3*5
    s = {"Q": 3, "valid": 10, "m": 2, "n_codes": 4, "want": 5}
    assert w.work(s) == (20, 60 + 96 + 120)


def test_knn_step():
    knn = spec.module("work", "knn")
    cfg = {"k": 10, "data": {"n_base": 100, "d": 8}}
    np.testing.assert_array_equal(knn.query_flops([0, 1], cfg, {}),
                                  [1600.0, 1600.0])
    ops = knn.launch_ops(4, np.array([1, 2]), cfg, {})
    assert ops == {"distance_topk": {"N": 100, "d": 8, "Q": 4, "k": 10}}


def test_ann_counts_valid_candidates_of_the_probed_lists():
    ann = spec.module("work", "ann")
    # 3 cells on a line; list sizes 2, 1, 3 (-1 pads the capacity of 4)
    params = SimpleNamespace(
        centroids=np.array([[0.0, 0.0], [10.0, 0.0], [20.0, 0.0]]),
        cell_ids=np.array([[0, 1, -1, -1], [2, -1, -1, -1],
                           [3, 4, 5, -1]]))
    cfg = {"k": 2, "data": {"d": 2},
           "fitted": {"n_cells": 3, "pq_m": 2, "nprobe": 2, "refine": 3}}
    pool = np.array([[1.0, 0.0], [19.0, 0.0]], np.float32)
    c = ann.counters(SimpleNamespace(params=params), pool, cfg)
    # query 0 probes cells 0,1 (2+1), query 1 cells 2,1 (3+1); the zero
    # padding row probes cells 0,1
    np.testing.assert_array_equal(c["valid"], [3, 4])
    assert c["valid_pad"] == 3
    ops = ann.launch_ops(4, np.array([0, 1, 1]), cfg, c)
    assert ops["adc_topk"] == {"Q": 4, "valid": 3 + 4 + 4 + 3, "m": 2,
                               "n_codes": 256, "want": 3}
    assert ops["distance_topk"] == {"N": 3, "d": 2, "Q": 4, "k": 2}
    fixed = 2 * 3 * 2 + 2 * 256 * 2 + 2 * 3 * 2
    np.testing.assert_array_equal(ann.query_flops(np.array([0, 1]), cfg, c),
                                  [fixed + 2 * 3, fixed + 2 * 4])
