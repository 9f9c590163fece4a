"""opcount.py against numbers worked by hand for both configurations."""

import json
import os

import pytest

from benchmark import opcount

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]


def cfg(name):
    return json.load(open(os.path.join(BENCH, "configs", name + ".json")))


def test_mnist8m_one_call():
    c = cfg("mnist8m-784-l2")
    rows = c["rows"]
    assert rows % 8192 == 0 and c["dim"] == 784
    flops = opcount.knn_flops(4096, rows, 784)
    assert flops == 2 * 4096 * rows * 784
    nbytes = opcount.knn_bytes(4096, 1, rows, 784, 10)
    assert nbytes == rows * 784 * 4 + 4096 * 784 * 4 + 4096 * 10 * 8
    least, bound = opcount.least_seconds(4096, 1, rows, 784, 10, PEAKS)
    assert bound == "compute"  # 2*4096 flop for each 4 bytes read
    assert least == pytest.approx(flops / 197e12)


def test_bigann_full_batch_is_compute_bound_and_a_small_one_memory_bound():
    c = cfg("bigann10m-128-l2")
    rows = c["rows"]
    assert rows == 1221 * 8192 and c["dim"] == 128
    # 1024 rows: 2*1024*10002432*128 = 2.622e12 flop -> 13.3 ms; the corpus
    # read is 5.12e9 B -> 6.25 ms
    least, bound = opcount.least_seconds(1024, 1, rows, 128, 10, PEAKS)
    assert bound == "compute" and least == pytest.approx(0.013310, rel=1e-3)
    # 64 rows: 1.64e11 flop -> 0.83 ms, the same read -> 6.25 ms
    least, bound = opcount.least_seconds(64, 1, rows, 128, 10, PEAKS)
    assert bound == "memory" and least == pytest.approx(0.006253, rel=1e-3)
    # two batches read the corpus twice
    assert opcount.knn_bytes(128, 2, rows, 128, 10) == (
        2 * rows * 128 * 4 + 128 * 128 * 4 + 128 * 10 * 8)
