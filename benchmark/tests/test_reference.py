"""The plain reference against tests/oracle.py's float64 oracle, and the
comparison's numbers."""

import os
import sys

import numpy as np

from benchmark import compare, reference
from benchmark.datagen import clustered_u8

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tests"))
from oracle import oracle_all_knn  # noqa: E402

SPEC = {"centres": 10, "centre_scale": 255.0, "sigma": 25.0}
LIMITS = {"recall_min": 0.999, "tie_rtol": 1e-5, "dist_rel_err_max": 1e-4}


def corpus(seed=3, rows=1024, dim=784):
    return np.asarray(clustered_u8.device_corpus(seed, rows, dim, SPEC,
                                                 chunk_rows=512))


def test_data_are_whole_numbers_in_range_and_seeded():
    x = corpus()
    assert x.dtype == np.float32 and x.min() >= 0 and x.max() <= 255
    assert (x == np.rint(x)).all()
    assert (x == corpus()).all() and (x != corpus(seed=2**31 + 3)).any()
    cen = clustered_u8.centres(3, SPEC, 784)
    rows = clustered_u8.host_rows(np.random.default_rng(0), 64, cen, SPEC)
    assert rows.shape == (64, 784) and (rows == np.rint(rows)).all()


def test_all_pairs_with_self_excluded_equals_the_float64_oracle():
    x = corpus(rows=512, dim=128)
    probe = np.arange(100, 164)
    d, i = reference.exact_knn(x, x[probe], 10, self_ids=probe,
                               block_rows=128)
    od, oi = oracle_all_knn(x, 10)
    assert (i == oi[probe]).all()
    assert np.array_equal(d.astype(np.float64), od[probe])  # exact sums


def test_query_mode_and_zero_exclusion():
    x = corpus(rows=1024, dim=128)
    q = np.concatenate([x[:3], x[5:8] + 1.0])  # three rows are corpus rows
    d, i = reference.exact_knn(x, q, 10, block_rows=256)
    od, oi = oracle_all_knn(x, 10, queries=q)
    assert (i == oi).all() and np.array_equal(d.astype(np.float64), od)
    assert (d[:3, 0] > 0).all()  # the zero-distance self match is left out
    d0, i0 = reference.exact_knn(x, q, 10, exclude_zero=False, block_rows=256)
    assert (d0[:3, 0] == 0).all() and (i0[:3, 0] == [0, 1, 2]).all()


def test_comparison_numbers():
    x = corpus(rows=1024, dim=128)
    d, i = reference.exact_knn(x, x[:32] + 1.0, 10, block_rows=256)
    ok = compare.compare_answers(i, d, i, d, LIMITS)
    assert ok["ok"] and ok["numbers"]["recall_at_k"][0] == 1.0
    # a near-tie swap is a hit, a wrong neighbour is not
    i2, d2 = i.copy(), d.copy()
    i2[0, 9] = 999999
    assert compare.compare_answers(i2, d2, i, d, LIMITS)["ok"]
    d2[0, 9] *= 1.01
    bad = compare.compare_answers(i2, d2, i, d, LIMITS)
    assert not bad["ok"] and not bad["numbers"]["dist_rel_err_max"][2]
    i3 = np.roll(i, 1, axis=0)
    bad = compare.compare_answers(i3, d, i, d, LIMITS)
    assert not bad["ok"] and bad["numbers"]["recall_at_k"][0] < 0.5
    d4 = d.copy()
    d4[3, ::-1] = d[3]
    assert not compare.compare_answers(i, d4, i, d, LIMITS)[
        "numbers"]["not_finite_or_not_ascending"][2]
    assert not compare.compare_answers(i[:, :5], d[:, :5], i, d, LIMITS)["ok"]
