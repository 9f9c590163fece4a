"""The control of ``correct``: the reference put in the program's place and
computed in the nearest precision below the one each configuration states
has to fail a limit, at a size a test run holds. (On the chip the control is
the program itself with the configuration's ``control`` switched on,
``run.py --control``; PERF.md has those readings. The CPU backend computes
every matmul precision in float32, so here the lower precision is emulated:
operands rounded to bfloat16 pieces, products summed in float32.)"""

import json
import os

import numpy as np
import pytest

from benchmark import compare, reference
from benchmark.datagen import clustered_u8

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bf16_pieces(x, n):
    """x as a sum of n bfloat16 numbers (float32 arrays), largest first."""
    import jax.numpy as jnp

    out, rest = [], np.asarray(x, np.float32)
    for _ in range(n):
        p = np.asarray(jnp.asarray(rest).astype(jnp.bfloat16).astype(jnp.float32))
        out.append(p)
        rest = rest - p
    return out


def lower_precision_knn(corpus, queries, k, precision, self_ids=None):
    """Matmul-form squared L2 on centred data, the inner products in
    ``default`` (one bfloat16 pass), ``high`` (three passes) or ``highest``
    (float32) precision."""
    mu = corpus.mean(axis=0, dtype=np.float32)
    c, q = corpus - mu, queries - mu
    if precision == "highest":
        qc = q.astype(np.float64) @ c.astype(np.float64).T
    else:
        n = {"default": 1, "high": 2}[precision]
        qp, cp = bf16_pieces(q, n), bf16_pieces(c, n)
        pairs = [(0, 0)] if n == 1 else [(0, 0), (0, 1), (1, 0)]
        qc = sum(qp[i].astype(np.float64) @ cp[j].astype(np.float64).T
                 for i, j in pairs)
    d2 = ((q * q).sum(1)[:, None] + (c * c).sum(1)[None, :] - 2 * qc
          ).astype(np.float32)
    if self_ids is not None:
        d2[np.arange(len(q)), self_ids] = np.inf
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d2, order, axis=1), order.astype(np.int32)


@pytest.mark.parametrize("name,rows,seeds", [
    ("mnist8m-784-l2", 8192, (11, 12, 2**31 + 13)),
    ("bigann10m-128-l2", 32768, (21, 22, 2**31 + 23)),
])
def test_lower_precision_fails_a_limit_and_the_stated_one_passes(
        name, rows, seeds):
    cfg = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    stated = cfg["knn"].get("matmul_precision") or "highest"
    lower = cfg["control"]["matmul_precision"]
    assert (stated, lower) in {("high", "default"), ("highest", "high")}
    for seed in seeds:
        x = np.asarray(clustered_u8.device_corpus(
            seed, rows, cfg["dim"], cfg["data"], chunk_rows=2048))
        rng = np.random.default_rng(seed)
        if cfg["exclude_self"]:
            ids = rng.choice(rows, 128, replace=False).astype(np.int32)
            q, self_ids = x[ids], ids
        else:
            cen = clustered_u8.centres(seed, cfg["data"], cfg["dim"])
            q, self_ids = clustered_u8.host_rows(rng, 128, cen, cfg["data"]), None
        ref_d, ref_i = reference.exact_knn(x, q, cfg["k"], self_ids=self_ids,
                                           exclude_zero=cfg["exclude_zero"])
        d, i = lower_precision_knn(x, q, cfg["k"], stated, self_ids)
        sound = compare.compare_answers(i, d, ref_i, ref_d, cfg["limits"])
        assert sound["ok"], sound["numbers"]
        d, i = lower_precision_knn(x, q, cfg["k"], lower, self_ids)
        control = compare.compare_answers(i, d, ref_i, ref_d, cfg["limits"])
        assert not control["ok"], control["numbers"]
