"""A corpus that changes while it is served (ISSUE 34), at a size the CPU
holds: a runbook of 20 cycles — insert a cluster range, search, delete the
oldest range of another cluster — through ``Frontend.upsert`` / ``.submit``
/ ``.delete`` on a 32 k x 100 serial index (L2, fractional rows, a width
off the lane grid), against the plain model of the index
(``benchmark/reference_stream.py``) at EVERY search step."""

import numpy as np
import pytest

from benchmark import reference_stream, runbook
from benchmark.harness import load_by_path
from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.frontend import Frontend, SLOPolicy
from mpi_knn_tpu.resilience import ResiliencePolicy
from mpi_knn_tpu.serve import ServeSession, build_index

ROWS, DIM, K, CYCLES = 32768, 100, 10, 20
CONFIG = {"rows": ROWS, "dim": DIM, "k": K, "data": {
    "generator": "clustered_f32_stream", "clusters": 32, "block_rows": 128,
    "cluster_sigma": 0.25, "sub_sigma": 0.15, "sigma": 0.08}}
MIX = {"range_rows": 256, "max_cycles": CYCLES, "warm_cycles": 0,
       "checkpoints": [1, 10, 20], "query_pool_rows": 64}


@pytest.fixture(scope="module")
def world():
    seed = 2**31 + 5
    gen = load_by_path("datagen", "clustered_f32_stream")
    book = runbook.plan(CONFIG, MIX, seed)
    subs = gen.sub_centres(seed, CONFIG["data"], DIM,
                           book["cluster_of_block"])
    X = np.asarray(gen.device_corpus(seed, ROWS, DIM, CONFIG["data"], subs))
    added = np.concatenate([
        gen.host_block(seed, CONFIG["data"], b, subs[b])
        for c in book["cycles"]
        for b in runbook.blocks_of(c["insert"], 128)])
    pool = gen.query_rows(seed, CONFIG["data"],
                          runbook.pool_targets(book, MIX, seed), subs)
    return book, X, added, pool


def test_runbook_through_the_front_end_against_the_model(world):
    book, X, added, pool = world
    index = build_index(X, KNNConfig(
        k=K, backend="serial", query_tile=64, corpus_tile=2048,
        query_bucket=64, bucket_headroom=0.1, mutation_bucket=128,
        exclude_zero=False))
    assert index.onepass is None  # fractional rows: the multi-pass dot
    fe = Frontend(ServeSession(index, resilience=ResiliencePolicy()),
                  SLOPolicy(max_batch_rows=128, max_wait_s=0.001,
                            max_queue_rows=8192)).start(warm_sizes=[64])
    model = reference_stream.StreamModel([(0, X), (ROWS, added)], ROWS)
    deleted = np.zeros(model.ids, dtype=bool)
    searched = touched = 0
    try:
        for cycle in book["cycles"]:
            for op in runbook.steps(cycle):
                lo, hi = op.get("start"), op.get("end")
                if op["operation"] == "insert":
                    for a in range(lo, hi, 128):
                        out = fe.upsert("writer", np.arange(a, a + 128),
                                        added[a - ROWS:a - ROWS + 128])
                        assert out["upserted"] == 128
                elif op["operation"] == "delete":
                    for a in range(lo, hi, 128):
                        out = fe.delete("writer", np.arange(a, a + 128))
                        assert out["deleted"] == 128 and not out["missing"]
                    deleted[lo:hi] = True
                else:
                    d, i = fe.submit("reader", pool).result(timeout=120)
                    ref_d, ref_i = model.exact_knn_live(pool, K)
                    assert (i == ref_i).mean() > 0.999
                    assert np.allclose(d, ref_d, rtol=2e-4)
                    assert not deleted[i].any()
                    searched += 1
                    touched += int((ref_i >= ROWS).any(axis=1).sum())
                model.apply(op)
            assert index.live_rows == ROWS  # between cycles
    finally:
        fe.stop()
    assert searched == CYCLES
    assert touched > 0.25 * CYCLES * len(pool)  # the writes decide answers
    # tombstones sit inside full tiles, and inserted rows took their place
    ids = np.asarray(index.tile_ids)
    assert (ids[: ROWS // 2048] >= ROWS).any()
    assert int((ids >= 0).sum()) == ROWS
