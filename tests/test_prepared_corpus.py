"""The corpus is prepared once, not once a call (ISSUE 31): ``api.all_knn``
remembers the last device corpus it prepared, ``api.prepare_corpus`` hands
the handle out, and a hit, a miss, a handle and the path every call took
before (``center_for_l2`` + ``prepare_tiles`` + ``knn_chunk_update``; the
ring: the same centring, the shards placed by hand, ``_ring_knn_sharded``)
answer bit for bit the same.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from mpi_knn_tpu import KNNConfig, all_knn, api, prepare_corpus
from mpi_knn_tpu.backends.serial import (
    effective_tiles,
    knn_chunk_update,
    onepass_rule,
    prepare_tiles,
)
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.ops.distance import center_for_l2, onepass_fact
from mpi_knn_tpu.ops.topk import init_topk_tiles

BACKENDS = ("serial", "ring-overlap")
M, DIM, K = 1500, 24, 5


def cfg_for(backend: str, **kw) -> KNNConfig:
    """Tiles under 1024 columns keep the CPU off the interpreted lane-bin
    kernels; 1024-row query tiles (a device, on the ring) carry the
    one-pass rule."""
    base = dict(k=K, backend=backend, query_tile=1024, corpus_tile=512)
    if backend != "serial":
        base["num_devices"] = 4
    return KNNConfig().replace(**{**base, **kw})


def query_rows(backend: str) -> int:
    return 1024 if backend == "serial" else 4096


def rows(seed: int, n: int, whole: bool) -> np.ndarray:
    x = np.random.default_rng(seed).integers(0, 256, (n, DIM))
    return (x if whole else x + 0.25).astype(np.float32)


def counts() -> dict:
    reg = obs_metrics.get_registry()
    return {r: reg.counter("knn_corpus_prepare_total",
                           labels={"result": r}).value
            for r in ("hit", "miss", "bypass")}


def moved(before: dict) -> dict:
    return {r: int(v - before[r]) for r, v in counts().items()}


@pytest.fixture(autouse=True)
def nothing_remembered():
    api._remembered.clear()
    yield
    api._remembered.clear()


def same(a, b):
    return (np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
            and np.array_equal(np.asarray(a.dists), np.asarray(b.dists)))


def parent_path(X, Q, q_ids, cfg: KNNConfig):
    """What a call did before there was anything to keep: (dists, ids)."""
    corpus, queries, fact = X, Q, None
    if cfg.center and cfg.metric == "l2":
        corpus, queries, fact, _ = center_for_l2(X, Q, all_pairs=False)
    nq = Q.shape[0]
    if cfg.backend == "serial":
        q_tile, c_tile = effective_tiles(cfg, X.shape[0], nq)
        q_tiles, qid_tiles, c_tiles, c_ids, q_pad = prepare_tiles(
            corpus, queries, q_ids, cfg, q_tile, c_tile)
        carry = init_topk_tiles(q_pad // q_tile, q_tile, cfg.k,
                                dtype=jnp.float32)
        one = onepass_fact(cfg, fact) if onepass_rule(cfg, q_tile) else None
        d, i, *_ = knn_chunk_update(
            q_tiles, qid_tiles, c_tiles, c_ids, *carry, cfg, one)
        return (np.asarray(d).reshape(q_pad, cfg.k)[:nq],
                np.asarray(i).reshape(q_pad, cfg.k)[:nq])
    from mpi_knn_tpu.backends import ring
    from mpi_knn_tpu.parallel.mesh import make_ring_mesh
    from mpi_knn_tpu.parallel.partition import pad_rows_any

    mesh = make_ring_mesh(cfg.num_devices, axis_name=cfg.mesh_axis)
    q_axis, axis, dp, ring_n = ring.parse_ring_mesh(mesh)
    q_tile, c_tile, q_pad, c_pad = ring.ring_tiles(
        cfg, X.shape[0], nq, dp, ring_n)
    by_rows = NamedSharding(mesh, P(axis))
    dtype = jnp.dtype(cfg.dtype)
    one = (onepass_fact(cfg, fact) if onepass_rule(cfg, q_tile) else None)
    d, i, *_ = ring._ring_knn_sharded(
        jax.device_put(pad_rows_any(queries, q_pad, dtype=dtype), by_rows),
        jax.device_put(
            pad_rows_any(q_ids, q_pad, fill=-1, dtype=jnp.int32), by_rows),
        jax.device_put(pad_rows_any(corpus, c_pad, dtype=dtype), by_rows),
        ring._global_ids_on(by_rows, X.shape[0], c_pad),
        cfg, True, mesh, axis, q_tile, c_tile, q_axis=q_axis, onepass=one,
    )
    return np.asarray(d)[:nq], np.asarray(i)[:nq]


FORMS = {
    "l2-centred": {},
    "l2-uncentred": {"center": False},
    "cosine": {"metric": "cosine"},
}


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("with_ids", (True, False), ids=("ids", "no-ids"))
@pytest.mark.parametrize("whole", (True, False), ids=("whole", "fractional"))
@pytest.mark.parametrize("backend", BACKENDS)
def test_hit_miss_handle_and_parent_path_are_bit_identical(
        backend, whole, with_ids, form):
    cfg = cfg_for(backend, **FORMS[form])
    nq = query_rows(backend)
    X = rows(1, M, whole)
    # the queries: corpus rows (self-exclusion bites with ids) and strangers
    pick = np.random.default_rng(2).integers(0, M, nq).astype(np.int32)
    Q = X[pick].copy()
    Q[nq // 2:] = rows(3, nq - nq // 2, whole)
    ids = np.where(np.arange(nq) < nq // 2, pick, -1).astype(np.int32)
    ids = ids if with_ids else None
    q_ids = ids if with_ids else np.full(nq, -1, np.int32)

    Xd, Qd = jnp.asarray(X), jnp.asarray(Q)
    before = counts()
    miss = all_knn(Xd, queries=Qd, query_ids=ids, config=cfg)
    hit = all_knn(Xd, queries=Qd, query_ids=ids, config=cfg)
    assert moved(before) == {"hit": 1, "miss": 1, "bypass": 0}
    handle = prepare_corpus(Xd, config=cfg, query_rows=nq)
    brought = all_knn(handle, queries=Qd, query_ids=ids, config=cfg)
    want_d, want_i = parent_path(Xd, Qd, q_ids, cfg)
    for got in (miss, hit, brought):
        assert np.array_equal(np.asarray(got.ids), want_i)
        assert np.array_equal(np.asarray(got.dists), want_d)
    # the one-pass rule engaged where the data allows it, and nowhere else
    steps = np.asarray(hit.dist_steps)
    steps = steps.reshape(-1, steps.shape[-1]).sum(axis=0)
    assert np.array_equal(
        np.asarray(miss.dist_steps), np.asarray(hit.dist_steps))
    assert (steps[0] > 0) == (whole and form == "l2-centred")

    # a host corpus is never remembered, and answers as it always did
    before = counts()
    host = all_knn(X, queries=Q, query_ids=ids, config=cfg)
    assert moved(before) == {"hit": 0, "miss": 0, "bypass": 1}
    want_d, want_i = parent_path(X, Q, q_ids, cfg)
    assert np.array_equal(np.asarray(host.ids), want_i)
    assert np.array_equal(np.asarray(host.dists), want_d)


KEY_CHANGES = {
    # another array of equal shape and values is another corpus
    "other_array": lambda X, cfg, nq: (jnp.array(np.asarray(X)), cfg, nq),
    # the corpus tile follows the query rows: max_tile_elems caps the product
    "slice_height": lambda X, cfg, nq: (X, cfg, nq // 2),
    "dtype": lambda X, cfg, nq: (X, cfg.replace(dtype="bfloat16"), nq),
    "metric": lambda X, cfg, nq: (X, cfg.replace(metric="cosine"), nq),
    "center": lambda X, cfg, nq: (X, cfg.replace(center=False), nq),
    "corpus_tile": lambda X, cfg, nq: (X, cfg.replace(corpus_tile=128), nq),
}


@pytest.mark.parametrize("change", KEY_CHANGES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_another_corpus_or_another_form_misses(backend, change):
    nq = query_rows(backend)
    # 1024 x 256 elements a tile step: half the query rows, twice the tile
    cfg = cfg_for(backend, max_tile_elems=1024 * 256)
    X = jnp.asarray(rows(4, M, True))
    Q = jnp.asarray(rows(5, nq, True))
    all_knn(X, queries=Q, config=cfg)
    before = counts()
    all_knn(X, queries=Q, config=cfg.replace(k=K + 1))  # k shapes nothing
    assert moved(before) == {"hit": 1, "miss": 0, "bypass": 0}

    X2, cfg2, nq2 = KEY_CHANGES[change](X, cfg, nq)
    before = counts()
    got = all_knn(X2, queries=Q[:nq2], config=cfg2)
    assert moved(before) == {"hit": 0, "miss": 1, "bypass": 0}
    want = all_knn(np.asarray(X2), queries=np.asarray(Q[:nq2]), config=cfg2)
    assert np.array_equal(np.asarray(got.ids), np.asarray(want.ids))
    # one entry: the first form was replaced, and comes back as a miss
    before = counts()
    all_knn(X, queries=Q, config=cfg)
    assert moved(before) == {"hit": 0, "miss": 1, "bypass": 0}


@pytest.mark.parametrize("backend", BACKENDS)
def test_entry_and_its_arrays_go_with_the_callers_array(backend):
    cfg = cfg_for(backend)
    nq = query_rows(backend)
    Q = jnp.asarray(rows(5, nq, True))
    X = jnp.asarray(rows(6, M, True))
    all_knn(X, queries=Q, config=cfg).ids.block_until_ready()
    prepared = api._remembered.get(
        X, api._form_and_maker(backend, cfg, M, DIM, nq, None)[0])
    assert prepared is not None
    held = [weakref.ref(a) for a in vars(prepared).values()
            if isinstance(a, jax.Array)]
    assert len(held) >= 4  # the stack or the shards, ids, norms, offset, fact
    del prepared
    live = {id(a) for a in jax.live_arrays()}
    assert all(id(r()) in live for r in held)
    del X
    gc.collect()
    assert api._remembered._ref is None and api._remembered._prepared is None
    assert all(r() is None for r in held)
    assert all(a.shape != (M, DIM) and a.shape[-2:] != (512, DIM)
               for a in jax.live_arrays())


@pytest.mark.parametrize("backend", BACKENDS)
def test_host_corpus_changed_in_place_gives_the_changed_answer(backend):
    cfg = cfg_for(backend)
    nq = query_rows(backend)
    X, Q = rows(7, M, True), rows(8, nq, True)
    first = all_knn(X, queries=Q, config=cfg)
    X[np.asarray(first.ids)[:, 0]] += 64.0  # every nearest neighbour moves
    second = all_knn(X, queries=Q, config=cfg)
    fresh = all_knn(X.copy(), queries=Q, config=cfg)
    assert same(second, fresh) and not same(second, first)
    assert api._remembered._ref is None


@pytest.mark.parametrize("backend", BACKENDS)
def test_all_pairs_call_leaves_nothing_behind(backend):
    cfg = cfg_for(backend)
    X = jnp.asarray(rows(9, M, True))
    before = counts()
    got = all_knn(X, config=cfg)
    assert moved(before) == {"hit": 0, "miss": 0, "bypass": 1}
    assert api._remembered._ref is None
    want = all_knn(X, queries=X, query_ids=np.arange(M), config=cfg)
    assert same(got, want)


@pytest.mark.parametrize("traced", ("corpus", "queries"))
def test_under_an_outer_jit_nothing_is_remembered(traced):
    cfg = cfg_for("serial")
    X = jnp.asarray(rows(10, M, True))
    Q = jnp.asarray(rows(11, 1024, True))
    want = all_knn(np.asarray(X), queries=np.asarray(Q), config=cfg)
    api._remembered.clear()
    before = counts()
    if traced == "corpus":
        ids = jax.jit(lambda x, q: all_knn(x, queries=q, config=cfg).ids)(X, Q)
    else:
        ids = jax.jit(lambda q: all_knn(X, queries=q, config=cfg).ids)(Q)
    assert moved(before) == {"hit": 0, "miss": 0, "bypass": 1}
    assert api._remembered._ref is None  # no tracer escapes its trace
    assert np.array_equal(np.asarray(ids), np.asarray(want.ids))


@pytest.mark.parametrize("backend", BACKENDS)
def test_sliced_job_one_miss_then_hits_and_no_program_after_the_first_call(
        backend, tmp_path):
    """The benchmark's window: consecutive slices of ONE device array. One
    ``knn:api.prepare`` span and one miss, every later call a hit, and
    after the first call nothing is built or loaded (the ``jax.monitoring``
    events behind the benchmark's ``window_compiles``)."""
    cfg = cfg_for(backend)
    q = query_rows(backend)
    X = jnp.asarray(rows(12, 3 * q, True))
    take = jax.jit(lambda x, lo: jax.lax.dynamic_slice_in_dim(x, lo, q, 0))

    def call(n):
        lo = (n % 3) * q
        res = all_knn(X, queries=take(X, jnp.int32(lo)),
                      query_ids=np.arange(lo, lo + q, dtype=np.int32),
                      config=cfg)
        jax.block_until_ready((res.dists, res.ids))
        return res

    path = str(tmp_path / "flight.jsonl")
    obs_spans.set_recorder(obs_spans.FlightRecorder(path))
    obs_metrics.install_jax_compile_listener()
    reg = obs_metrics.get_registry()
    built = lambda: (reg.counter("jax_compiles_total").value  # noqa: E731
                     + reg.counter("jax_cache_loads_total").value)
    try:
        before = counts()
        first = call(0)
        programs = built()
        later = [call(n) for n in range(1, 6)]
        assert built() == programs
        assert moved(before) == {"hit": 5, "miss": 1, "bypass": 0}
    finally:
        obs_spans.set_recorder(None)
    spans, _ = obs_spans.reconstruct_spans(obs_spans.read_flight(path))
    names = [(s["cat"], s["name"]) for s in spans]
    assert names.count(("api", "prepare")) == 1
    assert names.count(("api", "all_knn")) == 6
    assert same(later[2], first)  # slice 0 again, now a hit
    whole = all_knn(np.asarray(X), queries=np.asarray(X[:q]),
                    query_ids=np.arange(q), config=cfg)
    assert np.array_equal(np.asarray(first.ids), np.asarray(whole.ids))


def test_a_handle_refuses_a_call_of_another_form():
    cfg = cfg_for("serial")
    X = jnp.asarray(rows(13, M, True))
    Q = jnp.asarray(rows(14, 1024, True))
    handle = prepare_corpus(X, config=cfg, query_rows=1024)
    with pytest.raises(ValueError, match="metric"):
        all_knn(handle, queries=Q, config=cfg.replace(metric="cosine"))
    with pytest.raises(ValueError, match="queries"):
        all_knn(handle, config=cfg)


def test_a_handle_of_a_host_corpus_is_a_snapshot():
    cfg = cfg_for("serial")
    X, Q = rows(15, M, True), rows(16, 1024, True)
    handle = prepare_corpus(X, config=cfg, query_rows=1024)
    want = all_knn(X.copy(), queries=Q, config=cfg)
    X += 64.0
    assert same(all_knn(handle, queries=Q, config=cfg), want)
