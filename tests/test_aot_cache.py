"""The persistent on-disk executable cache (``serve/aotcache.py``,
ISSUE 12) — the cold-start contract, machine-checked:

- cold vs cached are BIT-identical on serial, clustered (ivf) and
  sharded-clustered (ivf-sharded) serving, and the cached "second start"
  (a fresh index + session over the same facts) warms with ZERO XLA
  backend compiles, proven through ``watch_compiles``;
- the fingerprint invalidates on anything that reaches the program:
  config (k), bucket, index facts (corpus size, at-rest dtype) — while
  same-shape different-VALUES corpora correctly share an entry (the
  executable is data-independent; the resident arrays are arguments);
- corrupted and truncated entries fall back to a REAL compile loudly
  (RuntimeWarning + ``aot_cache_errors_total``), never wrong answers,
  and the fresh compile overwrites the bad entry;
- a loaded executable whose signature does not match the cell's argspec
  is refused (defense in depth under fingerprint collision);
- concurrent writers race benignly through the atomic-rename protocol;
- ``warm()`` dedupes ladder rungs that resolve to an identical frozen
  program BEFORE anything lowers (saves compiles even with the cache
  disabled) and compiles distinct cells across a thread pool with
  bit-identical results;
- the zero-copy ``.npz`` mmap loader (``utils/npz_mmap``) reads every
  member identically to ``np.load`` and serves bit-identically;
- the front end's per-bucket warming admission and the doctor's cache
  probe round trip.
"""

import pickle
import threading

import numpy as np
import pytest

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.obs.metrics import get_registry, watch_compiles
from mpi_knn_tpu.serve import ServeSession, aotcache, build_index
from mpi_knn_tpu.serve.engine import get_executable

K = 5
DIM = 24


@pytest.fixture(autouse=True)
def _isolated_cache(monkeypatch):
    """Every test starts with no process-level cache configured and
    leaves none behind (other suites must keep running cache-off)."""
    monkeypatch.delenv(aotcache.ENV_VAR, raising=False)
    aotcache.reset_for_tests()
    # jax's own persistent cache off as well: an entry point that ran
    # earlier in this process leaves it on (``use_compile_cache``), and on
    # the CPU an executable that came out of it does not serialize whole
    # (its reload ends in "Function ... not found")
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", jax_dir)
    compilation_cache.reset_cache()
    aotcache.reset_for_tests()


def _corpus(rng, m=1536, clustered=False):
    if clustered:
        cents = rng.standard_normal((12, DIM)).astype(np.float32) * 4
        assign = rng.integers(0, 12, size=m)
        return (cents[assign]
                + rng.standard_normal((m, DIM)).astype(np.float32)).astype(
                    np.float32)
    return rng.standard_normal((m, DIM)).astype(np.float32)


def _serial_index(X, **over):
    return build_index(X, KNNConfig(k=K, query_bucket=64, **over))


def _ivf_index(X, **over):
    from mpi_knn_tpu.ivf import build_ivf_index

    return build_ivf_index(
        X, KNNConfig(k=K, partitions=8, nprobe=4, query_bucket=64, **over)
    )


def _sharded_index(X, shards=4, **over):
    from mpi_knn_tpu.ivf import shard_ivf_index

    return shard_ivf_index(_ivf_index(X, **over), shards=shards)


_BUILDERS = {
    "serial": _serial_index,
    "ivf": _ivf_index,
    "ivf-sharded": _sharded_index,
}


def _serve_once(index, Q):
    sess = ServeSession(index)
    sess.warm([Q.shape[0]])
    out = list(sess.stream([Q]))
    assert len(out) == 1
    return out[0].dists.copy(), out[0].ids.copy(), sess


def _counter_value(name: str) -> int:
    return int(get_registry().counter(name).snapshot()["value"])


# ---------------------------------------------------------------------------
# the headline contract: cold vs cached, bit-identical, zero compiles


@pytest.mark.parametrize("backend", ["serial", "ivf", "ivf-sharded"])
def test_cold_vs_cached_bit_identical_zero_compiles(
    rng, tmp_path, backend
):
    """A fresh index + session over the same facts (the in-process stand-
    in for a process restart: the in-memory executable cache is empty,
    the jit caches are never consulted because a disk hit skips lowering
    entirely) warms from disk with ZERO XLA backend compiles and serves
    bit-identically to the cold start."""
    aotcache.set_cache_dir(tmp_path / "aot")
    X = _corpus(rng, clustered=backend != "serial")
    Q = X[:48]

    d_cold, i_cold, sess = _serve_once(_BUILDERS[backend](X), Q)
    assert sess.warm_report["compiled"] >= 1
    assert _counter_value("aot_cache_stores_total") >= 1

    index2 = _BUILDERS[backend](X)
    sess2 = ServeSession(index2)
    with watch_compiles() as events:
        rep = sess2.warm([Q.shape[0]])
    assert events == [], (
        "cached warm must issue zero XLA backend compiles"
    )
    assert rep["compiled"] == 0 and rep["loaded"] == rep["cells"] >= 1
    out = list(sess2.stream([Q]))[0]
    np.testing.assert_array_equal(out.dists, d_cold)
    np.testing.assert_array_equal(out.ids, i_cold)


def test_same_shape_different_values_share_entry_correctly(rng, tmp_path):
    """The executable is data-independent (resident arrays are runtime
    ARGUMENTS): two same-shaped corpora share one entry, and the revived
    program still answers from the right corpus."""
    aotcache.set_cache_dir(tmp_path / "aot")
    X1, X2 = _corpus(rng), _corpus(rng)
    Q = X1[:16]
    d1, i1, _ = _serve_once(_serial_index(X1), Q)

    index2 = _serial_index(X2)
    sess2 = ServeSession(index2)
    rep = sess2.warm([16])
    assert rep["loaded"] == rep["cells"]  # shared entry: a hit
    out = list(sess2.stream([Q]))[0]
    # different corpus → different answers, from the SAME executable
    assert not np.array_equal(out.dists, d1)
    ref = _serve_once(build_index(X2, KNNConfig(k=K, query_bucket=64)),
                      Q)
    np.testing.assert_array_equal(out.dists, ref[0])
    np.testing.assert_array_equal(out.ids, ref[1])


# ---------------------------------------------------------------------------
# fingerprint invalidation


def test_fingerprint_invalidation_axes(rng, tmp_path):
    """Anything that reaches the program re-keys: config (k), bucket,
    index facts (corpus size, at-rest dtype). Host-only pacing knobs do
    NOT re-key (the in-memory fingerprint rule extends to disk)."""
    X = _corpus(rng)
    index = _serial_index(X)
    cfg = index.cfg
    base = aotcache.fingerprint(index, cfg, 64)
    assert aotcache.fingerprint(index, cfg.replace(k=K + 2), 64) != base
    assert aotcache.fingerprint(index, cfg, 128) != base
    assert aotcache.fingerprint(
        index, cfg.replace(precision_policy="mixed"), 64
    ) != base
    # host-only pacing knobs are canonicalized out
    assert aotcache.fingerprint(
        index, cfg.replace(dispatch_depth=7), 64
    ) == base
    # index facts: a different corpus size is a different program
    other = _serial_index(_corpus(rng, m=2048))
    assert aotcache.fingerprint(other, cfg, 64) != base
    # at-rest dtype changes both cfg and array facts
    bf16 = _serial_index(X, dtype="bfloat16")
    assert aotcache.fingerprint(
        bf16, bf16.cfg, 64
    ) != base


def test_config_change_misses_and_compiles(rng, tmp_path):
    aotcache.set_cache_dir(tmp_path / "aot")
    X = _corpus(rng)
    _serve_once(_serial_index(X), X[:16])
    misses0 = _counter_value("aot_cache_misses_total")
    index2 = _serial_index(X)
    sess2 = ServeSession(index2, config=index2.cfg.replace(k=K + 3))
    rep = sess2.warm([16])
    assert rep["compiled"] == rep["cells"] >= 1 and rep["loaded"] == 0
    assert _counter_value("aot_cache_misses_total") > misses0


# ---------------------------------------------------------------------------
# corruption: loud fallback, never wrong answers


def _single_entry(cache_dir):
    entries = sorted(cache_dir.glob(f"*{aotcache.ENTRY_SUFFIX}"))
    assert len(entries) == 1
    return entries[0]


@pytest.mark.parametrize("damage", ["corrupt", "truncate"])
def test_damaged_entry_falls_back_loudly(rng, tmp_path, damage):
    cache_dir = tmp_path / "aot"
    aotcache.set_cache_dir(cache_dir)
    X = _corpus(rng)
    Q = X[:16]
    d_cold, i_cold, _ = _serve_once(_serial_index(X), Q)

    path = _single_entry(cache_dir)
    blob = path.read_bytes()
    if damage == "corrupt":
        mid = len(blob) // 2
        path.write_bytes(blob[:mid] + bytes([blob[mid] ^ 0xFF])
                         + blob[mid + 1:])
    else:
        path.write_bytes(blob[: len(blob) // 2])

    errors0 = _counter_value("aot_cache_errors_total")
    index2 = _serial_index(X)
    sess2 = ServeSession(index2)
    with pytest.warns(RuntimeWarning, match="falling back to a real"):
        rep = sess2.warm([16])
    assert rep["compiled"] == rep["cells"]  # the loud fallback compiled
    assert _counter_value("aot_cache_errors_total") > errors0
    out = list(sess2.stream([Q]))[0]
    np.testing.assert_array_equal(out.dists, d_cold)
    np.testing.assert_array_equal(out.ids, i_cold)
    # the fresh compile OVERWROTE the bad entry: third start hits clean
    index3 = _serial_index(X)
    sess3 = ServeSession(index3)
    rep3 = sess3.warm([16])
    assert rep3["loaded"] == rep3["cells"]


def test_signature_mismatch_refused(rng, tmp_path):
    """Defense under fingerprint collision: an entry stored under the
    WRONG key (simulated by renaming) is refused by the argspec check,
    counted as an error, and recompiled."""
    from mpi_knn_tpu.serve.engine import expected_args

    cache_dir = tmp_path / "aot"
    cache = aotcache.AOTCache(cache_dir)
    X = _corpus(rng)
    index = _serial_index(X)
    cfg = index.cfg
    exec_ = get_executable(index, cfg, 64)
    key64 = aotcache.fingerprint(index, cfg, 64)
    assert cache.store(key64, exec_.compiled, meta={})
    # graft bucket 64's executable under bucket 128's key
    key128 = aotcache.fingerprint(index, cfg, 128)
    cache.entry_path(key64).rename(cache.entry_path(key128))
    # the key check inside the entry fires first; defeat it to reach the
    # signature check (a true collision would carry a matching key)
    doc = pickle.loads(cache.entry_path(key128).read_bytes())
    doc["key"] = key128
    cache.entry_path(key128).write_bytes(pickle.dumps(doc))
    errors0 = _counter_value("aot_cache_errors_total")
    with pytest.warns(RuntimeWarning, match="signature"):
        loaded = cache.load(
            key128, expect_args=expected_args(index, cfg, 128)
        )
    assert loaded is None
    assert _counter_value("aot_cache_errors_total") > errors0


def test_store_failure_is_nonfatal(rng, tmp_path):
    """A cache that cannot write (full/readonly disk) must not take
    serving down: store returns False, counted + warned."""
    cache = aotcache.AOTCache(tmp_path / "aot")
    X = _corpus(rng)
    index = _serial_index(X)
    exec_ = get_executable(index, index.cfg, 64)
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")
    cache.dir = blocker / "sub"  # every write now fails
    errors0 = _counter_value("aot_cache_errors_total")
    with pytest.warns(RuntimeWarning, match="cannot store"):
        ok = cache.store("deadbeef", exec_.compiled, meta={})
    assert ok is False
    assert _counter_value("aot_cache_errors_total") > errors0


# ---------------------------------------------------------------------------
# concurrency


def test_concurrent_writers_atomic_rename(rng, tmp_path):
    """N threads storing the same key race benignly: afterwards exactly
    one complete entry exists and loads cleanly (readers during the race
    see either nothing or a full entry — never a torn file)."""
    cache = aotcache.AOTCache(tmp_path / "aot")
    X = _corpus(rng)
    index = _serial_index(X)
    cfg = index.cfg
    exec_ = get_executable(index, cfg, 64)
    key = aotcache.fingerprint(index, cfg, 64)
    results = []

    def writer():
        results.append(cache.store(key, exec_.compiled, meta={}))

    def reader():
        # misses and hits are both fine mid-race; a torn read would
        # surface as an errors-counter bump, asserted below
        cache.load(key)

    threads = [threading.Thread(target=writer) for _ in range(6)]
    threads += [threading.Thread(target=reader) for _ in range(6)]
    errors0 = _counter_value("aot_cache_errors_total")
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(results)
    assert _counter_value("aot_cache_errors_total") == errors0
    assert cache.load(key) is not None
    assert cache.stats()["entries"] == 1
    # no leftover temp files from the race
    assert not list((tmp_path / "aot").glob("*.tmp"))


# ---------------------------------------------------------------------------
# warm: fingerprint dedupe + thread pool (work with the cache DISABLED)


def test_warm_dedupes_identical_rungs_before_lowering(rng):
    """The bucket/2 ladder rung pads many sizes to the same row count as
    its parent rung — same frozen program. warm() must collapse those to
    ONE cell before anything lowers: the report says so, and the compile
    count (the machine check) agrees."""
    from mpi_knn_tpu.resilience import ResiliencePolicy

    X = _corpus(rng)
    index = _serial_index(X)
    sess = ServeSession(
        index, resilience=ResiliencePolicy(batch_deadline_s=10.0)
    )
    assert len(sess.ladder) >= 2  # full + mixed + bucket/2
    rep = sess.warm([64])
    assert rep["raw_cells"] == len(sess.ladder)
    assert rep["deduped"] >= 1
    assert rep["cells"] == rep["raw_cells"] - rep["deduped"]
    assert rep["compiled"] == rep["cells"]
    assert len(index._cache) == rep["cells"]


def test_parallel_warm_bit_identical(rng):
    """Distinct cells compiled across the thread pool serve bit-
    identically to a sequential warm, and every cell lands exactly
    once."""
    X = _corpus(rng)
    sizes = [16, 64, 128, 256]
    Q = X[:100]

    index_seq = _serial_index(X)
    sess_seq = ServeSession(index_seq)
    sess_seq.warm(sizes, parallel=1)
    ref = list(sess_seq.stream([Q]))[0]

    index_par = _serial_index(X)
    sess_par = ServeSession(index_par)
    rep = sess_par.warm(sizes, parallel=4)
    assert rep["compiled"] == rep["cells"] == len(index_par._cache)
    out = list(sess_par.stream([Q]))[0]
    np.testing.assert_array_equal(out.dists, ref.dists)
    np.testing.assert_array_equal(out.ids, ref.ids)
    # a second warm touches nothing
    rep2 = sess_par.warm(sizes, parallel=4)
    assert rep2["reused"] == rep2["cells"] and rep2["compiled"] == 0


def test_warm_state_and_bucket_ready(rng):
    X = _corpus(rng)
    index = _serial_index(X)
    sess = ServeSession(index)
    assert not sess.bucket_ready(10)
    rep = sess.warm([10])
    assert sess.bucket_ready(10) and sess.bucket_ready(64)
    assert not sess.bucket_ready(65)  # next bucket up, never warmed
    assert sess.warm_state == {
        "total": rep["cells"], "ready": rep["cells"], "done": True,
    }


# ---------------------------------------------------------------------------
# cache off: exact legacy behavior


def test_cache_off_touches_nothing(rng):
    X = _corpus(rng)
    index = _serial_index(X)
    hits0 = _counter_value("aot_cache_hits_total")
    misses0 = _counter_value("aot_cache_misses_total")
    exec_ = get_executable(index, index.cfg, 64)
    assert exec_.source == "compiled"
    assert _counter_value("aot_cache_hits_total") == hits0
    assert _counter_value("aot_cache_misses_total") == misses0


def test_env_var_activation(monkeypatch, tmp_path):
    monkeypatch.setenv(aotcache.ENV_VAR, str(tmp_path / "envcache"))
    aotcache.reset_for_tests()
    cache = aotcache.active_cache()
    assert cache is not None and cache.stats()["dir"] == str(
        tmp_path / "envcache"
    )
    # explicit disable beats the env var
    aotcache.set_cache_dir(None)
    assert aotcache.active_cache() is None


# ---------------------------------------------------------------------------
# zero-copy mmap loader


def test_mmap_npz_matches_np_load(rng, tmp_path):
    from mpi_knn_tpu.utils.npz_mmap import mmap_npz

    path = str(tmp_path / "arrs.npz")
    np.savez(
        path,
        a=rng.standard_normal((7, 5)).astype(np.float32),
        b=np.arange(11, dtype=np.int32),
        empty=np.zeros(0, np.float32),
        meta=np.frombuffer(b"hello", dtype=np.uint8),
    )
    z = mmap_npz(path)
    with np.load(path) as ref:
        assert set(z) == set(ref.files)
        for k in ref.files:
            np.testing.assert_array_equal(np.asarray(z[k]), ref[k])
    # non-empty members really are maps, not copies
    assert isinstance(z["a"], np.memmap)
    assert bytes(z["meta"]) == b"hello"


def test_mmap_npz_refuses_compressed(rng, tmp_path):
    path = str(tmp_path / "comp.npz")
    np.savez_compressed(path, a=np.ones((4, 4), np.float32))
    from mpi_knn_tpu.utils.npz_mmap import mmap_npz

    with pytest.raises(ValueError, match="compressed"):
        mmap_npz(path)


def test_load_ivf_mmap_bit_identical_and_loud_fallback(rng, tmp_path):
    from mpi_knn_tpu.ivf import load_ivf_index, save_ivf_index, search_ivf

    X = _corpus(rng, clustered=True)
    idx = _ivf_index(X)
    path = save_ivf_index(idx, str(tmp_path / "ivf.npz"))
    a = load_ivf_index(path, mmap=True)
    b = load_ivf_index(path, mmap=False)
    Q = X[:32]
    da, ia = search_ivf(a, Q)
    db, ib = search_ivf(b, Q)
    np.testing.assert_array_equal(np.asarray(da), np.asarray(db))
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    # an archive the mapper cannot handle falls back LOUDLY, same bits
    comp = str(tmp_path / "ivf_comp.npz")
    with np.load(path) as z:
        np.savez_compressed(comp, **{k: z[k] for k in z.files})
    with pytest.warns(RuntimeWarning, match="cannot mmap"):
        c = load_ivf_index(comp, mmap=True)
    dc, ic = search_ivf(c, Q)
    np.testing.assert_array_equal(np.asarray(dc), np.asarray(da))
    np.testing.assert_array_equal(np.asarray(ic), np.asarray(ia))


# ---------------------------------------------------------------------------
# front end: per-bucket admission while warming


def test_frontend_warming_admission(rng):
    from mpi_knn_tpu.frontend.scheduler import Rejection, SLOPolicy
    from mpi_knn_tpu.frontend.server import Frontend

    X = _corpus(rng)
    index = _serial_index(X)
    sess = ServeSession(index)
    fe = Frontend(sess, SLOPolicy(max_batch_rows=128, max_wait_s=0.001))
    # pump not started, warming not done: nothing built → 503 warming
    out = fe.submit("t0", np.zeros((8, DIM), np.float32))
    assert isinstance(out, Rejection)
    assert out.reason == "warming" and out.status == 503
    assert "0/0" in out.detail or "executables" in out.detail
    st = fe.stats()
    assert st["ready"] is False and st["warming"]["done"] is False
    # admission gates on the whole COALESCABLE span, not the request's
    # own bucket: an admitted small request can be merged up to the
    # fill target's bucket, so that bucket must be built too
    sess.warm([128])  # fill-target bucket (128) lands
    out2 = fe.submit("t0", np.zeros((80, DIM), np.float32))
    assert not isinstance(out2, Rejection)  # span = {128}: ready
    out3 = fe.submit("t0", np.zeros((8, DIM), np.float32))
    assert isinstance(out3, Rejection) and out3.reason == "warming"
    assert not sess.coalesced_ready(8, 128)  # bucket 64 still cold
    sess.warm([8])  # base bucket (64) lands → full span built
    out4 = fe.submit("t0", np.zeros((8, DIM), np.float32))
    assert not isinstance(out4, Rejection)
    # warm-up complete: the gate is bypassed entirely
    fe._serving_ready.set()
    out5 = fe.submit("t0", np.zeros((100, DIM), np.float32))
    assert not isinstance(out5, Rejection)
    assert fe.stats()["ready"] is True


# ---------------------------------------------------------------------------
# doctor probe


def test_doctor_probe_roundtrip(tmp_path):
    cache = aotcache.AOTCache(tmp_path / "aot")
    out = aotcache.probe_roundtrip(cache)
    assert out["store_ok"] and out["load_ok"] and out["bit_identical"]
    assert not out["had_entry"]
    assert cache.stats()["entries"] == 1
    # second probe reuses the well-known key (no cache growth)
    out2 = aotcache.probe_roundtrip(cache)
    assert out2["had_entry"]
    assert cache.stats()["entries"] == 1
