"""Persistent on-disk executable cache — restart-survivable AOT compiles.

Steady-state serving already compiles nothing (the in-memory bucketed
executable cache, PR 4), but the in-memory cache dies with the process:
every server restart, bench child, and doctor probe re-pays the whole
compile matrix before serving its first query. This module makes the
compiled artifact itself durable, so a restarted process compiles nothing
it has ever compiled before.

Mechanism — why serialized executables, not jax's persistent compilation
cache: jax's built-in cache (``jax_compilation_cache_dir``) still walks
the full trace → lower → ``compile_or_get_cached`` path and fires the
``backend_compile_duration`` monitoring event even on a hit, so "zero
XLA backend-compiles on the second start" would be unprovable from the
metrics registry, and tracing/lowering wall time would still be paid per
cell. Here a hit skips ALL of it: the entry stores the pickled PJRT
executable (``jax.experimental.serialize_executable``) plus its arg
pytrees, and loading is one ``deserialize_and_load`` — no trace, no
lower, no XLA invocation, no compile event. The lint CLI, which needs
HLO text rather than a runnable executable, uses jax's own cache instead
(``mpi-knn lint --cache-dir``); the two mechanisms share nothing but the
directory convention.

Keying — the full fingerprint, never the program text: an entry is
addressed by a sha256 over (a) the frozen :class:`KNNConfig` with
host-only pacing knobs canonicalized out (the in-memory cache's own
fingerprint rule), (b) the row bucket, (c) the index facts — backend,
corpus size/dim, every resident array's shape+dtype, tiling/partition/
shard layout, mesh topology, centering — and (d) the platform facts:
backend name, device count and kinds, jax/jaxlib versions, and this
module's format version. Anything that could change the lowered program
or the devices it binds to is in the key, so a mismatched entry is
simply never FOUND. Defense in depth on top: a loaded executable's
``args_info`` avals are checked against the argspec the engine would
have lowered (``serve.engine`` passes ``expect_args``), and a stale or
corrupt entry — bad magic, truncated pickle, checksum mismatch, wrong
jax version, aval mismatch, a deserialization error from a moved device
topology — falls back to a REAL compile loudly: counted in
``aot_cache_errors_total``, warned on stderr, overwritten by the fresh
compile. Never a mismatched program, never a silent miss.

Concurrency: writers serialize to a temp file in the cache directory and
``os.replace`` it into place — readers see either the old entry or the
new one, never a torn write, and concurrent warms (the parallel warm
pool, several bench children sharing one dir) need no locking.

Activation is process-level, not per-config (a cache directory is an
operational fact about the host, and nothing here may perturb executable
fingerprints): ``set_cache_dir(path)`` explicitly, the
``TKNN_AOT_CACHE`` env var ambiently, or ``--cache-dir`` on the serve /
query / doctor CLIs. No jax import at module load.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pathlib
import pickle
import threading
import warnings

from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.utils.atomicio import atomic_write_bytes

# bump when the entry layout (or anything about how executables are
# rebuilt from entries) changes: old entries must MISS, not half-load
FORMAT_VERSION = 2

ENTRY_SUFFIX = ".aotx"

ENV_VAR = "TKNN_AOT_CACHE"


# ---------------------------------------------------------------------------
# Fingerprinting


def index_facts(index) -> dict:
    """Everything about a resident index that reaches its per-batch
    program: backend, corpus/layout scalars, the shape+dtype of every
    resident array, and the mesh topology for the distributed backends.
    Two indices with equal facts lower bit-identical programs for a given
    (bucket, config); any difference — a re-tiled corpus, a different
    shard count, a quantized store — changes the key."""
    facts: dict = {
        "backend": index.backend,
        "m": int(index.m),
        "dim": int(index.dim),
        "has_mu": index.mu is not None,
    }
    for name in (
        "tiles", "tile_ids", "tile_sqs",
        "corpus_sharded", "corpus_ids_sharded", "corpus_scales_sharded",
        "centroids", "centroid_sqs", "buckets", "bucket_ids",
        "bucket_sqs", "bucket_scales",
    ):
        arr = getattr(index, name, None)
        if arr is not None:
            facts[name] = [
                [int(s) for s in arr.shape], str(arr.dtype)
            ]
    for name in ("c_tile", "partitions", "bucket_cap", "nprobe",
                 "shards", "per_shard"):
        v = getattr(index, name, None)
        if v is not None:
            facts[name] = int(v)
    tags = getattr(index, "tags", None)
    if tags is not None:
        # a tagged index's programs take a predicate a query row and the
        # bitsets (absent otherwise: no other entry's address moves)
        facts["tags"] = {
            "bitsets": [int(s) for s in tags.tag_bits.shape],
            "pack": int(tags.pack),
            "copy": tags.src is not None,
        }
    if getattr(index, "onepass", None) is not None:
        # the batch program takes the fact as one more argument and holds
        # both branches of the one-pass rule (absent otherwise, so every
        # other entry's address is unchanged)
        facts["onepass"] = True
    tiles = getattr(index, "tiles", None)
    if tiles is not None and int(tiles.shape[-1]) != int(index.dim):
        # a stack that rests wider than its rows (serve/index.py
        # rest_width): said in so many words beside the stack's own entry
        # above, so an executable cached for the unpadded stack is never
        # loaded (absent otherwise: no other entry's address moves)
        facts["rest_width"] = int(tiles.shape[-1])
    if getattr(index, "rest_offset", None) is not None:
        # (a byte stack's: one more operand of the batch program, after
        # the fact; the stack's own entry above carries the at-rest type)
        facts["rest_offset"] = True
    if getattr(index, "mean_frac", None) is not None:
        # (a clustered store's: one more operand of the batch program)
        facts["mean_frac"] = True
    mesh = getattr(index, "mesh", None)
    if mesh is not None:
        facts["mesh"] = {
            "axes": [str(a) for a in mesh.axis_names],
            "shape": [int(s) for s in mesh.devices.shape],
        }
    ring_meta = getattr(index, "ring_meta", None)
    if ring_meta is not None:
        facts["ring_meta"] = [
            ring_meta[0], ring_meta[1], int(ring_meta[2]),
            int(ring_meta[3]),
        ]
    return facts


def platform_facts() -> dict:
    """The process-side half of the fingerprint: an executable is a
    device binary bound to a client topology, so the platform, the device
    census, and the exact jax/jaxlib pair are key material — an entry
    compiled under any other combination must miss."""
    import jax
    import jaxlib

    devices = jax.devices()
    return {
        "platform": jax.default_backend(),
        "device_count": len(devices),
        "device_kinds": sorted({d.device_kind for d in devices}),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "format": FORMAT_VERSION,
    }


def fingerprint_facts(index, cfg, bucket: int, kind: str = "serve") -> dict:
    """The full human-readable fingerprint document (the sha256 preimage,
    also stored in each entry's meta so ``mpi-knn doctor`` and a human
    with ``pickle.load`` can see WHY an entry is what it is). ``kind``
    distinguishes the mutation programs (upsert/delete/assign/compact —
    ``serve.mutate``) from the serve batch program; the default "serve"
    is OMITTED from the document so every pre-mutation entry's address
    is unchanged."""
    from mpi_knn_tpu.serve.engine import _fingerprint_cfg

    cfg_doc = dataclasses.asdict(_fingerprint_cfg(cfg))
    if getattr(index, "tags", None) is None:
        # read by a tagged index's programs alone: left out elsewhere, so
        # every entry of an index without tags keeps its address
        del cfg_doc["max_query_tags"]
    for knob in ("kmeans_sample", "bucket_cap"):
        # a clustered build's knobs, set by few: left out where unset, so
        # every entry made before the fields existed keeps its address
        if cfg_doc[knob] is None:
            del cfg_doc[knob]
    doc = {
        "cfg": cfg_doc,
        "bucket": int(bucket),
        "index": index_facts(index),
        "platform": platform_facts(),
    }
    if kind != "serve":
        doc["kind"] = kind
    return doc


def fingerprint(index, cfg, bucket: int, kind: str = "serve") -> str:
    """Content address of one (index, config, bucket[, kind]) cell."""
    doc = json.dumps(
        fingerprint_facts(index, cfg, bucket, kind=kind), sort_keys=True
    )
    return hashlib.sha256(doc.encode()).hexdigest()


# ---------------------------------------------------------------------------
# The cache


def _counter(name: str, help: str):  # noqa: A002 — registry convention
    return obs_metrics.get_registry().counter(name, help=help)


def _count_hit():
    _counter("aot_cache_hits_total",
             "executables loaded from the persistent AOT cache").inc()


def _count_miss():
    _counter("aot_cache_misses_total",
             "persistent AOT cache lookups that found no entry").inc()


def _count_error():
    _counter(
        "aot_cache_errors_total",
        "stale/corrupt/unloadable AOT cache entries that fell back to a "
        "real compile (loud, never a wrong program)",
    ).inc()


def _count_store():
    _counter("aot_cache_stores_total",
             "executables serialized into the persistent AOT cache").inc()


class AOTCache:
    """One cache directory of content-addressed serialized executables.

    Every entry is a single file ``<key>.aotx``: a pickle of
    ``{"format", "jax", "key", "sha256", "payload", "in_tree",
    "out_tree", "device_ids", "meta"}`` where ``payload`` is the
    serialized PJRT executable, the trees are the pickled arg/result
    pytree defs, ``device_ids`` names the devices the executable was
    compiled for (it is revived onto exactly those — a one-device
    program loads in a multi-device process), and ``sha256`` is the
    payload digest (truncation/bit-rot detection on top of pickle's own
    framing). All read-side failures degrade to a
    miss — counted and warned, never raised into serving."""

    def __init__(self, path: str | os.PathLike):
        self.dir = pathlib.Path(path)
        self.dir.mkdir(parents=True, exist_ok=True)

    def entry_path(self, key: str) -> pathlib.Path:
        return self.dir / f"{key}{ENTRY_SUFFIX}"

    # -- read side --------------------------------------------------------

    def load(self, key: str, expect_args=None):
        """The compiled executable for ``key``, or None (a miss — absent,
        stale, corrupt, or mismatched entries all land here; only absence
        is silent). ``expect_args`` is an optional sequence of
        ``(shape_tuple, dtype_str)`` the loaded executable's flattened
        ``args_info`` must match — the engine passes the argspec it would
        have lowered, so a fingerprint collision (or a bug in the key)
        can still never serve a mismatched program."""
        path = self.entry_path(key)
        try:
            blob = path.read_bytes()
        except FileNotFoundError:
            _count_miss()
            return None
        except OSError as e:
            _warn_bad(key, f"unreadable entry file: {e}")
            return None
        try:
            doc = pickle.loads(blob)
            if doc.get("format") != FORMAT_VERSION:
                raise ValueError(
                    f"format {doc.get('format')!r} != {FORMAT_VERSION}"
                )
            if doc.get("key") != key:
                raise ValueError("entry key does not match its filename")
            payload = doc["payload"]
            if hashlib.sha256(payload).hexdigest() != doc["sha256"]:
                raise ValueError("payload checksum mismatch (truncated or "
                                 "corrupt entry)")
            import jax
            from jax.experimental import serialize_executable

            if doc.get("jax") != jax.__version__:
                raise ValueError(
                    f"entry compiled under jax {doc.get('jax')} but this "
                    f"process runs {jax.__version__}"
                )
            in_tree = pickle.loads(doc["in_tree"])
            out_tree = pickle.loads(doc["out_tree"])
            by_id = {d.id: d for d in jax.devices()}
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in doc["device_ids"]],
            )
            if expect_args is not None:
                _check_args(compiled, expect_args)
        except Exception as e:  # noqa: BLE001 — every failure is a miss
            _warn_bad(key, f"{type(e).__name__}: {e}")
            return None
        _count_hit()
        return compiled

    # -- write side -------------------------------------------------------

    def store(self, key: str, compiled, meta: dict | None = None) -> bool:
        """Serialize ``compiled`` under ``key`` via the shared atomic
        temp + ``os.replace`` helper (``utils.atomicio``; concurrent
        writers race benignly: the last full entry wins, readers never
        see a torn file). Returns
        False — counted and warned, never raised — when the executable
        does not support serialization or the write fails: a broken
        cache must not take serving down with it."""
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled
            )
            import jax

            doc = {
                "format": FORMAT_VERSION,
                "jax": jax.__version__,
                "key": key,
                "sha256": hashlib.sha256(payload).hexdigest(),
                "payload": payload,
                "in_tree": pickle.dumps(in_tree),
                "out_tree": pickle.dumps(out_tree),
                "device_ids": [
                    d.id
                    for d in compiled.runtime_executable().local_devices()
                ],
                "meta": meta or {},
            }
            atomic_write_bytes(self.entry_path(key), pickle.dumps(doc))
        except Exception as e:  # noqa: BLE001 — storing is best-effort
            _count_error()
            warnings.warn(
                f"aot cache: cannot store entry {key[:12]}…: "
                f"{type(e).__name__}: {e}",
                RuntimeWarning,
                stacklevel=2,
            )
            return False
        _count_store()
        return True

    # -- introspection ----------------------------------------------------

    def stats(self) -> dict:
        """{dir, entries, bytes} — the doctor verdict's cache block."""
        entries = 0
        nbytes = 0
        try:
            for p in self.dir.glob(f"*{ENTRY_SUFFIX}"):
                entries += 1
                nbytes += p.stat().st_size
        except OSError:
            pass
        return {"dir": str(self.dir), "entries": entries, "bytes": nbytes}


def _check_args(compiled, expect_args) -> None:
    """Compare the loaded executable's flattened input avals against the
    argspec the engine would have lowered; any difference means the entry
    is NOT this cell's program (fingerprint collision or key bug) and
    must be recompiled."""
    import jax

    got = [
        (tuple(a.shape), str(a.dtype))
        for a in jax.tree_util.tree_leaves(compiled.args_info)
    ]
    want = [(tuple(s), str(d)) for s, d in expect_args]
    if got != want:
        raise ValueError(
            f"loaded executable signature {got} does not match the "
            f"expected argspec {want}"
        )


def _warn_bad(key: str, why: str) -> None:
    _count_error()
    warnings.warn(
        f"aot cache: entry {key[:12]}… is unusable ({why}); falling back "
        "to a real compile and overwriting it",
        RuntimeWarning,
        stacklevel=3,
    )


# ---------------------------------------------------------------------------
# Process-level activation

_lock = threading.Lock()
_active: AOTCache | None = None
_configured = False  # set_cache_dir was called (None = explicit disable)


def set_cache_dir(path: str | os.PathLike | None) -> AOTCache | None:
    """Activate (or, with None, explicitly disable) the process-wide
    cache. Explicit configuration beats the ``TKNN_AOT_CACHE`` env var."""
    global _active, _configured
    with _lock:
        _active = AOTCache(path) if path is not None else None
        _configured = True
        return _active


def active_cache() -> AOTCache | None:
    """The process's cache, if any: the explicitly configured one, else
    one ambient from ``TKNN_AOT_CACHE``, else None (cache off — every
    lookup site must behave exactly as before this module existed).

    An unusable ambient directory (read-only mount, permission wall)
    disables the cache loudly instead of raising: this is called from
    the executable-build path inside live serving, and a broken cache
    must never take serving down with it. Explicit
    :func:`set_cache_dir` still raises — a CLI flag pointing nowhere is
    a startup usage error, not a degradation."""
    global _active, _configured
    with _lock:
        if _configured:
            return _active
        env = os.environ.get(ENV_VAR)
        if env:
            try:
                _active = AOTCache(env)
            except OSError as e:
                _count_error()
                warnings.warn(
                    f"aot cache: {ENV_VAR}={env!r} is unusable "
                    f"({type(e).__name__}: {e}); caching disabled for "
                    "this process",
                    RuntimeWarning,
                    stacklevel=2,
                )
                _active = None
            _configured = True
            return _active
        return None


def reset_for_tests() -> None:
    """Forget process-level activation (tests mutate env/config)."""
    global _active, _configured
    with _lock:
        _active = None
        _configured = False


# ---------------------------------------------------------------------------
# Doctor probe


def probe_roundtrip(cache: AOTCache) -> dict:
    """Store-then-load round trip on a tiny probe program — the doctor's
    hard evidence that THIS directory on THIS platform can persist and
    revive an executable (permissions, disk, serialization support), with
    the revived program's output compared bit-for-bit. The probe key is
    derived from the platform facts alone, so repeated doctor runs
    overwrite one well-known entry instead of growing the cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = hashlib.sha256(
        json.dumps({"probe": FORMAT_VERSION,
                    "platform": platform_facts()},
                   sort_keys=True).encode()
    ).hexdigest()
    had_entry = cache.entry_path(key).exists()
    lowered = jax.jit(lambda a: (a @ a.T).sum(axis=0)).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)
    )
    compiled = lowered.compile()
    stored = cache.store(key, compiled, meta={"probe": True})
    loaded = cache.load(key) if stored else None
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    bit_identical = False
    if loaded is not None:
        bit_identical = bool(
            (np.asarray(jax.device_get(compiled(x)))
             == np.asarray(jax.device_get(loaded(x)))).all()
        )
    return {
        "probe_key": key[:16],
        "had_entry": had_entry,
        "store_ok": stored,
        "load_ok": loaded is not None,
        "bit_identical": bit_identical,
    }
