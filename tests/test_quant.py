"""The quantization layer (ISSUE 9): block-scaled int8 ring transfer and
int8/int4 at-rest clustered stores (``ops/quant.py``).

Four layers:

- **primitive properties** — quant/dequant round trip within scale/2 per
  element, nibble pack/unpack exact, zero-block and all-negative-block
  edge cases, odd-dim padding;
- **transfer** — the int8 ring gate: recall@10 vs the f64 oracle on both
  rotation schedules with uni ≡ bidir bit-identically, the resumable
  kill/resume parity, serving parity + zero steady-state compiles, and
  the R4 wire-payload acceptance (ppermute bytes ≤ 0.27× the f32 cell at
  d=128, read from the lowered HLO);
- **at rest** — int8/int4 clustered stores: recall floors, save/load and
  shard/unshard bit-identity, sharded search parity, byte cuts against
  the same-layout f32 store, the SIFT-32k int4 acceptance gate;
- **config** — int8 transfer is refused under precision_policy="exact"
  (no rerank to absorb the quantization) and the validation message
  enumerates the accepted set.

On recall bars: these are MEASURED bars, not aspirations. int8 value
quantization (codes + per-row scales, dequantized rerank) floors around
0.99 recall@10 on every realistic dataset we measured — the exact rerank
is exact w.r.t. the DEQUANTIZED rows, so quantization noise reaches the
final ordering and no overfetch can buy it back. The gates assert the
measured level with margin; DESIGN.md's compression-ladder table carries
the full bytes-vs-recall story per level.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi_knn_tpu import KNNConfig, all_knn
from mpi_knn_tpu.ops.quant import (
    dequantize_rows,
    pack_int4,
    packed_dim,
    quantize_rows,
    row_wire_bytes,
    unpack_int4,
)
from tests.oracle import oracle_all_knn, recall_against_oracle

K = 10


def _mnist_like(rng, m=512, d=96):
    """Integer-pixel-magnitude data (the headline workload's regime) whose
    centered form is genuinely lossy under block-scaled int8."""
    return np.rint(rng.random((m, d)) * 255.0).astype(np.float32)


# ---------------------------------------------------------------------------
# primitive properties


@pytest.mark.parametrize("dtype", ["int8", "int4"])
@pytest.mark.parametrize("d", [32, 33])  # odd dim exercises the nibble pad
def test_roundtrip_error_within_half_scale(rng, dtype, d):
    x = (rng.standard_normal((64, d)) * rng.uniform(0.1, 200)).astype(
        np.float32
    )
    codes, scales = quantize_rows(jnp.asarray(x), dtype)
    assert codes.dtype == jnp.int8
    assert codes.shape == (64, packed_dim(d, dtype))
    back = np.asarray(dequantize_rows(codes, scales, dtype, d))
    err = np.abs(back - x)
    # round-to-nearest: every element within half a scale step (tiny fp
    # slack — the bound itself is computed in f32)
    assert (err <= np.asarray(scales)[:, None] / 2 + 1e-5).all()


@pytest.mark.parametrize("dtype", ["int8", "int4"])
def test_zero_block_and_all_negative_block(rng, dtype):
    x = np.zeros((3, 16), np.float32)
    x[1] = -np.abs(rng.standard_normal(16)).astype(np.float32) * 50 - 1.0
    x[2, 3] = 7.5  # one-hot-ish block: scale set by a single element
    codes, scales = quantize_rows(jnp.asarray(x), dtype)
    back = np.asarray(dequantize_rows(codes, scales, dtype, 16))
    # zero block: scale 0, codes 0, dequantization EXACTLY zero
    assert float(np.asarray(scales)[0]) == 0.0
    assert (back[0] == 0.0).all()
    # all-negative block: symmetric quantization is sign-faithful and the
    # extreme element reconstructs exactly (code = -qmax)
    assert (back[1] <= 0).all()
    amax_col = np.argmin(x[1])
    assert back[1, amax_col] == pytest.approx(x[1, amax_col], rel=1e-6)
    assert (np.abs(back[1] - x[1]) <= np.asarray(scales)[1] / 2 + 1e-5).all()


def test_nibble_pack_unpack_exact(rng):
    codes = rng.integers(-7, 8, size=(8, 31)).astype(np.int8)
    packed = pack_int4(jnp.asarray(codes))
    assert packed.shape == (8, 16)
    assert np.array_equal(np.asarray(unpack_int4(packed, 31)), codes)


def test_row_wire_bytes_ladder():
    # the single pricing rule: f32 4d, bf16 2d, int8 d+4, int4 d/2+4
    assert row_wire_bytes(128, None, 4) == 512
    assert row_wire_bytes(128, None, 2) == 256
    assert row_wire_bytes(128, "int8") == 132
    assert row_wire_bytes(128, "int4") == 68


# ---------------------------------------------------------------------------
# config validation (ISSUE 9 satellite: the message enumerates the
# accepted set; exact+int8 is refused loudly)


def test_transfer_dtype_message_enumerates_accepted_set():
    with pytest.raises(ValueError) as e:
        KNNConfig(ring_transfer_dtype="int16")
    for accepted in ("bfloat16", "float32", "int8"):
        assert accepted in str(e.value)


def test_int8_transfer_refused_under_exact_policy():
    with pytest.raises(ValueError, match="mixed"):
        KNNConfig(ring_transfer_dtype="int8", precision_policy="exact")


def test_quant_dtype_without_partitions_refused():
    with pytest.raises(ValueError, match="partitions"):
        KNNConfig(dtype="int8")


# ---------------------------------------------------------------------------
# transfer: the int8 ring


def test_ring_int8_recall_gate_uni_bidir_bit_identical(rng):
    """The transfer gate: block-scaled int8 rotation under the mixed
    pipeline holds recall@10 ≥ 0.99 vs the f64 oracle (measured ~0.993 on
    this data — the dequantized-rerank noise floor; bf16 sits at ~0.999,
    DESIGN.md carries the ladder), and the bidir schedule is
    BIT-IDENTICAL to uni: both dequantize the same codes, so the merge
    order cannot change a single bit."""
    X = _mnist_like(rng)
    want_d, want_i = oracle_all_knn(X, k=K)
    outs = {}
    for sched in ("uni", "bidir"):
        got = all_knn(
            X,
            k=K,
            backend="ring",
            precision_policy="mixed",
            ring_transfer_dtype="int8",
            ring_schedule=sched,
            query_tile=64,
            corpus_tile=128,
        )
        rec = recall_against_oracle(got.ids, want_d, want_i, K)
        assert rec >= 0.99, f"{sched}: recall@10 {rec} < 0.99"
        outs[sched] = got
    assert np.array_equal(outs["uni"].ids, outs["bidir"].ids)
    assert np.array_equal(outs["uni"].dists, outs["bidir"].dists)


@pytest.mark.parametrize("sched", ["uni", "bidir"])
def test_ring_int8_resumable_kill_resume_bit_identical(rng, sched, tmp_path):
    """The quantized travelers reconstruct across a kill: codes are a
    deterministic function of the f32 corpus, per-row quantization
    commutes with the resume roll, and the scale vectors thread through
    the one-round jits — so a killed-and-resumed run is bit-identical to
    an uninterrupted one on both schedules."""
    from mpi_knn_tpu.backends.ring_resumable import all_knn_ring_resumable

    X = _mnist_like(rng, m=300, d=48)
    qids = np.arange(300, dtype=np.int32)
    cfg = KNNConfig(
        k=8,
        backend="ring",
        precision_policy="mixed",
        ring_transfer_dtype="int8",
        ring_schedule=sched,
        query_tile=32,
        corpus_tile=64,
    )
    d_full, i_full = all_knn_ring_resumable(X, X, qids, cfg)
    ck = str(tmp_path / sched)
    all_knn_ring_resumable(
        X, X, qids, cfg, checkpoint_dir=ck, stop_after_rounds=2
    )
    d_res, i_res = all_knn_ring_resumable(X, X, qids, cfg, checkpoint_dir=ck)
    assert np.array_equal(np.asarray(i_full), np.asarray(i_res))
    assert np.array_equal(np.asarray(d_full), np.asarray(d_res))


def test_ring_int8_serve_parity_zero_compiles_and_gauge(rng):
    """Quantized serve cells ride the bucketed AOT cache: the resident
    index holds the WIRE representation (codes + scales, ~4× less HBM),
    serving is bit-identical to the one-shot driver, the steady state
    compiles nothing (jax.monitoring-counted), and the
    ``ring_transfer_wire_bytes`` gauge (stamped at lower time) shows the
    int8 rotation moving < 1/3 the bytes of the f32 rotation."""
    from mpi_knn_tpu.obs.metrics import get_registry, watch_compiles
    from mpi_knn_tpu.serve import ServeSession, build_index
    from mpi_knn_tpu.serve.engine import query_knn

    X = _mnist_like(rng)
    cfg = KNNConfig(
        k=K,
        backend="ring-overlap",
        precision_policy="mixed",
        ring_transfer_dtype="int8",
        query_tile=64,
        corpus_tile=128,
        query_bucket=64,
    )
    idx = build_index(X, cfg)
    assert idx.corpus_sharded.dtype == jnp.int8
    assert idx.corpus_scales_sharded is not None

    res = query_knn(X[:64], idx)
    got = all_knn(X, queries=X[:64], k=K, config=cfg)
    assert np.array_equal(res.ids, got.ids)
    np.testing.assert_allclose(res.dists, got.dists)

    session = ServeSession(idx)
    session.warm([64])
    session.submit(X[:64])
    session.drain()
    with watch_compiles() as compiles:
        for _ in range(3):
            session.submit(X[:64])
            session.drain()
    assert compiles == []

    gauges = get_registry().snapshot()["metrics"]
    int8_bytes = gauges["ring_transfer_wire_bytes"]["value"]
    idx_f32 = build_index(X, cfg.replace(ring_transfer_dtype=None))
    s2 = ServeSession(idx_f32)
    s2.warm([64])
    f32_bytes = get_registry().snapshot()["metrics"][
        "ring_transfer_wire_bytes"
    ]["value"]
    assert int8_bytes < f32_bytes / 3


def test_r4_permute_payload_at_most_27pct_of_f32(rng):
    """The ISSUE 9 wire acceptance, read from the LOWERED HLO at d=128:
    the int8 cell's total collective-permute payload bytes per rotation
    step are ≤ 0.27× the f32 cell's ((d + 4 + 4) / (4d + 4) = 0.264 at
    d=128 — codes + scale row + id row against f32 rows + id row)."""
    from mpi_knn_tpu.analysis.rules import count_collectives, max_buffer_bytes
    from mpi_knn_tpu.backends.ring import (
        _ring_knn_sharded,
        parse_ring_mesh,
        ring_tiles,
    )
    from mpi_knn_tpu.parallel.mesh import make_ring_mesh
    from mpi_knn_tpu.utils.hlo_graph import parse_hlo

    mesh = make_ring_mesh(None)
    q_axis, axis, dp, ring_n = parse_ring_mesh(mesh)
    d = 128
    m, nq = 256, 64

    def permute_bytes(cfg, corpus, scale):
        q_tile, c_tile, q_pad, c_pad = ring_tiles(cfg, m, nq, dp, ring_n)
        lowered = _ring_knn_sharded.lower(
            jnp.zeros((q_pad, d), jnp.float32),
            jnp.zeros((q_pad,), jnp.int32),
            corpus,
            jnp.zeros((c_pad,), jnp.int32),
            cfg,
            True,
            mesh,
            axis,
            q_tile,
            c_tile,
            q_axis=q_axis,
            corpus_scale=scale,
        )
        module = parse_hlo(lowered.compiler_ir("hlo").as_hlo_text())
        permutes = count_collectives(module).get("collective-permute", [])
        assert permutes, "no rotation permutes in the lowered ring"
        return sum(
            max_buffer_bytes(module.instr(c, n).type_str)
            for c, n in permutes
        )

    base = KNNConfig(k=K, backend="ring-overlap", precision_policy="mixed",
                     query_tile=32, corpus_tile=32)
    f32_cfg = base
    int8_cfg = base.replace(ring_transfer_dtype="int8")
    _, _, _, c_pad = ring_tiles(base, m, nq, dp, ring_n)
    f32_bytes = permute_bytes(
        f32_cfg, jnp.zeros((c_pad, d), jnp.float32), None
    )
    int8_bytes = permute_bytes(
        int8_cfg,
        jnp.zeros((c_pad, d), jnp.int8),
        jnp.zeros((c_pad,), jnp.float32),
    )
    assert int8_bytes <= 0.27 * f32_bytes, (int8_bytes, f32_bytes)


# ---------------------------------------------------------------------------
# at rest: int8/int4 clustered stores


def _brute_recall(X, ids, k):
    X64 = X.astype(np.float64)
    mu = X64.mean(0)
    Xc = X64 - mu
    D = (
        (Xc**2).sum(1)[:, None]
        + (Xc**2).sum(1)[None, :]
        - 2.0 * Xc @ Xc.T
    )[: ids.shape[0]]
    np.fill_diagonal(D[:, : ids.shape[0]], np.inf)
    want = np.argsort(D, 1, kind="stable")[:, :k]
    return np.mean(
        [len(set(a) & set(b)) / k for a, b in zip(ids, want)]
    )


@pytest.mark.parametrize("dtype,floor", [("int8", 0.95), ("int4", 0.70)])
def test_ivf_quantized_store_recall_floor(rng, dtype, floor):
    """Full-scan (nprobe == partitions) recall of the quantized store vs
    the f64 oracle — pure at-rest quantization loss, no partition
    pruning. Measured ~0.98 (int8) / ~0.86 (int4) on this data; bars
    carry margin."""
    from mpi_knn_tpu.ivf import build_ivf_index, search_ivf

    X = (rng.standard_normal((2048, 32)) * 3).astype(np.float32)
    idx = build_ivf_index(
        X, KNNConfig(k=K, partitions=16, nprobe=16, dtype=dtype)
    )
    _, ids = search_ivf(
        idx, X[:128], query_ids=np.arange(128, dtype=np.int32)
    )
    rec = _brute_recall(X, ids, K)
    assert rec >= floor, f"{dtype}: full-scan recall {rec} < {floor}"


@pytest.mark.parametrize("dtype", ["int8", "int4"])
def test_ivf_quantized_save_load_shard_roundtrips_bit_identical(
    rng, dtype, tmp_path
):
    from mpi_knn_tpu.ivf import (
        build_ivf_index,
        load_ivf_index,
        save_ivf_index,
        search_ivf,
        search_ivf_sharded,
        shard_ivf_index,
        unshard_ivf_index,
    )

    X = (rng.standard_normal((1024, 24)) * 3).astype(np.float32)
    idx = build_ivf_index(
        X, KNNConfig(k=5, partitions=8, nprobe=3, dtype=dtype)
    )
    d0, i0 = search_ivf(idx, X[:64])

    # save/load: codes, scales and results bit-identical
    path = save_ivf_index(idx, str(tmp_path / f"{dtype}.npz"))
    idx2 = load_ivf_index(path)
    assert np.array_equal(np.asarray(idx.buckets), np.asarray(idx2.buckets))
    assert np.array_equal(
        np.asarray(idx.bucket_scales), np.asarray(idx2.bucket_scales)
    )
    d1, i1 = search_ivf(idx2, X[:64])
    assert np.array_equal(i0, i1) and np.array_equal(d0, d1)

    # shard/unshard: layout derived, store bit-identical, search parity
    sidx = shard_ivf_index(idx2, shards=4)
    d2, i2, stats = search_ivf_sharded(sidx, X[:64])
    assert np.array_equal(i0, i2) and np.allclose(d0, d2)
    assert stats[:, 1].sum() == 0  # safe route cap: nothing dropped
    back = unshard_ivf_index(sidx)
    assert np.array_equal(np.asarray(back.buckets), np.asarray(idx.buckets))
    assert np.array_equal(
        np.asarray(back.bucket_scales), np.asarray(idx.bucket_scales)
    )


def test_ivf_quantized_serve_zero_compiles_and_at_rest_gauge(rng):
    from mpi_knn_tpu.ivf import build_ivf_index
    from mpi_knn_tpu.obs.metrics import get_registry, watch_compiles
    from mpi_knn_tpu.serve import ServeSession

    X = (rng.standard_normal((1024, 24)) * 3).astype(np.float32)
    idx = build_ivf_index(
        X,
        KNNConfig(k=5, partitions=8, nprobe=3, dtype="int8",
                  query_bucket=64),
    )
    session = ServeSession(idx)
    session.warm([64])
    session.submit(X[:64])
    session.drain()
    with watch_compiles() as compiles:
        for _ in range(3):
            session.submit(X[:64])
            session.drain()
    assert compiles == []
    gauge = get_registry().snapshot()["metrics"]["ivf_at_rest_bytes"]
    assert gauge["value"] == idx.nbytes_resident


def test_ivf_at_rest_byte_cuts_vs_same_layout_f32(rng):
    """The HBM claim, same bucket layout (padding cancels): int4 cuts
    ≥ 4× (measured ~7.5× at d=128: d/2 + 4 scale bytes vs 4d), int8
    ≥ 3× (~3.9×), bf16 exactly 2× on the row array."""
    from mpi_knn_tpu.ivf import build_ivf_index

    X = (rng.standard_normal((2048, 128)) * 3).astype(np.float32)
    sizes = {}
    for dtype in ("float32", "int8", "int4"):
        idx = build_ivf_index(
            X, KNNConfig(k=5, partitions=8, nprobe=2, dtype=dtype)
        )
        sizes[dtype] = idx.nbytes_resident
    assert sizes["float32"] >= 4 * sizes["int4"]
    assert sizes["float32"] >= 3 * sizes["int8"]


def test_sift32k_int4_acceptance_gate():
    """The ISSUE 9 int4 acceptance on the SIFT-shaped 32k gate, with the
    honestly MEASURED recall bar: the auto-tuned store reaches recall@10
    ≥ 0.80 vs the f64 oracle (measured ≈ 0.835 — int4 value quantization
    cannot reach the f32 index's 0.95-targeted level on this data; the
    ladder table in DESIGN.md documents the trade), the at-rest cut vs
    the same-layout f32 store is ≥ 4× (measured 7.5×), and R2-strict
    re-certifies the wire-priced probe-gather bound on the REAL lowered
    serve program (an f32-sized bucket gather — dequantizing before the
    gather — would fail the gate)."""
    from mpi_knn_tpu.analysis import engine
    from mpi_knn_tpu.analysis.lowering import (
        LintTarget,
        _ivf_meta,
        hlo_texts,
        serve_resident_bytes,
    )
    from mpi_knn_tpu.data.synthetic import make_sift_like
    from mpi_knn_tpu.ivf import build_ivf_index, search_ivf
    from mpi_knn_tpu.serve.engine import lower_bucket

    X = make_sift_like(m=32768, d=128, seed=0)
    cfg = KNNConfig(k=K, partitions=64, kmeans_iters=10, query_bucket=256,
                    dtype="int4")
    idx = build_ivf_index(X, cfg)

    # measured recall@10 vs the f64 oracle on a held-out sample
    sample = np.linspace(0, 32767, num=128, dtype=np.int64)
    _, got = search_ivf(idx, X[sample], query_ids=sample.astype(np.int32))
    X64 = X.astype(np.float64)
    od = (
        (X64[sample] ** 2).sum(1)[:, None]
        + (X64**2).sum(1)[None, :]
        - 2.0 * (X64[sample] @ X64.T)
    )
    od[od <= 1e-9] = np.inf
    od[np.arange(len(sample)), sample] = np.inf
    order = np.argsort(od, axis=1, kind="stable")[:, : K + 5]
    want_d = np.take_along_axis(od, order, axis=1)
    rec = recall_against_oracle(got, want_d, order.astype(np.int32), K)
    assert rec >= 0.80, f"int4 32k gate: recall {rec} < 0.80"

    # ≥ 4× at-rest byte cut vs the same bucket layout at f32
    f32_layout_bytes = (
        idx.partitions * idx.bucket_cap * idx.dim * 4
    )
    assert f32_layout_bytes >= 4 * idx.nbytes_resident

    # R2-strict on the real serve-cache lowering, wire-priced gathers
    serve_cfg = idx.compatible_cfg(idx.cfg)
    lowered, q_pad, q_tile = lower_bucket(idx, serve_cfg, 256)
    target = LintTarget("ivf", "l2", "float32", serve=True, quant="int4")
    meta = {
        **_ivf_meta(idx, serve_cfg, q_tile, q_pad, 256),
        "serve": True,
        "donated_params": idx.layout.donate_argnums,
        # the f32-EQUIVALENT copy threshold: a quantized store's own
        # wire-width probe gather legitimately exceeds the compressed
        # residency (see lowering.serve_resident_bytes)
        "resident_bytes": serve_resident_bytes(idx),
    }
    assert meta["quantized"] is True
    ctx = engine.LintContext(target=target, cfg=serve_cfg, meta=meta)
    findings, ran = engine.run_rules(hlo_texts(lowered), ctx)
    assert {"R2-memory", "R3-dtype", "R6-ivf-probe", "R5-donation"} <= set(
        ran
    )
    assert not findings, "\n".join(
        f"[{f.rule}] {f.stage}: {f.message}" for f in findings
    )


def test_quantized_cfg_is_frozen_corpus_side(rng):
    """The at-rest dtype is baked into the store: a query config changing
    it is refused (serving int8 answers under an f32 label would lie
    about the math)."""
    from mpi_knn_tpu.ivf import build_ivf_index

    X = (rng.standard_normal((256, 16)) * 3).astype(np.float32)
    idx = build_ivf_index(
        X, KNNConfig(k=5, partitions=4, nprobe=2, dtype="int8")
    )
    with pytest.raises(ValueError, match="dtype"):
        idx.compatible_cfg(idx.cfg.replace(dtype="float32"))
