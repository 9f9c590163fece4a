"""The quickest proof that the system still starts on the chip.

Drives the two main paths once through the entry points a user calls, at
the full width of the headline model (60 000 x 784 float32, k = 10, L2,
all-pairs with self-exclusion, ``bench.py``'s configuration), and checks
what comes out by the repo's own means. No number printed here is a
benchmark result: the times say that a phase ran, not how fast the system
is.

Phases, each printing one line with its wall time:

- ``device``   jax comes up on a TPU whose ``device_kind`` has a shipped
               profile (an unknown device is an error, never a default).
- ``allknn``   ``api.all_knn`` on the serial backend, first call (compile)
               and one warm call timed apart; recall@10 against the
               float64 oracle on a 256-row sample.
- ``cosine``   the prepared cosine path (ISSUE 32) on fractional rows of
               lengths 0.5-2x their own: a resident index's answers equal
               the one-shot call's bit for bit, and the direct form's in
               float64 on the host.
- ``fused_screen`` the screen inside the kernel (ISSUE 51): the same rows
               under L2 — the kernel's lists against float64 under its own
               eps, the answer against the six-pass program's, the steps'
               counter, a planted crowd flagged and re-scanned.
- ``screen``   the certified screen (ISSUE 47) on this device's own
               three-pass dot: a full 1024-row tile of fractional rows on
               the lane grid answers as the six-pass program answers, a
               tile's screen values stay under the bound, and a planted
               crowd of near-equal neighbours is flagged and re-scanned.
- ``ring``     with more than one chip: both ring schedules over
               min(4, chips) devices against the serial result, the
               shardings spanning that many devices.
               With one chip it says ``not run`` and the summary carries
               ``"ring_devices": 0``.
- ``serve``    ``python -m mpi_knn_tpu serve`` over the same corpus: waits
               for ``/healthz`` ready, posts requests of several sizes from
               two tenants through ``frontend.loadgen``'s client, compares
               the ids with ``all_knn(X, queries=Q)``, parses ``/metrics``,
               requires that no request compiled anything, and checks the
               exit code of a SIGTERM shutdown.

One process per chip: this parent never imports jax. It runs the compute
phases in one child, reaps it, and only then starts the server child.

The last stdout line is one JSON object with exactly these keys, the device
as jax reports it: ``{"ok": true, "device": {"platform": "tpu", "kind":
"...", "count": 1}}``. The line before it, ``summary: platform=... {...}``,
carries the per-phase ``ok`` and seconds, ``ring_devices`` and
``compile_cache_dir``. The exit code is 0 only if every phase passed.
Without a TPU the device phase fails and neither line is printed. ``--tiny``
cuts the row counts (never the width) and ``--allow-cpu`` lets the script
run on the CPU for debugging the script itself; every line then says
``platform=cpu``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import traceback
from types import SimpleNamespace

import numpy as np

from mpi_knn_tpu.frontend import loadgen
from mpi_knn_tpu.obs.metrics import parse_prometheus

K = 10
RECALL_GATE = 0.999
SERVE_ROWS = (1, 16, 300, 1024)  # three buckets of a 256-row base
SERVE_TILES = (1024, 8192)


def say(phase: str, platform: str, ok: bool, seconds: float, detail: str):
    print(
        f"{phase}: {'ok' if ok else 'FAILED'} platform={platform} "
        f"{detail} ({seconds:.2f} s)",
        flush=True,
    )


def compare_neighbors(ids, dists, ref_ids, ref_dists) -> tuple[float, str]:
    """(share of slots naming the same neighbor, what is wrong or ``""``).
    A slot may differ only where both distances agree to f32 resolution:
    two programs that round the same sums in a different order rank
    near-equal candidates differently, more often the denser the corpus.
    More than 2 % of such slots means something else is wrong."""
    ids, ref_ids = np.asarray(ids), np.asarray(ref_ids)
    dists, ref_dists = np.asarray(dists), np.asarray(ref_dists)
    if ids.shape != ref_ids.shape:
        return 0.0, f" mismatch: shape {ids.shape} != {ref_ids.shape}"
    diff = ids != ref_ids
    equal = 1.0 - float(diff.mean())
    if not np.isfinite(dists).all():
        return equal, " mismatch: non-finite distances"
    tied = np.isclose(dists[diff], ref_dists[diff], rtol=1e-5, atol=0.0)
    if not tied.all():
        return equal, (f" mismatch: {int((~tied).sum())} slots differ "
                       "beyond a tie")
    if equal < 0.98:
        return equal, f" mismatch: {1 - equal:.2%} of slots are tie flips"
    return equal, ""


# ---------------------------------------------------------------------------
# the compute child: device, allknn, ring — one process, one chip


def per_call_passes(X, nq: int, cfg):
    """The first ``nq`` rows of device array ``X`` against all of it, by the
    passes every call made over its corpus before one was kept
    (``center_for_l2`` + ``prepare_tiles`` + ``knn_chunk_update``)."""
    import jax.numpy as jnp

    from mpi_knn_tpu.backends.serial import (
        effective_tiles,
        knn_chunk_update,
        onepass_rule,
        prepare_tiles,
    )
    from mpi_knn_tpu.ops.distance import center_for_l2, onepass_fact
    from mpi_knn_tpu.ops.topk import init_topk_tiles

    corpus, queries, fact, _ = center_for_l2(X, X[:nq], all_pairs=False)
    q_tile, c_tile = effective_tiles(cfg, X.shape[0], nq)
    q_tiles, qid_tiles, c_tiles, c_ids, q_pad = prepare_tiles(
        corpus, queries, np.arange(nq, dtype=np.int32), cfg, q_tile, c_tile)
    carry = init_topk_tiles(q_pad // q_tile, q_tile, cfg.k, dtype=jnp.float32)
    one = onepass_fact(cfg, fact) if onepass_rule(cfg, q_tile) else None
    d, i, *_ = knn_chunk_update(
        q_tiles, qid_tiles, c_tiles, c_ids, *carry, cfg, one)
    return SimpleNamespace(ids=i.reshape(q_pad, cfg.k)[:nq],
                           dists=d.reshape(q_pad, cfg.k)[:nq])


def compute_child(args) -> int:
    platform = "cpu" if args.allow_cpu else "tpu"
    out = {"device": None, "phases": {}, "ring_devices": 0,
           "compile_cache_dir": None}

    def record(phase, ok, t0, detail, **extra):
        seconds = time.perf_counter() - t0
        out["phases"][phase] = {"ok": bool(ok),
                                "seconds": round(seconds, 3), **extra}
        say(phase, platform, ok, seconds, detail)
        return ok

    t0 = time.perf_counter()
    try:
        compute_phases(args, platform, out, record)
    except Exception as e:  # noqa: BLE001 — a phase that raises has failed
        traceback.print_exc()
        record("raised", False, t0, f"{type(e).__name__}: {str(e)[:300]}")
    with open(os.path.join(args.work, "compute.json"), "w") as f:
        json.dump(out, f)
    return 0 if all(p["ok"] for p in out["phases"].values()) else 1


def compute_phases(args, platform, out, record) -> None:
    # -- device -----------------------------------------------------------
    t0 = time.perf_counter()
    from mpi_knn_tpu.utils.platform import force_platform, use_compile_cache

    force_platform(platform)
    out["compile_cache_dir"] = use_compile_cache()
    import jax
    import jax.numpy as jnp

    try:
        devices = jax.devices()
    except RuntimeError as e:
        record("device", False, t0, f"no {platform} device: {e}")
        return
    import importlib.metadata

    import jaxlib

    from mpi_knn_tpu.analysis.cost import profile_for_platform

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "absent"
    kind = devices[0].device_kind
    profile = profile_for_platform(devices[0].platform, kind)
    out["device"] = {"platform": devices[0].platform, "kind": kind,
                     "count": len(devices)}
    if not record(
        "device",
        jax.default_backend() == platform and profile is not None,
        t0,
        f"kind={kind!r} count={len(devices)} profile={profile} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}",
    ):
        return

    from bench import oracle_topk
    from mpi_knn_tpu import KNNConfig, all_knn
    from mpi_knn_tpu.data.mnist import load_mnist
    from mpi_knn_tpu.utils.report import recall_at_k

    m = 2000 if args.tiny else 60000
    X, _, source = load_mnist(m=m)
    cfg = KNNConfig(  # bench.py's configuration
        k=K, backend="serial", query_tile=4096, corpus_tile=8192,
        topk_method="exact", merge_schedule="twolevel",
        matmul_precision="high",
    )

    # -- allknn -----------------------------------------------------------
    def cache_entries():
        try:
            return len(os.listdir(out["compile_cache_dir"]))
        except FileNotFoundError:
            return 0

    t0 = time.perf_counter()
    entries = cache_entries()
    Xd = jax.device_put(jnp.asarray(X))
    jax.block_until_ready(Xd)
    t1 = time.perf_counter()
    serial = all_knn(Xd, config=cfg)
    jax.block_until_ready((serial.dists, serial.ids))
    compile_s = time.perf_counter() - t1
    t1 = time.perf_counter()
    serial = all_knn(Xd, config=cfg)
    jax.block_until_ready((serial.dists, serial.ids))
    warm_s = time.perf_counter() - t1
    s_ids, s_dists = np.asarray(serial.ids), np.asarray(serial.dists)
    sample = np.linspace(0, m - 1, num=min(256, m), dtype=np.int64)
    recall = recall_at_k(s_ids[sample], oracle_topk(X, sample, K))
    # the per-tile selection's counter on one real tile of this data: the
    # share of rows its certificate flags (a flagged row sends the whole
    # tile step to the full-width top-k); None where the shape bypasses
    from mpi_knn_tpu.ops.distance import pairwise_dist
    from mpi_knn_tpu.ops.topk import lane_bin_flagged_share

    Xc = Xd - jnp.mean(Xd, axis=0)
    flagged = lane_bin_flagged_share(
        pairwise_dist(Xc[:cfg.query_tile], Xc[-cfg.corpus_tile:]), K)
    # the one-pass rule's data test on THIS device: centred whole-number
    # rows are bf16 numbers, the same rows + 0.25 are not (a test by
    # rounding read True for both inside a TPU fusion, which tier-1 on the
    # CPU cannot see: PERF.md §6, PR 29)
    from mpi_knn_tpu.ops.distance import center_corpus

    W = jnp.asarray(np.random.default_rng(2).integers(
        0, 256, (4096, X.shape[1])).astype(np.float32))
    rule_facts = [bool(center_corpus(w)[2]) for w in (W, W + 0.25)]
    # a sliced job over ONE device array prepares its corpus once: the
    # second call brings nothing new and answers what the first did, and
    # both what the all-pairs call (prepared, used, dropped) answered for
    # those rows; on fractional rows (no exact arithmetic to hide behind)
    # a remembered corpus answers bit for bit what the per-call passes do
    from mpi_knn_tpu.obs.metrics import get_registry

    def prepared():
        return [int(get_registry().counter(
            "knn_corpus_prepare_total", labels={"result": r}).value)
            for r in ("miss", "hit")]

    nq = min(cfg.query_tile, m)
    rows = np.arange(nq, dtype=np.int32)
    before = prepared()
    sliced = [all_knn(Xd, queries=Xd[:nq], query_ids=rows, config=cfg)
              for _ in range(2)]
    prepare = [b - a for a, b in zip(before, prepared())]
    frac = W + 0.25
    kept = [all_knn(frac, queries=frac[:1024], query_ids=rows[:1024],
                    config=cfg) for _ in range(2)][1]
    sliced_equal = [
        bool(np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
             and np.array_equal(np.asarray(a.dists), np.asarray(b.dists)))
        for a, b in ((sliced[1], sliced[0]),
                     (sliced[1], SimpleNamespace(ids=s_ids[:nq],
                                                 dists=s_dists[:nq])))]
    # two programs (the norms an input / made inside), fractional rows: the
    # same neighbours, distances to the last bit or two — since PR 33 the
    # v5e compiler no longer gives both the same bits (1 ulp apart in a
    # third of the slots, both equally far from float64: PERF.md §7)
    per_call = per_call_passes(frac, 1024, cfg)
    sliced_equal.append(bool(
        np.array_equal(np.asarray(kept.ids), np.asarray(per_call.ids))
        and np.allclose(np.asarray(kept.dists), np.asarray(per_call.dists),
                        rtol=5e-7, atol=0.0)))
    per_call_bits = float(np.mean(
        np.asarray(kept.dists) == np.asarray(per_call.dists)))
    ok = (
        s_ids.shape == (m, K)
        and np.isfinite(s_dists).all()
        and recall >= RECALL_GATE
        and rule_facts == [True, False]
        and prepare == [1, 1]
        and all(sliced_equal)
    )
    record(
        "allknn", ok, t0,
        f"data={source} shape={list(X.shape)} k={K} first_call_s="
        f"{compile_s:.2f} warm_call_s={warm_s:.3f} recall@{K}={recall:.4f} "
        f"compile_cache_entries={entries}->{cache_entries()} "
        f"select_flagged_rows_and_tile={flagged} "
        f"onepass_facts_whole_frac={rule_facts} "
        f"prepare={'hit' if prepare == [1, 1] else prepare} "
        f"sliced_equal_first_allpairs_percall={sliced_equal} "
        f"percall_dists_bit_equal_share={per_call_bits:.4f}",
        first_call_s=round(compile_s, 3), warm_call_s=round(warm_s, 4),
        recall=round(float(recall), 5),
    )

    # what the server must answer, computed through the one-shot API with
    # the configuration the serve command builds from its flags
    rng = np.random.default_rng(1)
    n_q = sum(SERVE_ROWS)
    Q = (X[rng.integers(0, m, n_q)]
         + rng.standard_normal((n_q, X.shape[1])) * 8.0).astype(np.float32)
    expect = all_knn(
        Xd, queries=Q,
        config=KNNConfig(k=K, backend="serial", query_tile=SERVE_TILES[0],
                         corpus_tile=SERVE_TILES[1]),
    )
    np.savez(os.path.join(args.work, "expect.npz"), queries=Q,
             ids=np.asarray(expect.ids), dists=np.asarray(expect.dists))

    # -- cosine ------------------------------------------------------------
    # the prepared cosine path on THIS device: fractional rows, each at a
    # length of its own (with unit rows 1 - q.c is right whatever is
    # skipped), served from a resident index against the one-shot call,
    # and both against the direct form in float64 on the host
    t0 = time.perf_counter()
    from mpi_knn_tpu import build_index, query_knn

    crng = np.random.default_rng(3)
    c_rows = min(m, 16384)
    C = ((X[:c_rows] + 0.25) * np.exp(crng.uniform(
        np.log(0.5), np.log(2.0), (c_rows, 1)))).astype(np.float32)
    n_cq = 1100  # one full 1024-row query tile and a ragged one
    CQ = ((C[crng.integers(0, c_rows, n_cq)]
           + crng.standard_normal((n_cq, C.shape[1])) * 8.0)
          * np.exp(crng.uniform(np.log(0.5), np.log(2.0), (n_cq, 1)))
          ).astype(np.float32)
    ccfg = KNNConfig(k=K, backend="serial", metric="cosine",
                     query_tile=SERVE_TILES[0], corpus_tile=SERVE_TILES[1],
                     exclude_zero=False, query_bucket=64)
    cos_steps = get_registry().counter(
        "knn_dist_tile_steps_total", labels={"path": "cosine"})
    steps_before = cos_steps.value
    Cd = jax.device_put(jnp.asarray(C))
    one_shot = all_knn(Cd, queries=CQ, config=ccfg)
    cindex = build_index(Cd, ccfg)
    served = query_knn(CQ, cindex)
    rest_width = int(cindex.tiles.shape[-1])
    cs_ids, co_ids = np.asarray(served.ids), np.asarray(one_shot.ids)
    cs_d, co_d = np.asarray(served.dists), np.asarray(one_shot.dists)
    if rest_width == cindex.dim:
        agree_how = "bit_for_bit"
        agree = bool(np.array_equal(cs_ids, co_ids)
                     and np.array_equal(cs_d, co_d))
    else:
        # the index rests zero-padded on the lane grid and its scan screens
        # (serve/index.py rest_width; the one-shot call keeps the rows'
        # width): the same values up to float32's summation order. Slot
        # for slot the two distances lie within 2e-6, and wherever the two
        # ids differ the rows TIE: their float64 distances to that query,
        # computed here on the host, lie within 2e-6 of each other (these
        # pixel rows are nearly parallel and swap a few in a thousand)
        agree_how = "to_2e-6_and_every_other_id_a_tie"
        row, _ = differ = np.nonzero(cs_ids != co_ids)

        def direct64(ids):
            q, c = CQ[row].astype(np.float64), C[ids].astype(np.float64)
            return 1.0 - (q * c).sum(1) / (
                np.linalg.norm(q, axis=1) * np.linalg.norm(c, axis=1))

        agree = bool(
            np.max(np.abs(cs_d - co_d)) <= 2e-6
            and (cs_ids >= 0).all() and (co_ids >= 0).all()
            and np.all(np.abs(direct64(cs_ids[differ])
                              - direct64(co_ids[differ])) <= 2e-6))
        agree_how += f"(ids_differing={len(row)}_of_{cs_ids.size})"
    c64, q64 = C.astype(np.float64), CQ[:64].astype(np.float64)
    c_len = np.linalg.norm(c64, axis=1)
    direct = 1.0 - (q64 @ c64.T) / (
        np.linalg.norm(q64, axis=1)[:, None] * c_len[None, :])
    want_i = np.argsort(direct, axis=1, kind="stable")[:, :K]
    want_d = np.take_along_axis(direct, want_i, axis=1)
    got_d = np.asarray(served.dists)[:64].astype(np.float64)
    # a slot naming another row is a hit where its distance ties the k-th
    got_i = np.asarray(served.ids)[:64]
    cos_recall = float((
        (got_i[:, :, None] == want_i[:, None, :]).any(axis=2)
        | (np.abs(got_d - want_d[:, -1:]) <= 2e-6)).mean())
    # absolute: pixel rows are nearly parallel (distances of 1e-3 and
    # under), and a float32 similarity near 1 resolves 6e-8
    cos_err = float(np.max(np.abs(got_d - want_d)))
    record(
        "cosine",
        agree and cos_recall >= RECALL_GATE and cos_err <= 2e-6
        and cos_steps.value > steps_before,
        t0,
        f"corpus={list(C.shape)} queries={n_cq} fractional rows of length "
        f"{c_len.min():.0f}-{c_len.max():.0f} "
        f"served_agrees_with_all_knn={agree} how={agree_how} "
        f"rest_width={rest_width} "
        f"recall@{K}_vs_float64={cos_recall:.5f} "
        f"dist_abs_err_max={cos_err:.2e} "
        f"cosine_tile_steps={int(cos_steps.value - steps_before)}",
        recall=round(float(cos_recall), 5),
    )

    # -- screen ------------------------------------------------------------
    # the certified screen, on THIS device's three-pass dot (the CPU has
    # none: every precision is float32's own there): fractional embedding-
    # shaped rows on the lane grid, a full 1024-row tile. The screened
    # program's answer against the six-pass program's (the rule patched
    # off under another jit key), a tile's screen values against its
    # six-pass values under the bound, and a planted crowd — 40 corpus rows
    # within the bound of one another around query 0 — that must be
    # flagged, re-scanned and answered as the six-pass program answers
    t0 = time.perf_counter()
    import unittest.mock

    from mpi_knn_tpu.backends import serial
    from mpi_knn_tpu.ops.distance import unit_rows

    srng = np.random.default_rng(47)
    s_dim, s_rows = 256, 2048 if args.tiny else 32768
    cen = srng.standard_normal((64, s_dim))
    cen /= np.linalg.norm(cen, axis=1, keepdims=True)
    sigma = 0.5 / np.sqrt(s_dim)

    def embed(n):
        rows = cen[srng.integers(0, 64, n)] + sigma * srng.standard_normal(
            (n, s_dim))
        return (rows * np.exp(srng.uniform(
            np.log(0.5), np.log(2.0), (n, 1)))).astype(np.float32)

    SC, SQ = embed(s_rows), embed(1024)
    crowd = srng.choice(s_rows, 40, replace=False)
    SC[crowd] = SQ[0] * (1 + 1e-6 * srng.standard_normal((40, s_dim)))
    scfg = KNNConfig(k=K, backend="serial", metric="cosine", query_tile=1024,
                     corpus_tile=1024 if args.tiny else 8192,
                     exclude_zero=False, exclude_self=False)
    wide = serial.screen_rule(scfg, 1024, scfg.corpus_tile, s_dim)
    SCd = jax.device_put(jnp.asarray(SC))
    screened = all_knn(SCd, queries=SQ, config=scfg)
    with unittest.mock.patch.object(serial, "screen_rule",
                                    lambda *a, **k: None):
        # (another static argument: a jit key of its own)
        sixpass = all_knn(SCd, queries=SQ,
                          config=scfg.replace(recall_target=0.96))
    s_rows_counted = (None if screened.screen_rows is None
                      else np.asarray(screened.screen_rows).tolist())
    sd, si = np.asarray(screened.dists), np.asarray(screened.ids)
    xd, xi = np.asarray(sixpass.dists), np.asarray(sixpass.ids)
    dist_diff = float(np.abs(sd - xd).max())
    # every returned distance is computed exactly, so an id can differ
    # only where two rows tie to float32's last bits (the crowd's do)
    ids_same = float((si == xi)[1:].mean())
    q_unit = unit_rows(jnp.asarray(SQ))
    inv = serial.cosine_inv_norms(SCd[:8192])  # (rows past the end: none)
    tile_ids = jnp.arange(min(8192, s_rows), dtype=jnp.int32)
    tile = [np.asarray(serial.masked_dist_tile(
        q_unit, jnp.full((1024,), -1, jnp.int32), None,
        SCd[:tile_ids.size], tile_ids, inv[:tile_ids.size], scfg,
        screen=flag)) for flag in (True, False)]
    eps = np.asarray(serial.screen_eps("cosine", s_dim, q_unit, None, None))
    screen_err = float(np.abs(tile[0] - tile[1]).max())
    record(
        "screen",
        wide == 3 * K + 2 and sixpass.screen_rows is None
        and s_rows_counted is not None and sum(s_rows_counted) == 1024
        and s_rows_counted[1] >= 1  # the crowd's row at least
        and s_rows_counted[0] >= 900
        and np.asarray(screened.select_tiles).tolist() == [0, 1]
        and dist_diff <= 2e-6 and ids_same >= 0.999
        and screen_err <= float(eps.min()),
        t0,
        f"corpus={list(SC.shape)} k'={wide} "
        f"screen_rows_certified_flagged={s_rows_counted} "
        f"select_tiles={np.asarray(screened.select_tiles).tolist()} "
        f"dist_abs_diff_vs_six_pass_max={dist_diff:.2e} "
        f"ids_equal_six_pass_rows_1_on={ids_same:.5f} "
        f"screen_tile_abs_err_max={screen_err:.2e} under eps={eps.min():.2e}",
    )

    # -- fused_screen ------------------------------------------------------
    # the screen INSIDE the kernel (ISSUE 51), on THIS device's MXU: the
    # same fractional rows under L2 (d = 256: the query tile in two
    # blocks). The kernel's lists against float64 at the slots they name
    # — three passes' error, under the kernel's own eps and far under one
    # pass's —, the program's answer against the six-pass program's, its
    # steps under ``dist_steps``' seventh column, and a crowd on a sphere
    # around query 0 flagged and re-scanned
    t0 = time.perf_counter()
    from mpi_knn_tpu.ops.distance import sq_norms
    from mpi_knn_tpu.ops.fused_scan import fused_scan
    from mpi_knn_tpu.ops.topk import lane_bin_depth

    FC = SC.copy()
    ball = srng.standard_normal((40, s_dim))
    FC[crowd] = SQ[0] + 0.05 * ball / np.linalg.norm(
        ball, axis=1, keepdims=True)
    fcfg = scfg.replace(metric="l2", center=False)
    c_tile = fcfg.corpus_tile
    block = serial.fused_screen_rule(fcfg, 1024, c_tile, s_dim)
    FCd = jax.device_put(jnp.asarray(FC))
    f_screened = all_knn(FCd, queries=SQ, config=fcfg)
    with unittest.mock.patch.object(serial, "screen_rule",
                                    lambda *a, **k: None):
        f_sixpass = all_knn(FCd, queries=SQ,
                            config=fcfg.replace(recall_target=0.96))
    f_steps = np.atleast_2d(f_screened.dist_steps).sum(0).tolist()
    f_rows = (None if f_screened.screen_rows is None
              else np.asarray(f_screened.screen_rows).tolist())
    f_diff = float(np.abs(np.asarray(f_screened.dists)
                          - np.asarray(f_sixpass.dists)).max())
    f_same = float((np.asarray(f_screened.ids)
                    == np.asarray(f_sixpass.ids))[1:].mean())
    stack = FCd.reshape(-1, c_tile, s_dim)
    n_t = stack.shape[0]
    q_sq, sqs = sq_norms(jnp.asarray(SQ)), serial.stack_norms(stack, "l2")
    kd, ki, _ = fused_scan(
        jnp.asarray(SQ), jnp.full((1024,), -1, jnp.int32), q_sq, stack,
        jnp.arange(s_rows, dtype=jnp.int32).reshape(n_t, c_tile), sqs,
        serial.bound_refreshes(n_t), k=wide,
        depth=lane_bin_depth(1024, c_tile, wide), exclude_self=False,
        exclude_zero=False, zero_eps=0.0, block=block or 1024, screen=True)
    kd, ki = np.asarray(kd)[:, :128], np.asarray(ki)[:, :128]
    held = np.isfinite(kd)
    real = ((SQ.astype(np.float64)[:, None, :]
             - FC.astype(np.float64)[np.maximum(ki, 0)]) ** 2).sum(-1)
    k_err = np.abs(kd - real)[held]
    f_eps = np.asarray(serial.screen_eps(
        "l2", s_dim, jnp.asarray(SQ), q_sq, jnp.max(sqs), fused=True))
    one_pass = 2.0 ** -8 * 2 * float(np.sqrt(q_sq.max() * sqs.max()))
    record(
        "fused_screen",
        block is not None and f_steps == [0] * 6 + [n_t]
        and f_rows is not None and sum(f_rows) == 1024 and f_rows[1] >= 1
        and f_rows[0] >= 900
        and np.asarray(f_screened.select_tiles).tolist() == [0, 1]
        and f_diff <= 1e-5 and f_same >= 0.999
        and held.sum() >= 32 * 1024
        and float(k_err.max()) <= float(f_eps.min())
        and float(k_err.max()) < 0.05 * one_pass,
        t0,
        f"corpus={list(FC.shape)} block={block} dist_steps={f_steps} "
        f"screen_rows_certified_flagged={f_rows} "
        f"dist_abs_diff_vs_six_pass_max={f_diff:.2e} "
        f"ids_equal_six_pass_rows_1_on={f_same:.5f} "
        f"kernel_list_abs_err_vs_float64_max={k_err.max():.2e} "
        f"under eps={f_eps.min():.2e} (one pass's: {one_pass:.2e})",
    )

    # -- rescan ------------------------------------------------------------
    # the carried selection's way out, on THIS device: more neighbours of
    # one query row than the lists are deep, all in ONE lane and spread
    # over DIFFERENT corpus tiles (no tile's own certificate would flag
    # them), must be flagged after the scan, answered again by the re-scan
    # and exact; the same call without the plant keeps the carried answer
    t0 = time.perf_counter()
    from mpi_knn_tpu.backends.serial import carried_depth, effective_tiles

    n_rq = min(1024, m)
    rcfg = cfg.replace(corpus_tile=1024) if args.tiny else cfg
    q_tile, c_tile = effective_tiles(rcfg, m, n_rq)
    depth = carried_depth(rcfg, q_tile, c_tile)
    n_tiles = -(-m // c_tile)
    lane, n_plant = 37, (depth or 0) + 2
    at = np.array([(j % n_tiles) * c_tile + lane + 128 * (j // n_tiles)
                   for j in range(n_plant)])
    RQ = X[:n_rq].astype(np.float32)
    planted = X.astype(np.float32).copy()
    planted[at] = RQ[0]
    planted[at, np.arange(n_plant)] += 16.0 * (1 + np.arange(n_plant))
    counts, close, chunks = [], [], []
    for corpus in (X.astype(np.float32), planted):
        got = all_knn(jax.device_put(jnp.asarray(corpus)), queries=RQ,
                      config=rcfg)
        counts.append(None if got.select_tiles is None
                      else np.asarray(got.select_tiles).tolist())
        # what *bins* did with the batch's chunks under the row bound
        chunks.append(None if got.bins_chunks is None
                      else np.asarray(got.bins_chunks).tolist())
        c64, q64 = corpus.astype(np.float64), RQ[:64].astype(np.float64)
        direct = ((q64 ** 2).sum(1)[:, None] + (c64 ** 2).sum(1)[None, :]
                  - 2.0 * q64 @ c64.T)
        direct[direct <= 0.0] = np.inf  # query mode drops zero distances
        close.append(bool(np.allclose(
            np.asarray(got.dists)[:64], np.sort(direct, axis=1)[:, :K],
            rtol=1e-5, atol=2.0)))
    # the planted rows, nearest first: more of one lane than the lists hold
    found = np.asarray(got.ids)[0, :min(n_plant, K)].tolist()
    from mpi_knn_tpu.ops.lane_bin import lane_bin_chunks
    from mpi_knn_tpu.ops.topk import lane_bin_bound_rides

    # every chunk inserted or skipped where the bound rides (the full-size
    # run's 1024 x 8192 tiles), no count where it does not (--tiny)
    n_chunks = n_tiles * lane_bin_chunks(q_tile, c_tile)
    rides = lane_bin_bound_rides(q_tile, c_tile)
    record(
        "rescan",
        depth is not None and counts == [[1, 0], [0, 1]] and all(close)
        and found == at[:K].tolist()
        and all((sum(c) == n_chunks) if rides else c is None
                for c in chunks),
        t0,
        f"tile={q_tile}x{c_tile} depth={depth} planted={n_plant} rows of "
        f"lane {lane} over {min(n_plant, n_tiles)} tiles "
        f"select_tiles_plain_planted={counts} "
        f"bound_rides={rides} bins_chunks_inserted_skipped={chunks[0]} "
        f"bins_skipped_share={(chunks[0] or [0, 0])[1] / n_chunks:.4f} "
        f"planted_rows_found_in_order={found == at[:K].tolist()} "
        f"dists_close_to_float64_plain_planted={close}",
    )

    # -- ring -------------------------------------------------------------
    if len(devices) == 1:
        print(f"ring: not run (1 device) platform={platform}", flush=True)
        return
    from mpi_knn_tpu.serve import build_index

    nd = min(4, len(devices))
    out["ring_devices"] = nd
    # where the ring puts the corpus (one placement for both schedules)
    corpus = build_index(
        X, cfg.replace(backend="ring-overlap", num_devices=nd)
    ).corpus_sharded
    shard_devices = {s.device for s in corpus.addressable_shards}
    shard_rows = {s.data.shape[0] for s in corpus.addressable_shards}
    for backend in ("ring-overlap", "ring"):
        t0 = time.perf_counter()
        got = all_knn(X, config=cfg.replace(backend=backend, num_devices=nd))
        jax.block_until_ready((got.dists, got.ids))
        equal, bad = compare_neighbors(got.ids, got.dists, s_ids, s_dists)
        result_devices = len(got.ids.sharding.device_set)
        spread = (
            len(shard_devices) == nd
            and shard_rows == {corpus.shape[0] // nd}
            and result_devices == nd
        )
        record(
            backend, not bad and spread, t0,
            f"devices={nd} corpus_shards={sorted(d.id for d in shard_devices)}"
            f" rows_per_shard={sorted(shard_rows)} result_devices="
            f"{result_devices} ids_equal_serial={equal:.5f}{bad}"
            # by the distance dot's path, the devices' rows added up: on
            # the chip a fourth column, the steps inside the fused scan
            f" dist_steps={np.atleast_2d(got.dist_steps).sum(0).tolist()}",
        )


# ---------------------------------------------------------------------------
# the serve phase: the parent is the client, the server is a child


def metric_total(samples: dict, name: str) -> float:
    return sum(v for k, v in samples.items()
               if k.split("{", 1)[0] == name)


def serve_phase(args, platform: str, work: str) -> tuple[bool, str]:
    expect = np.load(os.path.join(work, "expect.npz"))
    ready = os.path.join(work, "serve.url")
    argv = [
        sys.executable, "-m", "mpi_knn_tpu", "serve",
        "--data", "mnist", "--limit", str(2000 if args.tiny else 60000),
        "--platform", platform, "--backend", "serial", "--k", str(K),
        "--query-tile", str(SERVE_TILES[0]),
        "--corpus-tile", str(SERVE_TILES[1]),
        "--bucket", "256", "--max-batch-rows", "1024",
        "--bucket-headroom", "0.02", "--mutation-bucket", "64",
        "--port", "0", "--ready-file", ready, "-q",
    ]
    with open(os.path.join(work, "serve.log"), "wb") as log:
        proc = subprocess.Popen(argv, stdout=log, stderr=subprocess.STDOUT,
                                start_new_session=True)
    try:
        deadline = time.monotonic() + 600
        health = {}
        while not health.get("ready"):
            if proc.poll() is not None:
                return False, f"server exited {proc.returncode} at start-up"
            if time.monotonic() > deadline:
                return False, f"not ready after 600 s: {health}"
            time.sleep(0.5)
            if os.path.exists(ready):
                url = open(ready).read().strip()
                try:
                    health = loadgen.probe_server(url)
                except OSError:
                    pass  # a warming server answers 503
        profile = (health.get("device_profile") or {}).get("name", "")
        if not profile.startswith(platform):
            return False, f"server runs under profile {profile!r}"

        def compiled_total():
            return metric_total(
                parse_prometheus(loadgen.fetch_metrics(url)),
                "serve_executables_compiled_total",
            )

        before = compiled_total()
        sent, equal = 0, []
        for rep in range(2):  # second pass: every bucket already used
            lo = 0
            for i, n in enumerate(SERVE_ROWS):
                status, doc = loadgen.post_query(
                    url, f"smoke-{(i + rep) % 2}",
                    expect["queries"][lo:lo + n], timeout_s=120.0,
                )
                if status != 200:
                    return False, f"{n}-row request answered {status}"
                same, bad = compare_neighbors(
                    doc["ids"], doc["dists"],
                    expect["ids"][lo:lo + n], expect["dists"][lo:lo + n],
                )
                if bad:
                    return False, f"{n}-row request:{bad}"
                equal.append(same * n)
                lo += n
                sent += 1
        after = compiled_total()
        if after != before:
            return False, (f"requests compiled {after - before:g} "
                           "executables after warm-up")
        wrote = write_then_search(url, expect)
        if wrote:
            return False, wrote
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        if rc != 0:
            return False, f"SIGTERM shutdown exited {rc}"
        return True, (
            "raw_upsert+delete_seen_by_search=True "
            f"requests={sent} rows={list(SERVE_ROWS)} tenants=2 errors=0 "
            f"ids_equal_all_knn={sum(equal) / (2 * sum(SERVE_ROWS)):.5f} "
            f"buckets_warmed={health['warming']['total']}"
            f" executables_compiled={before:g}+0 profile={profile} "
            "sigterm_exit=0"
        )
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()


def post_raw(url: str, path: str, body: bytes):
    import json
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        url + path, data=body, method="POST",
        headers={"Content-Type": "application/octet-stream",
                 "X-Tenant": "smoke-writer"})
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, {}


def write_then_search(url: str, expect) -> str:
    """One raw upsert, one raw delete, a search that sees both, on the
    server's own platform (ISSUE 34). Two rows go in 64 grey levels of one
    pixel from two query rows (nearer than any corpus row, and far enough
    that float32's matmul form does not round the distance to the zero
    that ``exclude_zero`` hides), under new ids; one of them and the first query's old
    nearest neighbour go out again. "" when all is as it must be."""
    q = np.asarray(expect["queries"][:2], dtype=np.float32)
    rows = q.copy()
    rows[:, 0] += 64.0  # squared distance 4096
    new = np.array([1_000_000, 1_000_001], dtype="<i4")
    status, doc = post_raw(url, "/upsert",
                           new.tobytes() + rows.astype("<f4").tobytes())
    if status != 200 or doc.get("upserted") != 2:
        return f"raw /upsert answered {status} {doc}"
    status, doc = loadgen.post_query(url, "smoke-0", q, timeout_s=120.0)
    if status != 200 or [r[0] for r in doc["ids"]] != new.tolist():
        return f"the search after the upsert did not see it: {status} {doc}"
    if max(abs(r[0] - 4096.0) for r in doc["dists"]) > 4.0:
        return f"the upserted rows are not 4096 away: {doc['dists']}"
    old = int(expect["ids"][0][0])
    gone = np.array([1_000_000, old], dtype="<i4")
    status, doc = post_raw(url, "/delete", gone.tobytes())
    if status != 200 or doc.get("deleted") != 2:
        return f"raw /delete answered {status} {doc}"
    status, doc = loadgen.post_query(url, "smoke-1", q, timeout_s=120.0)
    if status != 200:
        return f"the search after the delete answered {status}"
    if set(gone.tolist()) & {i for r in doc["ids"] for i in r}:
        return f"a deleted id came back: {doc['ids']}"
    if doc["ids"][0][0] != int(expect["ids"][0][1]) or doc["ids"][1][0] != \
            1_000_001:
        return f"the search after the delete is not what is left: {doc['ids']}"
    return ""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="2 000 corpus rows instead of 60 000 (same width)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run on the CPU platform; for debugging this "
                    "script only, every line says platform=cpu")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--work", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child == "compute":
        return compute_child(args)

    platform = "cpu" if args.allow_cpu else "tpu"
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as work:
        if "jax" in sys.modules:
            raise RuntimeError("chip_smoke's parent imported jax: it would "
                               "hold the chip its children need")
        child = [sys.executable, os.path.abspath(__file__),
                 "--child", "compute", "--work", work]
        child += ["--tiny"] * args.tiny + ["--allow-cpu"] * args.allow_cpu
        rc = subprocess.run(child).returncode
        try:
            with open(os.path.join(work, "compute.json")) as f:
                summary = json.load(f)
        except OSError:
            print(f"compute child exited {rc} without a summary",
                  file=sys.stderr)
            return 1
        if not summary["phases"].get("device", {}).get("ok"):
            return 1  # no accelerator: no result line

        if rc == 0:
            t0 = time.perf_counter()
            try:
                ok, detail = serve_phase(args, platform, work)
            except Exception as e:  # noqa: BLE001 — a phase fails, loudly
                ok, detail = False, f"{type(e).__name__}: {e}"
            if not ok:
                log = os.path.join(work, "serve.log")
                if os.path.exists(log):
                    sys.stderr.write(open(log, errors="replace").read()[-4000:])
            seconds = time.perf_counter() - t0
            summary["phases"]["serve"] = {"ok": ok,
                                          "seconds": round(seconds, 3)}
            say("serve", platform, ok, seconds, detail)

    ok = rc == 0 and all(p["ok"] for p in summary["phases"].values())
    device = summary.pop("device")
    print(f"summary: platform={platform} {json.dumps(summary)}")
    # the driver's contract: these keys and no others, on the last line
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
