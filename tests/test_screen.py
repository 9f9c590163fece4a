"""The certified screen (ISSUE 47; ``backends/serial.py screen_rule`` /
``screen_eps`` / ``_merge_carried`` / ``_finish_screened``): where the rule
engages, the carried scan ranks in three bf16 passes, the k' candidates of
a row are finished at the configured precision, and a certificate says
when that is the six-pass answer.

On the CPU every precision is float32's own, so the proof is tested by
PLANTING the error: the screen's distance tile is replaced by the exact
values perturbed by up to ``screen_eps``, adversarially. Since ISSUE 51 an
L2 program of these shapes ranks INSIDE the kernel that walks the stack
(``fused_screen_rule``; the interpreter really multiplies bf16 pieces
there): a planted tile needs the XLA scan (``_merge`` patches the rule off
where it plants), and the kernel's own accumulation term is held against
an emulated split."""

import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_knn_tpu import KNNConfig, all_knn, build_index, query_knn
from mpi_knn_tpu.backends import serial
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.ops.distance import sq_norms, unit_rows
from mpi_knn_tpu.ops.topk import init_topk, lane_bin_depth
from mpi_knn_tpu.serve import ServeSession

K, WIDE = 10, 32
Q, C_TILE, TILES, D = 1024, 1024, 3, 128
N = TILES * C_TILE - 29  # the last tile ends in padding
METRICS = ("cosine", "l2", "ip")


def _cfg(metric, **kw):
    return KNNConfig(**{**dict(
        k=K, metric=metric, backend="serial", query_tile=Q,
        corpus_tile=C_TILE, exclude_self=False, center=False), **kw})


@functools.lru_cache(maxsize=None)
def _case(metric: str, seed: int = 0):
    """Fractional float32 rows in classes, so that a query has near
    neighbours at close, distinct distances; the operands as a tile
    program takes them: (q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs)."""
    rng = np.random.default_rng([seed, METRICS.index(metric)])
    cen = rng.normal(size=(32, D))
    x = (cen[rng.integers(0, 32, N)] + 0.4 * rng.normal(size=(N, D)))
    q = (cen[rng.integers(0, 32, Q)] + 0.4 * rng.normal(size=(Q, D)))
    x = np.concatenate([x, np.zeros((TILES * C_TILE - N, D))]).astype(
        np.float32)
    ids = np.arange(TILES * C_TILE, dtype=np.int32)
    ids[N:] = -1
    # a mutable index keeps ids that are no slot numbers
    ids[:N] = rng.permutation(N).astype(np.int32) + 7
    tiles = jnp.asarray(x.reshape(TILES, C_TILE, D))
    q_x = jnp.asarray(q.astype(np.float32))
    q_sq = None
    if metric == "l2":
        q_sq = sq_norms(q_x)
    elif metric == "cosine":
        q_x = unit_rows(q_x)
    return (q_x, jnp.full((Q,), -1, jnp.int32), q_sq, tiles,
            jnp.asarray(ids.reshape(TILES, C_TILE)),
            serial.stack_norms(tiles, metric))


def _merge(monkeypatch, cfg, case, plant=None, screen=True):
    """``merge_tiles_into_carry`` of ``case`` from an empty carry, under a
    jit of its own: the program the rule gives (``screen``; False: the
    six-pass program, the rule patched off), the screen's distance tile
    put through ``plant(values, tile ids) -> values`` where given (the XLA
    scan's tile: a value cannot be planted inside the kernel)."""
    if not screen:
        monkeypatch.setattr(serial, "screen_rule", lambda *a, **k: None)
    if plant is not None:
        monkeypatch.setattr(serial, "fused_screen_rule", lambda *a, **k: None)
        real = serial.masked_dist_tile

        def planted(*a, screen=False, **kw):
            d = real(*a, screen=screen, **kw)
            return plant(d, a[4]) if screen else d

        monkeypatch.setattr(serial, "masked_dist_tile", planted)

    @jax.jit
    def run(q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs):
        return serial.merge_tiles_into_carry(
            q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs,
            *init_topk(q_x.shape[0], cfg.k), cfg)

    out = run(*case)
    monkeypatch.undo()
    return tuple(None if o is None else np.asarray(o) for o in out)


def _eps(cfg, case):
    q_x, _, q_sq, tiles, _, tile_sqs = case
    return np.asarray(serial.screen_eps(
        cfg.metric, D, q_x, q_sq,
        serial.largest_norm_sq(cfg.metric, tiles, tile_sqs)))


def _true_ranks(cfg, case):
    """(Q, slots) the rank of every slot among a row's masked six-pass
    values (0: the nearest), by id."""
    q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs = case
    d = np.concatenate([np.asarray(serial.masked_dist_tile(
        q_x, q_ids, q_sq, tiles[t], tile_ids[t],
        None if tile_sqs is None else tile_sqs[t], cfg))
        for t in range(TILES)], axis=1)
    return np.argsort(np.argsort(d, axis=1, kind="stable"), axis=1), d


def _by_id(table):
    """(Q, ids) -> a lookup of a tile's columns, padding as its last."""
    return lambda ids: table[:, jnp.where(ids < 0, table.shape[1] - 1, ids)]


def _same_answers(got, want, case, rtol=2e-6):
    """Distances equal to float32's last bits (the finish's batched dot
    and the tile's dot sum in other orders on this backend; the L2 form's
    bits are those of its norms, not of the distance), ids equal but for
    near-ties: where the k-th and the (k+1)-th distance of a row are that
    close either id is the answer. Returns the share of equal ids."""
    gd, gi, wd, wi = got[0], got[1], want[0], want[1]
    floor = 1.0 if case[2] is None else float(
        jnp.max(case[2]) + jnp.max(case[5]))  # L2: |q|^2 + |c|^2
    np.testing.assert_array_less(
        np.abs(gd - wd) / np.maximum(np.abs(wd), floor), rtol)
    same = (gi == wi).mean()
    assert same > 0.999, same
    return same


@pytest.mark.parametrize("metric", METRICS)
def test_adversarial_error_up_to_eps_keeps_the_six_pass_answer(
        monkeypatch, metric):
    """(a) The screen's values are the exact ones pushed by up to eps the
    WRONG way — a row's true k nearest up, everything else down — and the
    merge still returns what the six-pass program returns, id for id and
    distance for distance: rows whose true k-th and k'-th neighbours the
    error cannot swap are certified, the others are flagged and re-scanned.
    Nothing of the screen's tile reaches the answer (the planted values
    are nowhere near the returned ones' last bits)."""
    cfg = _cfg(metric)
    case = _case(metric)
    assert serial.screen_rule(cfg, Q, C_TILE, D) == WIDE
    eps = _eps(cfg, case)
    rank, _ = _true_ranks(cfg, case)
    # by slot -> by id: the stack's ids are a permutation
    ids = np.asarray(case[4]).reshape(-1)
    push = np.zeros((Q, N + 8), np.float32)
    push[:, ids[:N]] = np.where(rank[:, :N] < K, 1.0, -1.0)
    push = jnp.asarray(push * eps[:, None])
    look = _by_id(push)

    def plant(d, blk_ids):
        return d + look(blk_ids)

    want = _merge(monkeypatch, cfg, case, screen=False)
    got = _merge(monkeypatch, cfg, case, plant)
    assert want[4] is None and got[4].sum() == Q
    _same_answers(got, want, case)
    exact = _merge(monkeypatch, cfg, case)
    _same_answers(exact, want, case)
    # the adversary can only cost re-scans
    assert got[4][1] >= exact[4][1]


@pytest.mark.parametrize("metric", METRICS)
def test_no_value_of_the_screen_reaches_the_answer(monkeypatch, metric):
    """Every returned distance is the finish's (or the re-scan's): with
    the screen's tile stretched to ``3 d + 7`` (an inner product's, whose
    near values are negative, to ``d / 4 + 7``) — the same ranking, values
    that are no distance of anything and far above the finished ones —
    every row is certified, none is re-scanned, and the answer is the
    six-pass program's."""
    cfg = _cfg(metric)
    case = _case(metric)
    want = _merge(monkeypatch, cfg, case, screen=False)
    stretch = 0.25 if metric == "ip" else 3.0
    got = _merge(monkeypatch, cfg, case, lambda d, ids: stretch * d + 7.0)
    assert got[4].tolist() == [Q, 0] and not got[2]
    _same_answers(got, want, case)


@pytest.mark.parametrize("metric", METRICS)
def test_neighbours_within_two_eps_flag_the_row(monkeypatch, metric):
    """(b) Rows whose 10th .. k'-th screen values lie within 2 eps of one
    another cannot be certified: they are flagged, counted in
    ``screen_rows``, and answered by the re-scan — the six-pass answer.
    Planted: for the first eight rows every value from the true k-th
    neighbour on is pulled to within eps of the k-th."""
    cfg = _cfg(metric)
    case = _case(metric)
    eps = _eps(cfg, case)
    rank, d = _true_ranks(cfg, case)
    kth = np.sort(d, axis=1)[:, K - 1]
    ids = np.asarray(case[4]).reshape(-1)
    squeeze = np.zeros((Q, N + 8), bool)
    squeeze[:8, ids[:N]] = (rank[:8, :N] >= K) & (rank[:8, :N] < 2 * WIDE)
    look_sq = _by_id(jnp.asarray(squeeze))
    level = jnp.asarray((kth + 0.5 * eps).astype(np.float32))

    def plant(d, blk_ids):
        return jnp.where(look_sq(blk_ids), level[:, None], d)

    want = _merge(monkeypatch, cfg, case, screen=False)
    clean = _merge(monkeypatch, cfg, case)
    got = _merge(monkeypatch, cfg, case, plant)
    assert got[4][1] >= max(8, clean[4][1]) and got[2]  # flagged, re-scanned
    _same_answers(got, want, case)
    # the re-scan's rows are the six-pass program's to the bit: one dot
    np.testing.assert_array_equal(got[0][:8], want[0][:8])
    np.testing.assert_array_equal(got[1][:8], want[1][:8])


@pytest.mark.parametrize("metric", METRICS)
def test_error_beyond_eps_is_a_wrong_answer_without_a_flag(
        monkeypatch, metric):
    """(c) The certificate is the only thing standing there: push one
    row's true nearest neighbour out by far MORE than eps (and nothing
    else) and the row is certified, not re-scanned — and wrong."""
    cfg = _cfg(metric)
    case = _case(metric)
    eps = _eps(cfg, case)
    rank, d = _true_ranks(cfg, case)
    span = (np.sort(d, axis=1)[:, 3 * WIDE] - np.sort(d, axis=1)[:, 0])
    assert (span[:8] > 50 * eps[:8]).all()
    ids = np.asarray(case[4]).reshape(-1)
    push = np.zeros((Q, N + 8), np.float32)
    push[:8, ids[:N]] = np.where(rank[:8, :N] == 0, span[:8, None], 0.0)
    look = _by_id(jnp.asarray(push))

    want = _merge(monkeypatch, cfg, case, screen=False)
    clean = _merge(monkeypatch, cfg, case)
    got = _merge(monkeypatch, cfg, case, lambda d, i: d + look(i))
    assert got[4].tolist() == clean[4].tolist()  # no flag more
    assert (got[1][:8, 0] != want[1][:8, 0]).all()  # the nearest is lost
    np.testing.assert_array_equal(got[1][:8, 0], want[1][:8, 1])
    _same_answers([a[8:] for a in got[:2]], [a[8:] for a in want[:2]], case)


@pytest.mark.parametrize("metric", METRICS)
def test_fewer_than_wide_finite_candidates_certify(monkeypatch, metric):
    """(d) A stack with fewer than k' live rows: the k'-th screen value is
    +inf, nothing finite was left out, the screen certifies every row —
    and the answer is the six-pass program's (the lanes' own certificate
    sends a row short of k' candidates to the re-scan, as it sends a row
    short of k in the parent)."""
    cfg = _cfg(metric)
    q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs = _case(metric)
    live = np.zeros(TILES * C_TILE, bool)
    live[np.random.default_rng(3).choice(N, WIDE - 5, replace=False)] = True
    few = jnp.where(jnp.asarray(live.reshape(TILES, C_TILE)), tile_ids, -1)
    case = (q_x, q_ids, q_sq, tiles, few, tile_sqs)
    want = _merge(monkeypatch, cfg, case, screen=False)
    got = _merge(monkeypatch, cfg, case)
    assert got[4].tolist() == [Q, 0]
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(a, b)
    assert np.isfinite(got[0]).all() and (got[1] >= 0).all()


def test_zero_test_waits_for_the_finish(monkeypatch):
    """The screen drops nothing by VALUE: a stored duplicate of a query
    row is a candidate, the finish's exact zero test removes it, and the
    row is answered as the six-pass program answers it."""
    cfg = _cfg("l2", exclude_zero=True)
    q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs = _case("l2")
    q_x = q_x.at[:16].set(tiles[1, 100:116])
    case = (q_x, q_ids, sq_norms(q_x), tiles, tile_ids, tile_sqs)
    want = _merge(monkeypatch, cfg, case, screen=False)
    got = _merge(monkeypatch, cfg, case)
    assert (want[0][:16] > 0).all()
    _same_answers(got, want, case)


_REPO = pathlib.Path(__file__).resolve().parent.parent


def _cell(name):
    config = json.loads((_REPO / "benchmark" / "configs" / name).read_text())
    return KNNConfig(**config["knn"]), config


@pytest.mark.parametrize("why,want,cfg,q_rows,c_tile,dim,how", [
    # "Where it engages": every condition of the rule, one row each
    ("the embedding cell's 1024-row bucket", WIDE,
     _cell("dbpedia-openai1m-1536-cos.json")[0], 1024, 8192, 1536, {}),
    ("its control: `high` is the program it always was", None,
     _cell("dbpedia-openai1m-1536-cos.json")[0].replace(
         matmul_precision="high"), 1024, 8192, 1536, {}),
    ("a 64-row bucket gains nothing and would pay the finish", None,
     _cell("dbpedia-openai1m-1536-cos.json")[0], 64, 8192, 1536, {}),
    ("512 rows: under the height the passes pay from", None,
     _cfg("cosine"), 512, 8192, 1536, {}),
    ("a 4096-row all-kNN tile of fractional rows", WIDE,
     _cfg("l2"), 4096, 8192, 256, {}),
    ("highest, spelled out", WIDE,
     _cfg("l2", matmul_precision="highest"), 1024, 8192, 128, {}),
    ("default precision: one pass already", None,
     _cfg("l2", matmul_precision="default"), 1024, 8192, 128, {}),
    ("a bf16 stack", None, _cfg("l2", dtype="bfloat16"), 1024, 8192, 128,
     {}),
    ("the program carries the one-pass branch (a whole-number corpus)",
     None, _cfg("l2"), 1024, 8192, 128, {"branch": True}),
    ("a predicate's words ride the scan", None,
     _cfg("l2"), 1024, 8192, 128, {"filtered": True}),
    ("the ring's rounds", None, _cfg("l2"), 1024, 8192, 128,
     {"varying": True}),
    ("the inner-product cell: d = 200 rests rows-minor", None,
     _cell("text2image10m-200-ip.json")[0], 1024, 8192, 200, {}),
    ("the streaming cell: d = 100", None,
     _cell("msturing10m-100-l2-stream.json")[0], 1024, 8192, 100, {}),
    ("an inner product on the lane grid", WIDE,
     _cfg("ip"), 1024, 8192, 256, {}),
    ("mixed proves nothing and stays as it is", None,
     _cfg("l2", precision_policy="mixed"), 1024, 8192, 128, {}),
    ("the stream schedule carries no lists", None,
     _cfg("l2", merge_schedule="stream"), 1024, 8192, 128, {}),
    ("another selection method", None,
     _cfg("l2", topk_method="block"), 1024, 8192, 128, {}),
    ("narrow corpus tiles: no lists to carry", None,
     _cfg("l2"), 1024, 512, 128, {}),
    ("k' = 3k + 2 past the finish kernel's 128 answers", None,
     _cfg("l2", k=43), 1024, 8192, 128, {}),
    ("k = 42: k' = 128 fits the kernel, not 1024 rows' lists (depth > 8)",
     None, _cfg("l2", k=42), 1024, 8192, 128, {}),
    ("a width past what the bound's slack covers", None,
     _cfg("l2"), 1024, 8192, 1 << 16, {}),
])
def test_screen_rule_engages_by_what_the_program_is(
        why, want, cfg, q_rows, c_tile, dim, how):
    """(e) The rule over shapes, metrics, precisions, facts and layouts."""
    assert serial.screen_rule(cfg, q_rows, c_tile, dim, **how) == want, why


def test_screen_width_and_depth():
    assert serial.screen_width(10) == WIDE == 32
    assert lane_bin_depth(1024, 8192, 24) == 6
    assert lane_bin_depth(1024, 8192, WIDE) == 7  # lists 896 wide


def test_counter_reaches_metrics_with_the_answer(monkeypatch):
    """(f) ``knn_screen_rows_total{result="certified"|"flagged"}``: a
    one-shot call carries ``[certified, flagged]`` on
    ``KNNResult.screen_rows``, a served batch's is added at retire, after
    the batch's own sync — nothing is fetched or counted inside the
    dispatch; a program that does not screen counts nothing."""
    reg = obs_metrics.MetricsRegistry()
    reg.count_screen_rows(np.array([1000, 24]))
    reg.count_screen_rows(np.array([1024, 0]))
    assert [reg.counter(obs_metrics.SCREEN_ROWS, labels={"result": r}).value
            for r in obs_metrics.SCREEN_RESULTS] == [2024, 24]
    assert 'knn_screen_rows_total{result="flagged"} 24' in (
        obs_metrics.to_prometheus(reg.snapshot()))

    def counted():
        reg = obs_metrics.get_registry()
        return [reg.counter(obs_metrics.SCREEN_ROWS,
                            labels={"result": r}).value
                for r in obs_metrics.SCREEN_RESULTS]

    rng = np.random.default_rng(4)
    x = rng.normal(size=(2 * C_TILE, D)).astype(np.float32)
    q = rng.normal(size=(Q, D)).astype(np.float32)
    cfg = _cfg("cosine", query_bucket=Q)
    one_shot = all_knn(x, queries=q, config=cfg)
    assert np.asarray(one_shot.screen_rows).tolist() == [Q, 0]
    assert all_knn(x, queries=q, config=cfg.replace(
        matmul_precision="high")).screen_rows is None
    assert all_knn(x, queries=q[:64], config=cfg).screen_rows is None

    index = build_index(x, cfg)
    before = counted()
    served = query_knn(q, index)
    assert np.asarray(served.screen_rows).tolist() == [Q, 0]
    # a session counts at retire and nowhere else
    count = obs_metrics.MetricsRegistry.count_screen_rows
    seen = []
    monkeypatch.setattr(
        obs_metrics.MetricsRegistry, "count_screen_rows",
        lambda self, rows: (seen.append(rows), count(self, rows))[1])
    session = ServeSession(index)
    submitted = session.submit(q)
    assert not seen  # nothing counted inside the dispatch
    batch, = submitted + session.drain()
    assert len(seen) == 1
    assert np.asarray(batch.screen_rows).tolist() == [Q, 0]
    assert [b - a for a, b in zip(before, counted())] == [2 * Q, 0]
    for got in (served, batch):
        np.testing.assert_array_equal(
            np.asarray(got.ids), np.asarray(one_shot.ids))


def _bf16_piece(v, truncate):
    """The bfloat16 number a float32 array is cut (or rounded, to nearest
    even) to, as float32: bfloat16 is float32's upper half."""
    bits = np.ascontiguousarray(v, np.float32).view(np.uint32)
    if not truncate:
        bits = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                           & np.uint32(1))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


def _rowdot(a, b):
    return (a.astype(np.float64) * b).sum(-1)


def _three_way_split_dot(x, y, truncate):
    """What a three-pass bf16 dot computes, its sums in float64 (a product
    of two bfloat16 numbers is exact in float32): x1 y1 + x1 y2 + x2 y1,
    the pieces cut by rounding or by truncation (x - x1 is exact)."""
    x1, y1 = _bf16_piece(x, truncate), _bf16_piece(y, truncate)
    x2, y2 = _bf16_piece(x - x1, truncate), _bf16_piece(y - y1, truncate)
    return _rowdot(x1, y1) + _rowdot(x1, y2) + _rowdot(x2, y1)


@pytest.mark.parametrize("truncate", [False, True], ids=["round", "cut"])
def test_three_way_split_error_is_under_the_constant(truncate):
    """``eps`` on paper, the split's term: 10^5 random pairs at the cell's
    width, and pairs built to hurt (every element's dropped bits set, all
    of one sign), through the emulated three-pass dot — bf16 rounding is
    exact arithmetic on the CPU — stay under ``_SCREEN_SPLIT |x| |y|``
    whichever way the pieces are cut; the derivation's own worst case,
    3.02 x 2^-14, is approached by the built pairs under truncation."""
    d = 1536
    rng = np.random.default_rng(11)
    worst = 0.0
    for _ in range(25):
        x = rng.standard_normal((4000, d), dtype=np.float32)
        y = 0.8 * x + 0.6 * rng.standard_normal((4000, d), dtype=np.float32)
        err = np.abs(_three_way_split_dot(x, y, truncate) - _rowdot(x, y))
        scale = np.sqrt(_rowdot(x, x) * _rowdot(y, y))
        worst = max(worst, (err / scale).max())
    assert worst < serial._SCREEN_SPLIT
    # built to hurt: all of one sign, every element's bits below the first
    # piece set (just under the next bfloat16 number: what a cut drops;
    # just under half a step: what a rounding drops)
    low = (2.0**-7 if truncate else 2.0**-8) - 2.0**-23
    a = (1 + rng.integers(0, 2**7, (256, d)) * 2.0**-7 + low).astype(
        np.float32)
    b = (1 + rng.integers(0, 2**7, (256, d)) * 2.0**-7 + low).astype(
        np.float32)
    err = np.abs(_three_way_split_dot(a, b, truncate) - _rowdot(a, b))
    built = (err / np.sqrt(_rowdot(a, a) * _rowdot(b, b))).max()
    assert worst < built < serial._SCREEN_SPLIT
    if truncate:  # a third of the derivation's own worst case and more
        assert built > 0.3 * 3.02 * 2.0**-14


def test_eps_per_metric_form():
    """The bound's forms: cosine needs no R and is zero for an all-zero
    (padding) row; an inner product scales with |q| R, L2 with 2 |q| R
    and the form's own roundings; at the cell's width the constant is
    what the docstring says."""
    q = jnp.asarray(np.stack([np.zeros(8), np.full(8, 0.5), np.ones(8)])
                    .astype(np.float32))
    k1536 = (2.0**-12 + 2.0**-18 + 2 * 1540 * 2.0**-23) * (1 + 2.0**-4)
    assert 6.4e-4 < k1536 < 6.6e-4
    cos = np.asarray(serial.screen_eps("cosine", 1536, q, None, None))
    assert cos[0] == 0 and cos[1] > 0
    np.testing.assert_allclose(
        cos[2], k1536 * np.sqrt(8) + 8 * 2.0**-23, rtol=1e-6)
    ip = np.asarray(serial.screen_eps("ip", 1536, q, None, jnp.float32(9.0)))
    np.testing.assert_allclose(
        ip[2], (k1536 + 2.0**-20) * np.sqrt(8) * 3, rtol=1e-6)
    l2 = np.asarray(serial.screen_eps(
        "l2", 1536, q, sq_norms(q), jnp.float32(9.0)))
    np.testing.assert_allclose(
        l2[2], 2 * k1536 * np.sqrt(8) * 3 + 2.0**-20 * (np.sqrt(8) + 3) ** 2,
        rtol=1e-6)
    with pytest.raises(ValueError, match="2\\^15"):
        serial.screen_eps("l2", 1 << 16, q, None, jnp.float32(1.0))


# ---------------------------------------------------------------------------
# the screen inside the kernel (ISSUE 51): its own accumulation term, and
# the certificate's way out with the kernel engaged


def _two_pieces(v, cut):
    """A float32 array's two bf16 pieces: as the kernel makes them
    (``ops/fused_scan.py``: the first CUT from the bits, the second what
    the cut left — exact in float32 — rounded to nearest), or both rounded,
    or both cut."""
    hi = _bf16_piece(v, truncate=cut != "round")
    return hi, _bf16_piece(v - hi, truncate=cut == "cut")


def _f32_sum(terms, order):
    """``terms`` (rows, n) float32 added along n IN float32: one after
    the other, in pairs (a tree), or 128 at a time and then the partial
    sums one after the other (an accumulator behind a 128-deep array)."""
    terms = np.ascontiguousarray(terms, np.float32)
    if order == "chain":
        return np.add.accumulate(terms, axis=1, dtype=np.float32)[:, -1]
    if order == "blocks":
        parts = np.stack([_f32_sum(terms[:, i:i + 128], "chain")
                          for i in range(0, terms.shape[1], 128)], axis=1)
        return _f32_sum(parts, "chain")
    while terms.shape[1] > 1:
        if terms.shape[1] % 2:
            terms = np.concatenate(
                [terms, np.zeros_like(terms[:, :1])], axis=1)
        terms = terms[:, 0::2] + terms[:, 1::2]
    return terms[:, 0]


@pytest.mark.parametrize("d", [128, 1536])
@pytest.mark.parametrize("cut", ["kernel", "round", "cut"])
@pytest.mark.parametrize("order", ["chain", "blocks", "tree"])
def test_kernel_accumulation_term_covers_an_emulated_split(d, cut, order):
    """``screen_additions(d, fused=True)`` on paper against the kernel's
    form emulated: the pieces as the kernel cuts them (and cut both other
    ways), the 3 d products (exact in float32) laid side by side and added
    in float32 in three orders — no order is the MXU's by contract — over
    random pairs and pairs built to hurt (all of one sign, so no rounding
    cancels; every dropped bit set). The sum's error stays under
    ``(3 d - 1) 2^-23 sum |products|``, the whole dot's under ``(c_P +
    (3 d - 1) 2^-23 (1 + 2^-5)) |x| |y|``; and the term is needed: the
    built pairs' chain is off by more than one rounding."""
    rng = np.random.default_rng([d, len(order), len(cut)])
    n = 2000 if d == 128 else 300
    x = rng.standard_normal((n, d), dtype=np.float32)
    y = 0.8 * x + 0.6 * rng.standard_normal((n, d), dtype=np.float32)
    low = 2.0**-7 - 2.0**-23  # just under the next bfloat16: what a cut drops
    a = (1 + rng.integers(0, 2**7, (n, d)) * 2.0**-7 + low).astype(np.float32)
    b = (1 + rng.integers(0, 2**7, (n, d)) * 2.0**-7 + low).astype(np.float32)
    adds = serial.screen_additions(d, fused=True) - (d + 4)
    assert adds == 3 * d - 1
    worst = 0.0
    for u, v in ((x, y), (a, b)):
        u1, u2 = _two_pieces(u, cut)
        v1, v2 = _two_pieces(v, cut)
        # (hi, hi, lo) against (hi, lo, hi), as the kernel lays them
        terms = np.concatenate([u1 * v1, u1 * v2, u2 * v1], axis=1)
        exact = terms.astype(np.float64).sum(1)
        np.testing.assert_array_equal(  # a product of two pieces is exact
            terms.astype(np.float64), np.concatenate(
                [u1.astype(np.float64) * v1, u1.astype(np.float64) * v2,
                 u2.astype(np.float64) * v1], axis=1))
        got = _f32_sum(terms, order).astype(np.float64)
        mass = np.abs(terms.astype(np.float64)).sum(1)
        acc = np.abs(got - exact) / mass
        assert acc.max() <= adds * serial._ACC_UNIT
        worst = max(worst, acc.max())
        scale = np.sqrt(_rowdot(u, u) * _rowdot(v, v))
        assert (mass <= (1 + 2.0**-5) * scale).all()
        whole = np.abs(got - _rowdot(u, v)) / scale
        assert whole.max() <= serial._SCREEN_SPLIT + adds * serial._ACC_UNIT * (
            1 + 2.0**-5)
    if order == "chain":  # one sign, one after the other
        assert worst > 4 * 2.0**-24


def test_eps_holds_the_kernels_term():
    """``screen_eps(..., fused=True)``: the same form with the kernel's
    additions, 4 d + 3 where the XLA screen has 2 (d + 4); larger, never
    smaller, and by that term alone."""
    q = jnp.asarray(np.ones((1, 8), np.float32))
    for d in (128, 1536):
        assert serial.screen_additions(d) == 2 * (d + 4)
        assert serial.screen_additions(d, fused=True) == 4 * d + 3
        xla, kernel = (float(serial.screen_eps(
            "l2", d, q, sq_norms(q), jnp.float32(9.0), fused=f)[0])
            for f in (False, True))
        np.testing.assert_allclose(
            kernel - xla, 2 * (2 * d - 5) * 2.0**-23 * (1 + 2.0**-4)
            * np.sqrt(8) * 3, rtol=1e-4)
    k128 = (2.0**-12 + 2.0**-18 + 515 * 2.0**-23) * (1 + 2.0**-4)
    assert 3.2e-4 < k128 < 3.3e-4


def _dist_step_counts():
    reg = obs_metrics.get_registry()
    return {p: reg.counter(obs_metrics.DIST_STEPS, labels={"path": p}).value
            for p in obs_metrics.DIST_PATHS}


def test_planted_near_tie_comes_back_through_the_rescan(monkeypatch):
    """With the KERNEL engaged (an L2 index of fractional rows on the lane
    grid): forty rows planted on a sphere around query row 0 — one real
    distance, apart by roundings alone, so the 10th and the k'-th screen
    values lie inside eps — cannot be certified; the row is flagged,
    ``knn_screen_rows_total{result="flagged"}`` moves, the re-scan answers
    it, and the served answer is the six-pass program's and float64's.
    The steps count under ``path="fused_screen"`` and ``multipass`` stands
    still."""
    cfg = _cfg("l2", query_bucket=Q)
    q_x, _, _, tiles, _, _ = _case("l2")
    x = np.array(tiles).reshape(-1, D)[:N]
    q = np.array(q_x)
    rng = np.random.default_rng(5)
    u = rng.normal(size=(40, D))
    at = rng.choice(N, size=40, replace=False)
    x[at] = (q[0] + 1.5 * u / np.linalg.norm(u, axis=1, keepdims=True)
             ).astype(np.float32)
    assert serial.fused_screen_rule(cfg, Q, C_TILE, D) == Q
    index = build_index(x, cfg)

    def counted():
        reg = obs_metrics.get_registry()
        return [reg.counter(obs_metrics.SCREEN_ROWS,
                            labels={"result": r}).value
                for r in obs_metrics.SCREEN_RESULTS]

    rows, steps = counted(), _dist_step_counts()
    got = query_knn(q, index)
    rows = [b - a for a, b in zip(rows, counted())]
    assert rows[0] + rows[1] == Q and 1 <= rows[1] < Q // 4, rows
    assert np.asarray(got.screen_rows).tolist() == rows
    assert np.asarray(got.select_tiles).tolist() == [0, 1]  # re-scanned
    moved = {p: v - steps[p] for p, v in _dist_step_counts().items()}
    assert moved == {**dict.fromkeys(obs_metrics.DIST_PATHS, 0),
                     "fused_screen": TILES}
    assert np.asarray(got.dist_steps).tolist() == [0] * 6 + [TILES]
    # the six-pass program of the same index: the rule patched off
    monkeypatch.setattr(serial, "screen_rule", lambda *a, **k: None)
    want = query_knn(q, build_index(x, cfg.replace(recall_target=0.96)))
    assert want.screen_rows is None
    assert np.asarray(want.dist_steps).tolist() == [0, TILES]
    np.testing.assert_array_equal(
        np.asarray(got.dists)[0], np.asarray(want.dists)[0])
    assert set(np.asarray(got.ids)[0]) <= set(at.tolist())
    same = (np.asarray(got.ids) == np.asarray(want.ids)).mean()
    assert same > 0.999, same
    real = ((q[:64, None, :].astype(np.float64)
             - x[np.asarray(got.ids)[:64]].astype(np.float64)) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(got.dists)[:64], real, rtol=5e-5)
