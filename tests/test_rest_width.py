"""The width a dense stack rests at (ISSUE 49; ``serve/index.py
rest_width``): a fractional float32 stack at a width off the 128-lane grid
rests zero-padded to it where the certified screen then engages and the
device has the room; ``index.dim`` stays the rows' own. Queries and writes
meet the stack at its width inside their programs, and every answer is
the unpadded rows' (zeros add exact zeros to every dot and norm)."""

import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from mpi_knn_tpu import KNNConfig, build_index, query_knn
from mpi_knn_tpu.backends import serial
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.serve import aotcache, build_index_blocks
from mpi_knn_tpu.serve import index as serve_index
from mpi_knn_tpu.serve.mutate import (
    delete_rows,
    stack_rests_row_major,
    upsert_rows,
)

from scripts.lowered_hashes import V5E_USABLE_BYTES, v5e_free_at_build

_REPO = pathlib.Path(__file__).resolve().parent.parent
RESERVE = serve_index.REST_RESERVE_BYTES


def _cell(name, **knn):
    config = json.loads((_REPO / "benchmark" / "configs" / name).read_text())
    return KNNConfig(**{**config["knn"], **knn}), config


def _free(config, metric="l2"):
    """Bytes free on a v5e while a cell's build asks the rule (the one
    model of the launchers' holdings, ``scripts/lowered_hashes.py``)."""
    return v5e_free_at_build(config["rows"], config["dim"], metric)


_STREAM = "msturing10m-100-l2-stream.json"
_IP = "text2image10m-200-ip.json"


def _stream(**knn):
    cfg, config = _cell(_STREAM, **knn)
    return cfg, config, {"free_bytes": _free(config)}


@pytest.mark.parametrize("why,want,cfg,config,how", [
    # the nine cells' shapes, as their builds meet the rule
    ("allknn-mnist8m: 784 is off the grid, whole numbers rank in one pass",
     784, *_cell("mnist8m-784-l2.json"), {"onepass": True}),
    ("ring4-mnist8m: the same rows (and the ring builds no serial stack)",
     784, *_cell("mnist8m-784-l2-ring4.json"), {"onepass": True}),
    ("serve-bigann10m-small / -bulk: on the grid",
     128, *_cell("bigann10m-128-l2.json"), {"onepass": True}),
    ("the same rows, fractional: on the grid still",
     128, *_cell("bigann10m-128-l2.json"), {}),
    ("serve-dbpedia1m-cos-bulk: on the grid",
     1536, *_cell("dbpedia-openai1m-1536-cos.json"), {}),
    ("serve-bigann100m-u8-bulk: a byte stack, on the grid",
     128, *_cell("bigann100m-128-l2-u8.json"), {"onepass": True}),
    ("serve-yfcc10m-filter-bulk: whole numbers, tags",
     192, *_cell("yfcc10m-192-l2-filter.json"),
     {"onepass": True, "tagged": True}),
    ("the same with fractional rows: a predicate's words ride the scan",
     192, *_cell("yfcc10m-192-l2-filter.json"), {"tagged": True}),
    ("serve-bigann10m-ivf-bulk: another store; its width is on the grid",
     128, *_cell("bigann10m-128-l2-ivf4096.json"), {}),
    ("serve-text2image10m-ip-bulk: no room beside the launcher's array",
     200, *_cell(_IP), {"free_bytes": _free(_cell(_IP)[1], "ip")}),
    ("the same where the backend reports no statistics: room assumed",
     256, *_cell(_IP), {}),
    ("the same built from rows in blocks: nothing beside the stack",
     256, *_cell(_IP), {"free_bytes": V5E_USABLE_BYTES}),
    ("stream-msturing10m-runbook: the caller's array and the centred copy "
     "leave 9.0e9 B, the padded stack, its planes and the reserve want "
     "6.3e9", 128, *_stream()),
    # every condition of the rule, one row each, at the streaming cell
    ("its control: `high` is the program it always was", 100,
     *_stream(matmul_precision="high")),
    ("highest, spelled out", 128, *_stream(matmul_precision="highest")),
    ("default precision: one pass already", 100,
     *_stream(matmul_precision="default")),
    ("whole-number rows: the one-pass fact", 100, *_stream()[:2],
     {"onepass": True}),
    ("tags", 100, *_stream()[:2], {"tagged": True}),
    ("a bf16 stack", 100, *_stream(dtype="bfloat16")),
    ("a 512-row serving tile: under the height the passes pay from", 100,
     *_stream(query_tile=512)),
    ("cosine at d = 100", 128, *_stream(metric="cosine")),
    ("mixed proves nothing and stays as it is", 100,
     *_stream(precision_policy="mixed")),
    ("the stream schedule carries no lists", 100,
     *_stream(merge_schedule="stream")),
    ("narrow corpus tiles: no lists to carry", 100,
     *_stream(corpus_tile=512)),
    ("k' = 3k + 2 past the finish kernel's 128 answers", 100,
     *_stream(k=43)),
    ("a device with 5.0e9 B free: 0.2e9 short of the stack alone", 100,
     *_stream()[:2], {"free_bytes": 5_000_000_000}),
    ("room for the stack by a few bytes and none for the reserve: the "
     "layout does not turn on the allocator's last bytes", 100,
     *_stream()[:2], {"free_bytes": 1224 * 8192 * (4 * 128 + 8) + 4096}),
    ("the stack, its planes and the reserve to the byte", 128,
     *_stream()[:2],
     {"free_bytes": 1224 * 8192 * (4 * 128 + 8) + RESERVE}),
])
def test_rest_width_follows_what_the_build_can_observe(
        why, want, cfg, config, how):
    """The rule as one table: (metric, dim, dtype, one-pass fact, tags,
    ``matmul_precision``, query tile, room) -> the width at rest."""
    c_tile, c_pad = serve_index._serial_tiling(cfg, config["rows"])
    how = {"onepass": False, **how}
    width, short = serve_index.rest_width(
        cfg, config["dim"], c_tile, c_pad, **how)
    assert width == want, why
    # a shortfall (positive) is said only where room ALONE declined, the
    # margin past the reserve (negative or 0) only where room granted
    wide = serve_index.pad_to_multiple(config["dim"], 128)
    roomy = serve_index.rest_width(
        cfg, config["dim"], c_tile, c_pad, **{**how, "free_bytes": None})[0]
    asked = "free_bytes" in how and roomy == wide != config["dim"]
    assert (short > 0) == (asked and width != wide), (why, short)
    planes = 4 if cfg.metric == "ip" else 8
    assert short == (c_pad * (4 * wide + planes) + RESERVE
                     - how["free_bytes"] if asked else 0), (why, short)


def test_the_ip_cell_is_short_by_what_perf_md_says():
    """17.28e9 B wanted (the launcher's 7.58e9 + a stack at 256 columns of
    9.70e9), 16.91e9 usable: short by 0.4e9 of the stack alone, by 1.5e9
    with the reserve — and the streaming cell has 2.7e9 to spare past it."""
    cfg, config = _cell(_IP)
    c_tile, c_pad = serve_index._serial_tiling(cfg, config["rows"])
    _, short = serve_index.rest_width(
        cfg, 200, c_tile, c_pad, onepass=False,
        free_bytes=_free(config, "ip"))
    assert 0.3e9 < short - RESERVE < 0.5e9, short
    cfg, config, how = _stream()
    c_tile, c_pad = serve_index._serial_tiling(cfg, config["rows"])
    width, short = serve_index.rest_width(
        cfg, 100, c_tile, c_pad, onepass=False, **how)
    assert width == 128 and -2.9e9 < short < -2.5e9, short


# ---------------------------------------------------------------------------
# a padded index end to end on the CPU (no statistics: room assumed)

K, Q, C_TILE = 10, 1024, 1024


def _cfg(metric="l2", **kw):
    return KNNConfig(**{**dict(
        k=K, metric=metric, backend="serial", query_tile=Q,
        corpus_tile=C_TILE, exclude_zero=False), **kw})


def _rows(rng, n, d):
    """Fractional float32 rows in classes: near neighbours at close,
    distinct distances."""
    cen = rng.normal(size=(16, d))
    return (cen[rng.integers(0, 16, n)]
            + 0.5 * rng.normal(size=(n, d))).astype(np.float32)


def _assert_exact(got, queries, live: dict, metric="l2", rtol=2e-5):
    """``got`` is the float64 brute-force top-k over ``live`` (id -> row):
    the same distances, and every returned id a live row AT its returned
    distance (so a swap of two near-ties passes and nothing else does)."""
    ids = np.fromiter(live, dtype=np.int64)
    x = np.stack([live[i] for i in ids]).astype(np.float64)
    q = queries.astype(np.float64)
    if metric == "ip":
        d = -(q @ x.T)
    else:
        d = (q * q).sum(1)[:, None] - 2 * q @ x.T + (x * x).sum(1)[None]
    want = np.sort(d, axis=1)[:, :K]
    scale = np.maximum(np.abs(want), (q * q).sum(1)[:, None] * 0.1)
    gd, gi = np.asarray(got.dists), np.asarray(got.ids)
    np.testing.assert_array_less(np.abs(gd - want) / scale, rtol)
    assert np.isin(gi, ids).all()
    at = np.searchsorted(ids, gi, sorter=np.argsort(ids))
    at = np.argsort(ids)[at]
    own = np.take_along_axis(d, at, axis=1)
    np.testing.assert_array_less(np.abs(own - want) / scale, rtol)
    return (np.take_along_axis(ids[None].repeat(len(q), 0),
                               np.argsort(d, axis=1)[:, :K], 1) == gi).mean()


def _padding_is_zero(index):
    assert index.tiles.shape[-1] > index.dim
    assert not np.asarray(index.tiles[..., index.dim:]).any()


def test_padded_l2_index_is_searched_written_and_searched_again():
    """d = 100 with headroom: built (the stack at 128 columns, ``dim``
    100), searched, upserted — new ids AND updates of live ids —, deleted
    from, searched again: every answer the float64 reference's top-10 over
    the rows live then, every padded column zero after the writes, and the
    upsert the one-scatter form the d = 128 indexes take."""
    rng = np.random.default_rng(49)
    d, n = 100, 3 * C_TILE - 50
    x, q = _rows(rng, n, d), _rows(rng, Q, d)
    cfg = _cfg(bucket_headroom=0.3, mutation_bucket=256)
    index = build_index(x, cfg)
    assert index.dim == d and index.tiles.shape == (4, C_TILE, 128)
    assert index.onepass is None and stack_rests_row_major(index)
    _padding_is_zero(index)
    reg = obs_metrics.get_registry()
    assert reg.gauge("serve_index_rest_width").value == 128
    assert reg.gauge("serve_index_rest_bytes_per_row").value == 4 * 128 + 8
    assert aotcache.index_facts(index)["rest_width"] == 128
    assert aotcache.index_facts(index)["dim"] == d
    # the norm plane is the unpadded rows' (zeros add nothing)
    centred = x.astype(np.float64) - np.asarray(index.mu, np.float64)
    np.testing.assert_allclose(
        np.asarray(index.tile_sqs).reshape(-1)[:n], (centred ** 2).sum(1),
        rtol=1e-5)

    live = dict(enumerate(x))
    first = query_knn(q, index)
    assert np.asarray(first.screen_rows).sum() == Q  # the screen engaged
    assert _assert_exact(first, q, live) > 0.999

    fresh = _rows(rng, 300, d)
    fresh[:150] = q[:150] + 0.01 * rng.normal(size=(150, d)).astype(
        np.float32)  # new nearest neighbours of the first query rows
    moved = q[150:250] + 0.01 * rng.normal(size=(100, d)).astype(np.float32)
    upsert_rows(index, np.arange(n, n + 300), fresh)
    upsert_rows(index, np.arange(100), moved)  # updates of live ids
    gone = np.arange(200, 700)
    delete_rows(index, gone)
    live.update(zip(range(n, n + 300), fresh))
    live.update(zip(range(100), moved))
    for i in gone:
        del live[int(i)]
    _padding_is_zero(index)
    again = query_knn(q, index)
    assert _assert_exact(again, q, live) > 0.999
    ids = np.asarray(again.ids)
    assert (ids[:150, 0] == np.arange(n, n + 150)).all()
    assert (ids[150:250, 0] == np.arange(100)).all()
    assert not np.isin(ids, gone).any()
    with pytest.raises(ValueError, match="dim=100"):
        upsert_rows(index, [5], np.zeros((1, 128), np.float32))


def test_padded_inner_product_index():
    """d = 200 under ``metric="ip"``: rests at 256 columns, no norm plane,
    no centring; the answers are the reference's largest inner products."""
    rng = np.random.default_rng(45)
    d, n = 200, 2 * C_TILE + 17
    x, q = _rows(rng, n, d), _rows(rng, Q, d)
    index = build_index(x, _cfg("ip"))
    assert index.dim == d and index.tiles.shape == (3, C_TILE, 256)
    _padding_is_zero(index)
    assert obs_metrics.get_registry().gauge(
        "serve_index_rest_bytes_per_row").value == 4 * 256 + 4
    got = query_knn(q, index)
    assert np.asarray(got.screen_rows).sum() == Q
    assert _assert_exact(got, q, dict(enumerate(x)), "ip") > 0.999


@pytest.mark.parametrize("how", ["device", "blocks"])
def test_every_build_path_rests_the_same_stack(how):
    """A device array (the one program that pads and tiles) and rows in
    blocks rest the stack a host array rests: the same padded tiles (to
    the mean's last bits from blocks), the same answers."""
    rng = np.random.default_rng(7)
    d, n = 100, 2 * C_TILE + 100
    x, q = _rows(rng, n, d), _rows(rng, Q, d)
    host = build_index(x, _cfg())
    if how == "device":
        other = build_index(jnp.asarray(x), _cfg())
    else:
        other = build_index_blocks(
            x.shape, [x[:700], x[700:1900], x[1900:]], _cfg())
    assert other.dim == d and other.tiles.shape == host.tiles.shape
    _padding_is_zero(other)
    np.testing.assert_allclose(
        np.asarray(other.tiles), np.asarray(host.tiles), atol=2e-6)
    np.testing.assert_array_equal(
        np.asarray(query_knn(q, other).ids), np.asarray(query_knn(q, host).ids))


@pytest.mark.parametrize("rows,width,fact", [
    ("whole", 100, True),
    ("fractional", 128, False),
    ("whole, then a fraction in the last block", 100, False),
])
def test_from_blocks_the_first_block_speaks_for_the_width(rows, width, fact):
    """The stack's width is wanted before the first block is in, whether
    the rows are whole numbers known after the last: the first block's
    rows decide. Whole numbers rest at their own width with the one-pass
    fact, the layout ``build_index`` gives the same array; fractional rows
    rest padded; a fraction in a LATER block leaves the stack at the rows'
    width without the fact (the unscreened program). The answers are the
    reference's in each."""
    rng = np.random.default_rng(12)
    d, n = 100, 2 * C_TILE + 100
    x, q = _rows(rng, n, d), _rows(rng, Q, d)
    if rows != "fractional":
        x, q = np.rint(4 * x), np.rint(4 * q)
    if rows.endswith("last block"):
        x[-1, 7] += 0.5
    index = build_index_blocks(
        x.shape, [x[:700], x[700:1900], x[1900:]], _cfg())
    assert index.dim == d and index.tiles.shape == (3, C_TILE, width)
    assert (index.onepass is not None) == fact
    if not rows.endswith("last block"):
        assert build_index(x, _cfg()).tiles.shape == index.tiles.shape
    got = query_knn(q, index)
    screened = got.screen_rows is not None and np.asarray(
        got.screen_rows).sum() > 0
    assert screened == (width == 128)
    # (whole-number rows tie at whole distances: ids may swap there, the
    # distances and each id's own distance may not)
    same = _assert_exact(got, q, dict(enumerate(x)))
    assert same > (0.999 if rows == "fractional" else 0.9)


def test_where_the_rule_declines_the_stack_is_the_parents(monkeypatch):
    """The control's configuration (``high``), whole-number rows, and a
    device too full each build the (T, c, 100) stack, and the gauge and
    the log say which."""
    rng = np.random.default_rng(3)
    x = _rows(rng, 2 * C_TILE, 100)
    reg = obs_metrics.get_registry()
    assert build_index(x, _cfg(matmul_precision="high")).tiles.shape[-1] == 100
    assert reg.gauge("serve_index_rest_width").value == 100
    whole = build_index(np.rint(4 * x), _cfg())
    assert whole.tiles.shape[-1] == 100 and whole.onepass is not None
    monkeypatch.setattr(serve_index, "device_free_bytes", lambda corpus: 10)
    said = []
    monkeypatch.setattr(serve_index.log, "info",
                        lambda msg, *a: said.append(msg % a))
    assert build_index(x, _cfg()).tiles.shape[-1] == 100
    assert reg.gauge("serve_index_rest_width").value == 100
    want = 2 * C_TILE * (4 * 128 + 8) + RESERVE - 10
    assert any(f"want {want} bytes more" in line for line in said), said
    # ... and a grant says its margin past the reserve
    monkeypatch.setattr(serve_index, "device_free_bytes",
                        lambda corpus: want + 10 + 77)
    assert build_index(x, _cfg()).tiles.shape[-1] == 128
    assert any("77 bytes to spare" in line for line in said), said


def test_tags_are_refused_on_a_padded_stack():
    """The tagged gather reads the stack at ``index.dim``:
    ``build_index(tags=)`` rests a tagged stack at its rows' width, and
    tags handed to ``build_tag_index`` over a padded one are refused."""
    from mpi_knn_tpu.serve.tags import build_tag_index

    rng = np.random.default_rng(5)
    x = _rows(rng, 2 * C_TILE, 100)
    bags = (np.arange(len(x) + 1), np.arange(len(x)) % 3)  # one tag a row
    tagged = build_index(x, _cfg(max_query_tags=2), tags=bags)
    assert tagged.tiles.shape[-1] == 100 and tagged.tags is not None
    with pytest.raises(ValueError, match="rests zero-padded at 128"):
        build_tag_index(build_index(x, _cfg(max_query_tags=2)), bags)


def test_a_near_tie_inside_eps_comes_back_through_the_rescan():
    """Planted: 40 rows on a shell around one query row, their squared
    distances 2e-3 apart — the 10th and the 32nd lie 0.044 apart, inside
    that row's ``screen_eps`` — so the certificate cannot vouch for it:
    the row is flagged (``knn_screen_rows_total{result="flagged"}``), the
    query tile re-scanned
    (``knn_select_query_tiles_total{path="rescanned"}``), and the answer
    is the exact one, id for id."""
    rng = np.random.default_rng(11)
    d, n = 100, 2 * C_TILE
    x = rng.normal(size=(n, d)).astype(np.float32)
    q = rng.normal(size=(Q, d)).astype(np.float32)
    u = rng.normal(size=(40, d))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    at = rng.choice(n, 40, replace=False)
    x[at] = q[3] + (u * np.sqrt(1 + 2e-3 * np.arange(40))[:, None]).astype(
        np.float32)
    index = build_index(x, _cfg(center=False))
    assert index.tiles.shape[-1] == 128
    eps = np.asarray(serial.screen_eps(
        "l2", 128, jnp.asarray(q[3:4]), None,
        serial.largest_norm_sq("l2", index.tiles, index.tile_sqs)))
    assert 22 * 2e-3 < eps[0]  # the plant is inside the bound

    def counted():
        reg = obs_metrics.get_registry()
        return (reg.counter(obs_metrics.SCREEN_ROWS,
                            labels={"result": "flagged"}).value,
                reg.counter(obs_metrics.SELECT_TILES,
                            labels={"path": "rescanned"}).value)

    before = counted()
    got = query_knn(q, index)
    flagged, rescanned = (b - a for a, b in zip(before, counted()))
    assert flagged > 0 and rescanned > 0
    assert np.asarray(got.screen_rows)[1] == flagged
    np.testing.assert_array_equal(np.asarray(got.ids)[3], at[:K])
    assert _assert_exact(got, q, dict(enumerate(x))) > 0.999
