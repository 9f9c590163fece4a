"""The programs that hold Mosaic kernels, compiled here for a described
v5e (no chip attached): the serial, ring, cosine, mutation and bucket-major
programs at the cells' shapes, and how a tile stack rests. On the CPU the
same kernel bodies run in interpreter mode in the other test files."""

import numpy as np
import pytest


@pytest.mark.parametrize("backend", ["ring-overlap", "ring"])
def test_ring_program_with_lane_bin_kernels_compiles_for_four_v5e(
        backend, monkeypatch):
    """The deployment ``mnist8m-784-l2-ring4`` at its tile shape (4096 x
    8192 x 784, ``high``), two tiles a chip: the lane-bin kernels are typed
    for the XLA ring's ``shard_map``, whose varying-axes check stays on
    (the CPU mesh cannot run them there: ``ops/topk.py``), Mosaic compiles
    them inside the ring's program, and the travelling block is laid out
    once, ahead of the rounds, not copied in each of them (PERF.md §6,
    PR 28: at 1 048 576 rows a chip that copy did not fit)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.backends import ring
    from tests.conftest import TPU_MODE

    if TPU_MODE:
        pytest.skip("needs four chips; chip_smoke.py runs the ring there")
    try:
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 — no compile-only TPU client here
        pytest.skip(f"no compile-only TPU topology: {e}")
    # the kernels and the selection ask the backend whether to interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    cfg = KNNConfig(
        k=10, backend=backend, num_devices=4, matmul_precision="high",
        query_tile=4096, corpus_tile=8192, merge_schedule="twolevel")
    mesh = Mesh(np.asarray(devices), (cfg.mesh_axis,))
    by_rows = NamedSharding(mesh, P(cfg.mesh_axis))
    tiles, dim = 2, 784
    m, nq = 4 * tiles * cfg.corpus_tile, 4 * cfg.query_tile

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=by_rows)

    with jax.enable_x64(False):
        lowered = ring._ring_knn_sharded.lower(
            arg((nq, dim), jnp.float32), arg((nq,), jnp.int32),
            arg((m, dim), jnp.float32), arg((m,), jnp.int32),
            cfg, backend == "ring-overlap", mesh, cfg.mesh_axis,
            cfg.query_tile, cfg.corpus_tile)
        text = lowered.as_text()
        assert "tpu_custom_call" in text  # bins and finish, under shard_map
        assert ring.PERMUTE_SCOPE in lowered.as_text(debug_info=True)
        hlo = lowered.compile().as_text()
    # what travels is the stack of tiles, so no round lays the block out anew
    permutes = [ln for ln in hlo.splitlines()
                if " collective-permute-start(" in ln and "f32[" in ln]
    assert permutes and all(
        f"f32[{tiles},{cfg.corpus_tile},{dim}]" in ln for ln in permutes)


# ---------------------------------------------------------------------------
# the one-pass rule's programs at the cells' sizes, compiled for the v5e
# (PR 29): which dot each branch holds, and what the branch costs in memory


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    from tests.conftest import TPU_MODE

    if TPU_MODE:
        pytest.skip("compile-only checks; the cells run these on the chip")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 — no compile-only TPU client here
        pytest.skip(f"no compile-only TPU topology: {e}")


def _dist_dots(hlo: str) -> dict:
    """The distance dots of a compiled program by their branch's scope
    under ``knn.dist`` (``knn.dist_<branch>``; "" for none) -> (operand
    element types, operand_precision or None)."""
    import re

    fusions = dict(re.findall(
        r"^\s*%(\S+) = (\w+)\[[^\n]*? fusion\(", hlo, flags=re.M))
    params = dict(re.findall(
        r"^\s*%(\S+) = (\w+)\[[^\n]*? parameter\(", hlo, flags=re.M))
    dots = {}
    for ln in hlo.splitlines():
        m = re.search(r" convolution\(%(\S+), %(\S+)\).*?(?:operand_precision"
                      r"=\{(\w+),\w+\})?, metadata=\{op_name=\"[^\"]*"
                      r"knn\.dist/(?:knn\.dist_(\w+)/)?dot_general", ln)
        if m:
            kinds = tuple(fusions.get(o) or params.get(o) for o in m.group(1, 2))
            dots[m.group(4) or ""] = (kinds, m.group(3))
    return dots


def _assert_the_lists_ride_the_scan(hlo: str, q: int, tiles: int,
                                    k: int = 10):
    """An engaged program as the v5e compiler leaves it (ISSUE 33): *bins*
    updates the (q, 640) lists in place (``k``: what the lists answer for
    — k' under the certified screen, ISSUE 47, whose lists are 896 wide) — its outputs alias its list
    operands and nothing copies a list, not the scan and not the one-pass
    rule's conditional, which takes and returns them (a copy a step is 21
    MB read and written again at 4096 rows: half of what the change wins)
    — no (T, q, k) stack of survivors is left, and no loop asks for the
    tile stack in another layout (the re-scan once did: a copy of the
    whole stack, 4.6 GiB at d = 784). Where the row bound rides the scan
    (ISSUE 35: the 1024-row programs) *bins* takes it as a third operand
    ahead of the lists, and the finish kernel appears a second time — over
    the lists' first 128 columns, scope ``bound`` — in the conditional that
    takes the bound anew at a few of the scan's steps."""
    import re

    from mpi_knn_tpu.ops.topk import lane_bin_bound_rides, lane_bin_depth

    bounded = lane_bin_bound_rides(q, 8192)
    lists = rf"\[{q},{128 * lane_bin_depth(q, 8192, k)}\]"
    lines = hlo.splitlines()
    copies = [ln for ln in lines
              if re.search(rf"= [fs]32{lists}\S* copy\(", ln)]
    assert not copies, copies
    # (the kernel that walks the whole stack, ISSUE 37, makes the lists:
    # it takes none)
    bins = [ln for ln in lines if "tpu_custom_call" in ln
            and "knn.fused" not in ln
            and re.search(rf"= \(f32{lists}\S*, s32{lists}", ln)]
    first = 3 if bounded else 2  # after ids, the tile (and the bound)
    assert bins and all(
        f"output_to_operand_aliasing={{{{0}}: ({first}, {{}}), "
        f"{{1}}: ({first + 1}, {{}})}}" in ln for ln in bins), bins
    finishes = [ln.split()[0] for ln in lines if "tpu_custom_call" in ln
                and re.search(rf"= \(f32\[{q},{k}\]", ln)]
    assert sorted(name.rstrip(".0123456789") for name in finishes) == (
        ["%bound", "%finish"] if bounded else ["%finish"]), finishes
    assert f"[{tiles},{q},10]" not in hlo
    # (the ring's rounds copy the travelling block in the layout it has,
    # rows minor at d = 784, as they always did: PERF.md §5)
    stack_copies = [ln for ln in lines if re.search(
        rf"= f32\[{tiles},8192,\d+\]\{{2,1,0\S* copy\(", ln)]
    assert not stack_copies, stack_copies


def _assert_one_kernel_walks_the_stack(
        hlo: str, q: int, tiles: int, dim: int,
        under: str = r"jit\(knn_chunk_update\)/while/body/closed_call"):
    """A program whose one-pass branch is the fused scan (ISSUE 37), as
    the v5e compiler leaves it: the one-pass rule's conditional sits ONCE,
    outside any loop over the corpus tiles (the only loop above it is the
    map over query tiles), its engaged branch is one Mosaic call that
    takes the stack and the id and norm planes as they rest — no
    ``dynamic-slice`` and no copy of a tile, no (q, 8192) float32 distance
    tile, no loop — and the other branch is the scan of multi-pass steps
    with *bins* inside it. A width off the lane grid (ISSUE 40: d = 784)
    rests as (tiles, dim, 8192), rows minor, and the kernel takes that
    shape: the ``swapaxes`` in front of it is a BITCAST of the branch's
    parameter, the bytes at rest under another name — no ``copy`` and no
    ``transpose`` of the 4.59 GiB stack anywhere in the branch. ``under``:
    the scopes above the conditional (a ring's round holds it too)."""
    import re

    blocks = re.split(r"\n(?=(?:ENTRY )?%\S+ \([^\n]*\) -> [^\n]* \{\n)",
                      hlo)
    calls = [ln for ln in hlo.splitlines()
             if "tpu_custom_call" in ln and "knn.fused" in ln]
    assert len(calls) == 1, calls
    op_name = re.search(r'op_name="([^"]*)"', calls[0]).group(1)
    assert re.fullmatch(
        under + r"/cond/branch_1_fun/knn\.fused/pallas_call", op_name), op_name
    branch, = [b for b in blocks if calls[0] in b]
    assert f"f32[{tiles},8192,{dim}]" in branch.splitlines()[0]
    operands = re.search(r"custom-call\(([^)]*)\)", calls[0]).group(1)
    stack = re.sub(r"/\*[^*]*\*/", "", operands).split(", ")[4]
    if dim % 128:
        view = re.search(
            rf"{re.escape(stack)} = f32\[{tiles},{dim},8192\]\{{2,1,0\S* "
            r"bitcast\((%\S+)\)", branch)
        assert view, stack
        stack = view.group(1)
    assert re.search(
        rf"{re.escape(stack)} = f32\[{tiles},8192,{dim}\]\S* "
        r"get-tuple-element\(", branch), stack
    # (the prefetched refresh flags, a few hundred bytes, come by an
    # asynchronous copy: ``copy-start``)
    for gone in ("dynamic-slice", " while(", f"f32[{q},8192]", " copy(",
                 " transpose("):
        assert gone not in branch, gone
    # the other branch: the scan, its steps' dot at the configured
    # precision and *bins*
    bins = [ln for ln in hlo.splitlines()
            if "tpu_custom_call" in ln and "/bins/" in ln]
    assert bins and all(
        "cond/branch_0_fun/while/body" in ln for ln in bins), bins


@pytest.mark.parametrize("d", [8, 64, 100, 104, 128, 192, 200, 256, 784,
                               1000, 1536])
def test_the_rest_layout_of_a_stack_follows_its_shape_alone(v5e_devices, d):
    """What ``ops/topk.py fused_scan_engages`` says of a stack at rest,
    READ in programs compiled for the v5e: a float32 (T, c, d) parameter's
    layout is the same whatever the program does with it (its first
    element; rows gathered out of it; the kernel's view of it) and, where
    d is a multiple of 8, whatever the tile count — 1, 3, a prime, a
    multiple of 128, the cells' 768 and 1221: row-major on the lane grid,
    (T, d, c) off it, where ``swapaxes(1, 2)`` is then a bitcast. At a
    width the sublane grid refuses (100) the order follows the tile count
    and the rule keeps out."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu.ops.fused_scan import rests_rows_minor

    one = SingleDeviceSharding(v5e_devices[0])
    uses = {
        "first": lambda x: x[0, 0, 0],
        "gather": lambda x: x.reshape(-1, d)[jnp.arange(64) * 7].sum(0),
        "view": lambda x: jnp.swapaxes(x, 1, 2)[:, :, :128].sum(),
    }

    def layout(tiles, c, use):
        hlo = jax.jit(uses[use]).lower(jax.ShapeDtypeStruct(
            (tiles, c, d), jnp.float32, sharding=one)).compile().as_text()
        return re.search(
            rf"f32\[{tiles},{c},{d}\]\{{([\d,]+:T\([\d,]+\))\}} parameter\(0\)",
            hlo).group(1)

    read = {(tiles, c): layout(tiles, c, "first")
            for c in (1024, 8192)
            for tiles in (1, 3, 127, 128, 192, 768, 1157, 1221, 1224)
            if tiles * c * d * 4 < 12e9}  # what a 16 GB chip can hold
    assert len(read) >= 12
    # the shapes ``serve/index.py rest_width`` pads to (ISSUE 49): the
    # streaming cell's stack at 128 columns, the inner-product cell's at 256
    for width, tiles in ((128, 1224), (256, 1157)):
        assert d != width or (tiles, 8192) in read
    for (tiles, c) in ((3, 1024), (128, 8192)):
        for use in ("gather", "view"):
            assert layout(tiles, c, use) == read[tiles, c], (tiles, c, use)
    if d % 8 == 0:
        rest = "1,2,0" if rests_rows_minor(d) else "2,1,0"
        assert set(read.values()) == {rest + ":T(8,128)"}, read
    else:
        assert read[3, 8192] == "1,2,0:T(8,128)", read
        assert read[1224, 8192] == "1,0,2:T(8,128)", read


@pytest.mark.parametrize("cell,q,tiles,dim,precision,temp_gib", [
    # allknn-mnist8m: 13.85 GiB of 15.75 at the peak leaves no room for the
    # bf16 copy of the stack (2.30 GiB) that XLA hoists out of the scan when
    # the conditional sits around the whole scan, for the `default` path's
    # 3.19 GiB, or for the float32 copy (4.6 GiB) it makes for a second loop
    # over the stack: since ISSUE 33 the program needs 0.19 GiB (the
    # survivor stack and the cascade's sort scratch, 1.04 GiB, are gone);
    # since ISSUE 40 its one-pass branch is the kernel that walks the
    # stack, four 1024-row blocks over the rows-minor tiles, and the
    # program needs 0.15 GiB: no more than the parent's 0.19
    ("allknn-mnist8m", 4096, 192, 784, "high", 0.19),
    # serve-bigann10m-bulk: one 1024-row bucket over the resident stack
    # (0.03 GiB; 1.2 with the survivor stack)
    ("serve-bigann10m-bulk", 1024, 1221, 128, "highest", 0.1),
    # stream-msturing10m-runbook (ISSUE 34): the same bucket over 1224
    # tiles (24 of them headroom) of a width off the lane grid, d = 100
    ("stream-msturing10m-runbook", 1024, 1224, 100, "highest", 0.1),
    # shapes no cell runs, which the rule admits all the same (ISSUE 40):
    # the filtered cell's stack (768 tiles, a multiple of 128, of d = 192)
    # under a 1024-row bucket WITHOUT a predicate, and the all-kNN tile
    # over 256 tiles — the view is the bitcast at these tile counts too
    ("yfcc10m-no-predicate", 1024, 768, 192, "highest", 0.1),
    ("mnist-256-tiles", 4096, 256, 784, "high", 0.19),
])
def test_serial_program_under_the_one_pass_rule_compiles_for_the_v5e(
        v5e_devices, monkeypatch, cell, q, tiles, dim, precision, temp_gib):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.backends import serial

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_devices[0])
    cfg = KNNConfig(k=10, backend="serial", matmul_precision=precision,
                    query_tile=q, corpus_tile=8192)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (arg((1, q, dim), jnp.float32), arg((1, q), jnp.int32),
            arg((tiles, 8192, dim), jnp.float32), arg((tiles, 8192), jnp.int32),
            arg((1, q, 10), jnp.float32), arg((1, q, 10), jnp.int32))
    with jax.enable_x64(False):
        plain = serial.knn_chunk_update.lower(*args, cfg).compile()
        ruled = serial.knn_chunk_update.lower(
            *args, cfg, arg((), jnp.bool_)).compile()
    # the program without the branch: one dot, plain scope, at the
    # configured precision — or, where the certified screen engages
    # (ISSUE 47: float32 at ``highest``, 1024 rows, d % 128 == 0: what a
    # bigann-shaped corpus of FRACTIONAL rows would run), NO XLA dot in
    # the scan: under L2 on the lane grid the screened scan is the kernel
    # in its three-pass form (ISSUE 51), lists that answer for k' = 32
    screen = serial.screen_rule(cfg, q, 8192, dim)
    assert screen == (32 if cell == "serve-bigann10m-bulk" else None)
    in_kernel = serial.fused_screen_rule(cfg, q, 8192, dim)
    assert in_kernel == (1024 if screen else None)
    assert _dist_dots(plain.as_text()) == (
        {} if in_kernel else {"": (("f32", "f32"), precision)})
    # under the rule: the engaged branch holds ONE bf16 x bf16 -> f32 dot
    # (no operand_precision: a DEFAULT dot), the other the configured one;
    # where one kernel walks the stack (ISSUE 37: the bulk cell's shape;
    # ISSUE 40: the all-kNN cell's, in 1024-row blocks) the engaged
    # branch's dot is inside it
    fused = serial.fused_rule(cfg, q, 8192, dim)
    assert fused == (None if dim == 100 else 1024)
    assert _dist_dots(ruled.as_text()) == {
        **({} if fused else {"onepass": (("bf16", "bf16"), None)}),
        "multipass": (("f32", "f32"), precision),
    }
    if fused:
        _assert_one_kernel_walks_the_stack(ruled.as_text(), q, tiles, dim)
    else:
        assert "knn.fused" not in ruled.as_text()
    temps = [c.memory_analysis().temp_size_in_bytes / 2**30
             for c in (plain, ruled)]
    assert temps[1] <= temps[0] + 0.1 and temps[1] <= temp_gib, (cell, temps)
    if in_kernel:  # the kernel makes the lists: no *bins*, no bound call
        calls = sorted(ln.split()[0].rstrip(".0123456789")
                       for ln in plain.as_text().splitlines()
                       if "tpu_custom_call" in ln)
        assert calls == ["%finish", "%knn.fused"], calls
    else:
        _assert_the_lists_ride_the_scan(plain.as_text(), q, tiles)
    _assert_the_lists_ride_the_scan(ruled.as_text(), q, tiles)


def test_search_over_a_prepared_stack_compiles_for_the_v5e(
        v5e_devices, monkeypatch):
    """``allknn-mnist8m`` since ISSUE 31: the per-call program takes the
    stack's norms as an input (``serial._search_stack``: ``serve_chunk``
    under a jit, the query side's tiling inside it) and needs no more
    scratch than ``knn_chunk_update``, which computes them; the norms' own program, run once a corpus, squares no
    copy of the 4.6 GiB stack."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.backends import serial

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_devices[0])
    q, tiles, dim = 4096, 192, 784
    cfg = KNNConfig(k=10, backend="serial", matmul_precision="high",
                    query_tile=q, corpus_tile=8192)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    queries = (arg((1, q, dim), jnp.float32), arg((1, q), jnp.int32))
    stack = (arg((tiles, 8192, dim), jnp.float32),
             arg((tiles, 8192), jnp.int32))
    carry = (arg((1, q, 10), jnp.float32), arg((1, q, 10), jnp.int32))
    fact = arg((), jnp.bool_)
    with jax.enable_x64(False):
        per_call = serial.knn_chunk_update.lower(
            *queries, *stack, *carry, cfg, fact).compile()
        search = serial._search_stack.lower(
            arg((q, dim), jnp.float32), arg((q,), jnp.int32), *stack,
            arg((tiles, 8192), jnp.float32), fact, cfg=cfg, q_tile=q,
        ).compile()
        norms = serial._stack_norms.lower(stack[0], "l2").compile()
    assert _dist_dots(search.as_text()) == _dist_dots(per_call.as_text())
    gib = [c.memory_analysis().temp_size_in_bytes / 2**30
           for c in (per_call, search, norms)]
    assert gib[1] <= gib[0] + 0.01 and gib[2] <= 0.1, gib


def test_cosine_batch_program_compiles_for_the_v5e(v5e_devices, monkeypatch):
    """``serve-dbpedia1m-cos-bulk`` at its size (ISSUE 32): one 1024-row
    bucket over 123 resident tiles of 8192 x 1536 float32 and their inverse
    norms. The tile step holds ONE dot, float32 under ``knn.dist_cosine``,
    fed by the stack's slice itself — no divide, no root and no second
    8192 x 1536 tile anywhere in the scan; the query side's normalisation
    is ``knn.qunit``'s, outside it. The program's scratch stays under 0.22
    GiB beside the 5.77 GiB stack, and the norms' own program, run once an
    index, copies none of it.

    Since ISSUE 47 the program SCREENS: the scan's dot is ``high`` (three
    passes), its lists answer for k' = 32, the candidates' rows are
    gathered under ``knn.rerank`` from the stack viewed flat — a bitcast:
    NOTHING copies or transposes the stack, and the 201 MB of gathered
    rows are the scratch's largest part — and the re-scan's dot, the way
    out of both certificates, is the configured ``highest``."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.backends import serial

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_devices[0])
    q, tiles, dim = 1024, 123, 1536
    cfg = KNNConfig(k=10, backend="serial", metric="cosine", query_tile=q,
                    corpus_tile=8192, exclude_zero=False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    stack = arg((tiles, 8192, dim), jnp.float32)
    with jax.enable_x64(False):
        batch = jax.jit(
            serial.serve_chunk, static_argnames=("cfg",), donate_argnums=(2, 3)
        ).lower(
            arg((1, q, dim), jnp.float32), arg((1, q), jnp.int32),
            arg((1, q, 10), jnp.float32), arg((1, q, 10), jnp.int32),
            stack, arg((tiles, 8192), jnp.int32),
            arg((tiles, 8192), jnp.float32), None, cfg=cfg,
        ).compile()
        norms = serial._stack_norms.lower(stack, "cosine").compile()
    hlo = batch.as_text()
    assert serial.screen_rule(cfg, q, 8192, dim) == 32
    assert _dist_dots(hlo) == {"cosine": (("f32", "f32"), "high")}
    dots = re.findall(r"operand_precision=\{(\w+),\w+\}[^\n]*?op_name=\"([^\"]*)"
                      r"/dot_general\"", hlo)
    assert sorted((p, "fallback" in name) for p, name in dots) == [
        ("high", False), ("highest", True)], dots
    assert not _stack_moves(hlo, tiles, dim)
    gathered = [ln for ln in hlo.splitlines()
                if re.search(rf"= f32\[{q},32,{dim}\]\S* gather\(", ln)]
    assert len(gathered) == 1 and "knn.rerank" in gathered[0], gathered
    assert re.search(rf"f32\[{tiles * 8192},{dim}\]\{{1,0\S* parameter\(0\)",
                     hlo)  # the gather's source: the flat view
    _assert_the_lists_ride_the_scan(hlo, q, tiles, 32)
    normalising = [ln for ln in hlo.splitlines()
                   if re.search(r" (divide|sqrt|rsqrt)\(", ln)]
    # (and, once a batch, the root of the query rows' own norms in the
    # screen's bound: (1024,) values)
    assert normalising and all(
        "knn.qunit" in ln or "knn.select/screen" in ln for ln in normalising)
    # nothing computes a corpus tile: the only instructions of that shape
    # slice the stack for the dot (a fusion inside the dot's own fusion)
    made = {m.group(1) for m in re.finditer(
        rf"= f32\[8192,{dim}\]\S* ([a-z-]+)\(", hlo)}
    assert made <= {"parameter", "dynamic-slice", "bitcast", "fusion"}, made
    # (0.196 GiB: the gathered rows' 0.1875 and the lists; 0.03 before)
    assert batch.memory_analysis().temp_size_in_bytes <= 0.22 * 2**30
    assert norms.memory_analysis().temp_size_in_bytes <= 0.1 * 2**30


def _stack_moves(hlo: str, tiles: int, dim: int) -> list:
    """The instructions of a compiled program that copy or transpose a
    whole (tiles, 8192, dim) float32 stack, in either of its shapes."""
    import re

    return [ln.split(" = ")[0].strip() for ln in hlo.splitlines()
            if re.search(rf"= f32\[{tiles},(8192,{dim}|{dim},8192)\]\S* "
                         r"(copy|transpose)\(", ln)]


@pytest.mark.parametrize("program", [
    "ring-overlap", "ring", "dp-by-ring", "carry-in"])
def test_ring_program_under_the_one_pass_rule_compiles_for_four_v5e(
        v5e_devices, monkeypatch, program):
    """``ring4-mnist8m`` at its size, 128 tiles a chip (``ring-overlap``:
    the cell's program): both branches inside the ring's ``shard_map``
    (``check_vma`` on), the fact replicated. Since ISSUE 43 the one-pass
    branch of a round's merge is the kernel that walks the ARRIVING stack
    (``knn.fused``, typed under the check): no *bins* call, no slice and no
    (4096, 8192) distance tile in it, the travelling block read where it
    lands — it rests rows minor at d = 784, so the kernel's view of it is
    a bitcast and a round copies the block no more often than the program
    without the rule does; the wire is the float32 tile stack it was, the
    multi-pass branch the scan it was, and the temporaries what they were
    (12.99 GiB of 15.75 at the peak on the chip: one bf16 copy of the
    travelling block, 1.53 GiB, would still fit; the program asks for
    none). The other forms of the ring family must still compile at that
    size: the blocking schedule (the kernel too), the dp x ring mesh (two
    varying axes), and the serving program that threads ``carry_in`` and
    is handed no fact (the scan stays)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.backends import ring

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    backend = "ring" if program == "ring" else "ring-overlap"
    cfg = KNNConfig(
        k=10, backend=backend, num_devices=4, matmul_precision="high",
        query_tile=4096, corpus_tile=8192, merge_schedule="twolevel")
    tiles, dim = 128, 784
    if program == "dp-by-ring":
        mesh = Mesh(np.asarray(v5e_devices).reshape(2, 2),
                    ("dp", cfg.mesh_axis))
        q_axis, ring_n = "dp", 2
    else:
        mesh = Mesh(np.asarray(v5e_devices), (cfg.mesh_axis,))
        q_axis, ring_n = None, 4
    by_rows = NamedSharding(mesh, P(cfg.mesh_axis))
    q_rows = NamedSharding(mesh, ring._query_spec(q_axis, cfg.mesh_axis))
    m, nq = ring_n * tiles * cfg.corpus_tile, 4 * cfg.query_tile

    def arg(shape, dtype, sharding=by_rows):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    queries = (arg((nq, dim), jnp.float32, q_rows),
               arg((nq,), jnp.int32, q_rows))
    corpus = (arg((m, dim), jnp.float32), arg((m,), jnp.int32))
    static = (cfg, backend == "ring-overlap", mesh, cfg.mesh_axis,
              cfg.query_tile, cfg.corpus_tile)
    fact = arg((), jnp.bool_, NamedSharding(mesh, P()))
    wire = f"f32[{tiles},{cfg.corpus_tile},{dim}]"

    def permutes(hlo):
        return [ln for ln in hlo.splitlines()
                if " collective-permute-start(" in ln
                and f"[{tiles},{cfg.corpus_tile},{dim}]" in ln]

    with jax.enable_x64(False):
        if program == "carry-in":
            served = jax.jit(
                ring.ring_serve_sharded,
                static_argnames=("cfg", "overlap", "mesh", "axis", "q_tile",
                                 "c_tile", "q_axis"),
            ).lower(
                *queries, arg((nq, 10), jnp.float32, q_rows),
                arg((nq, 10), jnp.int32, q_rows), *corpus, None, *static,
            ).compile().as_text()
            assert "knn.fused" not in served
            assert permutes(served) and all(
                wire in ln for ln in permutes(served))
            return
        ruled = ring._ring_knn_sharded.lower(
            *queries, *corpus, *static, q_axis=q_axis, onepass=fact).compile()
        if program == "ring-overlap":
            plain = ring._ring_knn_sharded.lower(
                *queries, *corpus, *static).compile()
    hlo = ruled.as_text()
    # the one-pass dot is inside the kernel; the other branch's is the
    # configured one
    assert _dist_dots(hlo) == {"multipass": (("f32", "f32"), "high")}
    _assert_one_kernel_walks_the_stack(
        hlo, cfg.query_tile, tiles, dim,
        under=r"jit\(_ring_knn_sharded\)/shard_map/while/body/closed_call/"
        r"knn\.ring/round/while/body/closed_call")
    # the wire is what it was: float32 tile stacks travel
    assert permutes(hlo) and all(wire in ln for ln in permutes(hlo))
    assert ruled.memory_analysis().temp_size_in_bytes / 2**30 <= 7.0
    if program != "ring-overlap":
        return
    # the cell's program beside the one without the rule (the parent's
    # rounds): a round moves the block no more often, and the scan that
    # stays is the scan it was
    assert len(_stack_moves(hlo, tiles, dim)) <= len(
        _stack_moves(plain.as_text(), tiles, dim)), _stack_moves(
        hlo, tiles, dim)
    temps = [c.memory_analysis().temp_size_in_bytes / 2**30
             for c in (plain, ruled)]
    assert temps[1] <= temps[0] + 0.1, temps
    for compiled in (plain, ruled):
        _assert_the_lists_ride_the_scan(
            compiled.as_text(), cfg.query_tile, tiles)


# ---------------------------------------------------------------------------
# the mutation scatters at the streaming cell's size, compiled for the v5e
# (ISSUE 34): the stack is aliased, not copied


@pytest.mark.parametrize("kind", ["upsert", "delete"])
def test_mutation_scatter_aliases_the_stack_on_the_v5e(v5e_devices, kind):
    """``stream-msturing10m-runbook``: a 1024-row chunk into 1224 tiles of
    8192 x 100 float32 (4.78 GiB as the chip lays it out, 128 lanes a
    row). Every donated store array comes back as the same buffer
    (``input_output_alias``), the program's temporaries are the chunk's
    and one tile's (no corpus-sized one), and its scatters carry
    ``knn.mutate/<kind>``. The device keeps this stack rows-minor
    (``{1,0,2}``: no lane padding, 4.01 GB), where the one-scatter form
    compiles to a copy of the stack out and back (5.13 GB of temporaries):
    the upsert goes tile by tile (``mutate.scatter_rows_by_tile``)."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.serve import mutate

    one = SingleDeviceSharding(v5e_devices[0])
    tiles, c_tile, dim, chunk = 1224, 8192, 100, 1024
    cfg = KNNConfig(k=10, backend="serial", corpus_tile=c_tile)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ids_plane = arg((tiles, c_tile), jnp.int32)
    vec = arg((chunk,), jnp.int32)
    with jax.enable_x64(False):
        if kind == "upsert":
            program = mutate.serial_upsert_jit.lower(
                arg((chunk, dim), jnp.float32), vec, vec, vec, vec, vec,
                arg((tiles, c_tile, dim), jnp.float32), ids_plane,
                arg((tiles, c_tile), jnp.float32), cfg=cfg, by_tile=True,
            ).compile()
            donated = 3
        else:
            program = mutate.serial_delete_jit.lower(
                vec, vec, ids_plane).compile()
            donated = 1
    hlo = program.as_text()
    alias = re.search(r"input_output_alias=\{([^\n]*?)\}, entry", hlo)
    assert alias and alias.group(1).count("may-alias") + alias.group(
        1).count("must-alias") == donated, hlo[:400]
    mem = program.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= (
        tiles * c_tile * 4 * (1 + (dim + 1) * (kind == "upsert")))
    if kind == "upsert":
        assert "f32[1224,8192,100]{1,0,2:" in hlo  # the rest layout
    assert f"knn.mutate/{kind}" in hlo
    # no copy of a store-sized array anywhere in the program
    assert not re.search(rf"= \w+\[{tiles},{c_tile}[\],][^\n]* copy\(", hlo)


def test_stack_with_headroom_builds_on_the_v5e_without_a_second_copy(
        v5e_devices):
    """``stream-msturing10m-runbook``'s build: 9 830 400 x 100 rows into
    1224 tiles (24 of headroom). The device keeps the two shapes in
    different layouts (``{0,1}`` and ``{1,0,2}``), so a pad + reshape is a
    padded copy AND its re-layout (8.3 GB of temporaries; done eagerly the
    build held four corpus-sized arrays and died 3.4 GB short); tile by
    tile the program's temporaries are one tile's. Since ISSUE 49 this is
    the build where ``serve/index.py rest_width`` declines (the cell's
    control, a device too full); the padded one is read below
    (``-k rest_layout``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu.serve import index as serve_index

    one = SingleDeviceSharding(v5e_devices[0])
    program = serve_index._pad_and_tile.lower(
        jax.ShapeDtypeStruct((9_830_400, 100), jnp.float32, sharding=one),
        c_pad=10_027_008, c_tile=8192, dtype=jnp.dtype("float32"),
        width=100).compile()
    mem = program.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes
    assert mem.output_size_in_bytes == 1224 * 8192 * 100 * 4  # no lane pad


def test_the_rest_layout_of_a_padded_stack_from_build_to_batch(
        v5e_devices, monkeypatch):
    """``stream-msturing10m-runbook`` since ISSUE 49: 9 830 400 x 100 rows
    rest zero-padded at 128 columns (``serve/index.py rest_width``), 1224
    tiles row-major on the lane grid, in every program that touches the
    stack. The BUILD is still the one program that pads and tiles: its
    temporaries a tile's, its output the 5.13e9 B stack and no copy beside
    it. The UPSERT is the one-scatter form the d = 128 indexes take, every
    store array aliased, nothing store-sized copied. The BATCH program
    pads its 1024 x 100 query tile itself, screens, gathers the candidates
    from the stack viewed flat — a bitcast — and neither copies nor
    transposes the stack; the d = 100 stack's per-step slice copy
    (``f32[1,8192,100]``) is gone. Since ISSUE 51 the screened scan is ONE
    Mosaic call (``knn.fused``, the three-pass form: the lists it makes
    are 896 wide and hold slots): outside the re-scan's conditional the
    program holds no (1024, 8192) distance tile, no *bins* call, no
    bound-refresh call and no loop over the stack, and the only XLA dot is
    the re-scan's at ``highest``; the kernel's VMEM — the lists at depth
    7, the query side and a piece's bf16 pieces three widths wide — is
    under the rule's 64 MiB, and the call asks for that and its headroom."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.backends import serial
    from mpi_knn_tpu.serve import index as serve_index
    from mpi_knn_tpu.serve import mutate

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_devices[0])
    q, tiles, c_tile, dim, wide, chunk = 1024, 1224, 8192, 100, 128, 1024
    cfg = KNNConfig(k=10, backend="serial", query_tile=q, corpus_tile=c_tile,
                    exclude_zero=False)
    assert serve_index.rest_width(
        cfg, dim, c_tile, tiles * c_tile, onepass=False)[0] == wide
    rest = rf"f32\[{tiles},{c_tile},{wide}\]\{{2,1,0:T\(8,128\)\}}"

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    stack = arg((tiles, c_tile, wide), jnp.float32)
    ids_plane = arg((tiles, c_tile), jnp.int32)
    norms = arg((tiles, c_tile), jnp.float32)
    vec = arg((chunk,), jnp.int32)
    with jax.enable_x64(False):
        build = serve_index._pad_and_tile.lower(
            arg((9_830_400, dim), jnp.float32), c_pad=tiles * c_tile,
            c_tile=c_tile, dtype=jnp.dtype("float32"), width=wide).compile()
        upsert = mutate.serial_upsert_jit.lower(
            arg((chunk, dim), jnp.float32), vec, vec, vec, vec, vec,
            stack, ids_plane, norms, cfg=cfg, by_tile=False).compile()
        batch = jax.jit(
            serial.serve_chunk, static_argnames=("cfg",), donate_argnums=(2, 3)
        ).lower(
            arg((1, q, dim), jnp.float32), arg((1, q), jnp.int32),
            arg((1, q, 10), jnp.float32), arg((1, q, 10), jnp.int32),
            stack, ids_plane, norms, None, cfg=cfg).compile()

    mem = build.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes
    assert mem.output_size_in_bytes == tiles * c_tile * wide * 4
    assert re.search(rest, build.as_text())

    hlo = upsert.as_text()
    alias = re.search(r"input_output_alias=\{([^\n]*?)\}, entry", hlo)
    assert alias and alias.group(1).count("may-alias") + alias.group(
        1).count("must-alias") == 3, hlo[:400]
    mem = upsert.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= tiles * c_tile * 4 * (wide + 2)
    assert re.search(rest + r" parameter\(6\)", hlo)
    assert "knn.mutate/upsert" in hlo
    assert not re.search(rf"= \w+\[{tiles},{c_tile}[\],][^\n]* copy\(", hlo)

    hlo = batch.as_text()
    assert re.search(rest + r" parameter\(4\)", hlo)
    assert not _stack_moves(hlo, tiles, wide)
    assert f"f32[1,{c_tile},{dim}]" not in hlo and f",{dim}]" in hlo
    dots = re.findall(r"operand_precision=\{(\w+),\w+\}[^\n]*?op_name=\"([^\"]*)"
                      r"/dot_general\"", hlo)
    assert sorted((p, "fallback" in name) for p, name in dots) == [
        ("highest", True)], dots
    gathered = [ln for ln in hlo.splitlines()
                if re.search(rf"= f32\[{q},32,{wide}\]\S* gather\(", ln)]
    assert len(gathered) == 1 and "knn.rerank" in gathered[0], gathered
    assert re.search(
        rf"f32\[{tiles * c_tile},{wide}\]\{{1,0\S* parameter\(0\)", hlo)
    assert batch.memory_analysis().temp_size_in_bytes <= 64 << 20
    # the screened scan: one kernel over the stack where it rests
    from mpi_knn_tpu.ops.fused_scan import (
        _VMEM_HEADROOM, fused_scan_vmem_bytes)
    from mpi_knn_tpu.ops.topk import _FUSED_VMEM_BYTES, lane_bin_depth

    lines = hlo.splitlines()
    calls = [ln for ln in lines if "tpu_custom_call" in ln]
    fused = [ln for ln in calls if "knn.fused" in ln]
    assert len(fused) == 1 and re.search(
        rf"= \(f32\[{q},896\]\S*, s32\[{q},896\]", fused[0]), fused
    operands = re.sub(r"/\*[^*]*\*/", "", re.search(
        r"custom-call\(([^)]*)\)", fused[0]).group(1)).split(", ")
    assert operands[4:] == ["%tiles.1", "%tile_ids.1", "%tile_sqs.1"], operands
    assert sorted(ln.split()[0].rstrip(".0123456789") for ln in calls) == [
        "%finish", "%knn.fused"], calls  # no bins, no bound
    need = fused_scan_vmem_bytes(q, c_tile, wide, lane_bin_depth(
        q, c_tile, 32), passes=3)
    assert need <= _FUSED_VMEM_BYTES
    assert f'"size":"{need + _VMEM_HEADROOM}"' in fused[0], fused[0][-400:]
    # whatever still holds a distance tile or walks the stack is the
    # re-scan's, under its conditional
    whiles = [ln for ln in lines if " while(" in ln and "f32[1224," in ln]
    assert whiles and all("knn.select/fallback" in ln for ln in whiles), whiles
    made = [ln for ln in lines if re.search(
        rf"= f32\[{q},{c_tile}\]\S* (fusion|convolution|custom-call)\(", ln)]
    assert made and all("knn.select/fallback" in ln for ln in made), made


# ---------------------------------------------------------------------------
# the clustered batch program at the cell's size, compiled for the v5e
# (ISSUE 42): the walk fetches buckets where they rest, nothing copies the
# store


@pytest.mark.parametrize("fact", [False, True])
@pytest.mark.parametrize("exclude_self", [False, True])
def test_bucket_major_batch_program_compiles_for_the_v5e(
        v5e_devices, monkeypatch, exclude_self, fact):
    """``serve-bigann10m-ivf-bulk`` at its size: one 1024-row batch over
    4096 lists of 4728 x 128 float32 slots (9.9e9 B), ``nprobe`` 16. The
    batch is ONE query tile and its program holds the walk's kernel (a
    Mosaic call under ``knn.ivf/gather``) over 6144 work items, which
    returns slot numbers and no distance; the store enters it as it rests
    — flat for the finish's gather by a bitcast, a bucket a grid step for
    the kernel — so no instruction writes an array of the store's order
    (the row-major program's ``jnp.take`` of whole buckets compiled to
    three slices that copied all of it in every 16-row step: PERF.md
    section 6, PR 41), and the temporaries stay under 0.19e9 B beside
    10.07e9 B of arguments (the row-major program's: 4.09e9). ``fact``:
    the store holds the one-pass fact (ISSUE 46) — one bool argument more,
    the kernel with both dots in it, the query rows' bit test beside the
    score, the same temporaries to within that flag, and the kernel's
    VMEM with the bfloat16 copies of a bucket and a group counted."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.ivf import search

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_devices[0])
    q, lists, cap, dim, nprobe, k = 1024, 4096, 4728, 128, 16, 10
    cfg = KNNConfig(k=k, partitions=lists, nprobe=nprobe, query_tile=q,
                    exclude_self=exclude_self)
    assert search.ivf_query_shapes(cfg, nprobe, cap, dim, q) == (q, q)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    with jax.enable_x64(False):
        batch = jax.jit(
            search.ivf_serve_chunk, static_argnames=("cfg", "nprobe"),
            donate_argnums=(2, 3, 4),
        ).lower(
            arg((1, q, dim), jnp.float32), arg((1, q), jnp.int32),
            arg((1, q, k), jnp.float32), arg((1, q, k), jnp.int32),
            arg((search.PROBE_FIELDS,), jnp.int32),
            arg((lists, dim), jnp.float32), arg((lists,), jnp.float32),
            arg((lists, cap, dim), jnp.float32), arg((lists, cap), jnp.int32),
            arg((lists, cap), jnp.float32), None,
            arg((), jnp.bool_) if fact else None,
            arg((dim,), jnp.float32) if fact else None,
            cfg=cfg, nprobe=nprobe,
        ).compile()
    hlo = batch.as_text()
    items = search.bucket_major_items(q, nprobe, lists)
    assert items == 6144
    walk = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
            and f"s32[{items},{search.PROBE_GROUP},128]" in ln]
    assert len(walk) == 1 and "knn.ivf/gather" in walk[0], walk
    assert "mini-gather" not in hlo
    assert " while(" not in hlo  # no step over query rows is left
    width = {"f32": 4, "s32": 4, "u32": 4, "pred": 1, "bf16": 2}
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]\S* ([\w-]+)\(", hlo):
        kind, dims, op = m.groups()
        size = width.get(kind, 4) * np.prod([int(n) for n in dims.split(",")])
        assert size < 1e9 or op in ("parameter", "bitcast"), m.group(0)
    mem = batch.memory_analysis()
    # 0.1827e9 B (0.2082e9 with the self mask's ids), +32 KB under the fact
    assert mem.temp_size_in_bytes < (0.215e9 if exclude_self else 0.19e9), (
        mem.temp_size_in_bytes)
    for scope in ("knn.ivf/score", "knn.ivf/gather", "knn.rerank"):
        assert scope in hlo
    from mpi_knn_tpu.ops.bucket_walk import bucket_walk_vmem_bytes

    plain = bucket_walk_vmem_bytes(search.PROBE_GROUP, cap, dim)
    both = bucket_walk_vmem_bytes(search.PROBE_GROUP, cap, dim, onepass=True)
    assert both - plain == (cap + search.PROBE_GROUP) * dim * 2
    assert both <= search._WALK_VMEM_BYTES


# a BYTE stack (ISSUE 48: dtype="uint8"), in programs compiled for the v5e


@pytest.mark.parametrize("d", [128, 784])
def test_the_rest_layout_of_a_byte_stack(v5e_devices, d):
    """What ``ops/topk.py fused_scan_engages`` says of a uint8 (T, c, d)
    stack at rest, READ in programs compiled for the v5e: under (32, 128)
    tiles — ``T(8,128)(4,1)`` — row-major on the lane grid at every tile
    count up to the 12 208 a chip holds, rows-minor off it, where the rule
    keeps the kernel out."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu.ops.topk import fused_scan_engages

    one = SingleDeviceSharding(v5e_devices[0])

    def layout(tiles, c):
        hlo = jax.jit(lambda x: x[0, 0, 0]).lower(jax.ShapeDtypeStruct(
            (tiles, c, d), jnp.uint8, sharding=one)).compile().as_text()
        return re.search(
            rf"u8\[{tiles},{c},{d}\]\{{([^}}]+)\}} parameter\(0\)",
            hlo).group(1)

    read = {(tiles, c): layout(tiles, c)
            for c in (1024, 8192)
            for tiles in (1, 3, 127, 128, 1221, 12208)
            if tiles * c * d < 14e9}
    assert len(read) >= 8
    order = "2,1,0" if d % 128 == 0 else "1,2,0"
    assert set(read.values()) == {order + ":T(8,128)(4,1)"}, read
    assert fused_scan_engages(1024, 8192, d, 5, itemsize=1) == (
        1024 if d % 128 == 0 else None)


@pytest.mark.parametrize("tiles", [1221, 12208])
def test_byte_stack_batch_program_compiles_for_the_v5e(
        v5e_devices, monkeypatch, tiles):
    """``serve-bigann100m-u8-bulk``'s 1024-row bucket over 12 208 byte
    tiles (and the 10 M slice's 1 221): the one-pass branch is the kernel
    that walks the stack, under ``knn.scan_u8/knn.fused``, and takes the
    uint8 parameter where it rests — nothing copies, converts or re-lays
    the 12.8e9 B ahead of the call; the other branch widens a tile a step
    inside the scan; the program's temporaries are a batch's, and the
    kernel's VMEM is what ``fused_scan_vmem_bytes`` says of a byte tile."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.backends import serial
    from mpi_knn_tpu.ops.fused_scan import (
        _VMEM_HEADROOM,
        fused_scan_vmem_bytes,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_devices[0])
    q, dim = 1024, 128
    cfg = KNNConfig(k=10, backend="serial", dtype="uint8", query_tile=q,
                    corpus_tile=8192, exclude_self=False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    assert serial.fused_rule(cfg, q, 8192, dim) == 1024
    with jax.enable_x64(False):
        compiled = jax.jit(serial.serve_chunk, static_argnames=("cfg",)).lower(
            arg((1, q, dim), jnp.float32), arg((1, q), jnp.int32),
            arg((1, q, 10), jnp.float32), arg((1, q, 10), jnp.int32),
            arg((tiles, 8192, dim), jnp.uint8), arg((tiles, 8192), jnp.int32),
            arg((tiles, 8192), jnp.float32), arg((), jnp.bool_),
            arg((dim,), jnp.float32), cfg=cfg).compile()
    hlo = compiled.as_text()
    stack = rf"u8\[{tiles},8192,{dim}\]"
    # the stack's one definition is the parameter, row-major under the
    # byte tiling; whatever else names its shape only passes it on
    defs = [ln for ln in hlo.splitlines()
            if re.search(rf"= {stack}\{{", ln)
            and not re.search(r"parameter\(|get-tuple-element\(", ln)]
    assert not defs, defs[:3]
    kernels = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
               and "knn.scan_u8/knn.fused" in ln]
    assert len(kernels) == 1 and re.search(stack, kernels[0]), kernels
    assert "knn.dist_multipass" in hlo
    limit = re.search(r'vmem_limit_bytes[\\"]*:\s*[\\"]*(\d+)', kernels[0])
    want = fused_scan_vmem_bytes(q, 8192, dim, 5, itemsize=1)
    assert want < fused_scan_vmem_bytes(q, 8192, dim, 5) <= 64 << 20
    if limit:
        assert int(limit.group(1)) == want + _VMEM_HEADROOM
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * 2**30


@pytest.mark.parametrize("q", [256, 512, 1024])
def test_filtered_batch_program_compiles_for_the_v5e(v5e_devices,
                                                     monkeypatch, q):
    """``serve-yfcc10m-filter-bulk``'s scan dispatches (ISSUE 55: 512 rows
    is the bucket its batches' scan parts take) over the tagged index's
    768 tiles of d = 192: the one-pass branch is the kernel that walks the
    stack with the predicate's words an operand — the rows-minor stack a
    bitcast of the parameter, no slice or copy of a tile, no (q, 8192)
    distance tile and no (q / 8, 8, 32, 256) plane of the words in it; the
    words reach it by ONE copy a query tile (the compiler rests the
    gathered words a query row a slab and the kernel's operand is
    row-major), named for the predicate; the other branch keeps the masked
    scan of tile steps; the kernel's VMEM is what ``fused_scan_vmem_bytes``
    says with the words' two buffers."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.backends import serial
    from mpi_knn_tpu.ops.fused_scan import (
        _VMEM_HEADROOM,
        fused_scan_vmem_bytes,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_devices[0])
    tiles, dim, bitsets = 768, 192, 283
    cfg = KNNConfig(k=10, backend="serial", query_tile=1024,
                    corpus_tile=8192, exclude_self=False, max_query_tags=2)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    assert serial.fused_rule(cfg, q, 8192, dim, filtered=True) == q
    with jax.enable_x64(False):
        compiled = jax.jit(
            serial.serve_chunk_filtered, static_argnames=("cfg",)).lower(
            arg((1, q, dim), jnp.float32), arg((1, q), jnp.int32),
            arg((1, q, 10), jnp.float32), arg((1, q, 10), jnp.int32),
            arg((1, q, 2), jnp.int32),
            arg((tiles, 8192, dim), jnp.float32),
            arg((tiles, 8192), jnp.int32), arg((tiles, 8192), jnp.float32),
            arg((), jnp.bool_), arg((bitsets + 1, tiles, 256), jnp.uint32),
            cfg=cfg).compile()
    hlo = compiled.as_text()
    _assert_one_kernel_walks_the_stack(
        hlo, q, tiles, dim,
        under=r"jit\(serve_chunk_filtered\)/while/body/closed_call")
    call, = [ln for ln in hlo.splitlines()
             if "tpu_custom_call" in ln and "knn.fused" in ln]
    blocks = re.split(r"\n(?=(?:ENTRY )?%\S+ \([^\n]*\) -> [^\n]* \{\n)",
                      hlo)
    branch, = [b for b in blocks if call in b]
    words = rf"s32\[{tiles},{q},256\]"
    # the last of the kernel's operands, row-major
    assert f"s32[{tiles},{q},256]{{2,1,0}}}}, " in call, call[:900]
    made = [ln for ln in branch.splitlines() if re.search(rf"= {words}", ln)]
    assert len(made) == 1 and "knn.filter_mask" in made[0], made
    assert f"u32[{q // 8},8,32,256]" not in branch
    # the masked scan of tile steps is the other branch, plane and all
    assert re.search(
        rf"= u32\[{q // 8},8,32,256\]\S* copy\(", hlo)
    assert "cond/branch_0_fun/while/body" in hlo and "knn.filter_mask" in hlo
    limit = re.search(r'vmem_limit_bytes[\\"]*:\s*[\\"]*(\d+)', call)
    want = fused_scan_vmem_bytes(q, 8192, dim, 5, filtered=True)
    assert want - fused_scan_vmem_bytes(q, 8192, dim, 5) == 2 * q * 256 * 4
    if limit:
        assert int(limit.group(1)) == want + _VMEM_HEADROOM
    # the words twice (gathered, and in the kernel's order): 2 x 0.75 GiB
    # at 1024 rows
    words_gib = tiles * q * 256 * 4 / 2**30
    assert compiled.memory_analysis().temp_size_in_bytes / 2**30 <= (
        2 * words_gib + 0.05)


def test_block_ingest_program_holds_one_block_on_the_v5e(v5e_devices):
    """A block-fed build's peak is the stack, its planes and ONE block's
    temporaries (``serve/index.py build_index_blocks``): in the block
    program compiled for the chip at the byte cell's shapes — 12 208 tiles,
    a block of 56 — the donated stack is updated in place and the
    temporaries stay under two blocks' bytes, whether the block comes as
    bytes or as floats to be checked; the norms' program reads the bytes
    and squares no widened copy of them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu.backends import serial
    from mpi_knn_tpu.serve import index as ix

    one = SingleDeviceSharding(v5e_devices[0])
    tiles, c, d, n = 12208, 8192, 128, 56 * 8192

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    with jax.enable_x64(False):
        for block_dtype in (jnp.uint8, jnp.float32):
            mem = ix._ingest_block.lower(
                arg((tiles, c, d), jnp.uint8), arg((n, d), block_dtype),
                arg((), jnp.int32), sums=True).compile().memory_analysis()
            assert mem.alias_size_in_bytes >= tiles * c * d
            assert mem.temp_size_in_bytes < 2 * n * d * jnp.dtype(
                block_dtype).itemsize, (block_dtype, mem.temp_size_in_bytes)
        norms = serial._stack_norms.lower(
            arg((tiles, c, d), jnp.uint8), "l2",
            arg((d,), jnp.float32)).compile().memory_analysis()
    assert norms.temp_size_in_bytes < 0.1 * 2**30


def test_range_program_compiles_for_the_v5e(v5e_devices, monkeypatch):
    """``serve-ssnpp100m-range-bulk``'s 1024-row range program over 6104
    byte tiles of 256 columns (``backends/range_scan.py``): the scan is the
    fused kernel in its ranged form under ``knn.scan_range`` and takes the
    uint8 stack where it rests; the second path fetches a tile by its
    index and nothing copies, converts or re-lays the 12.8e9 B (a gather
    of sixteen tiles at once did: the whole stack in eight pieces); the
    program's temporaries are a batch's flat answers."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.backends import range_scan
    from mpi_knn_tpu.ops.fused_scan import (
        _VMEM_HEADROOM,
        fused_scan_vmem_bytes,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_devices[0])
    q, dim, tiles = 1024, 256, 6104
    cfg = KNNConfig(k=10, backend="serial", dtype="uint8", query_tile=q,
                    corpus_tile=8192, exclude_self=False, range_cap=8192)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    assert range_scan.range_engages(q, 8192, dim, 1)
    with jax.enable_x64(False):
        compiled = jax.jit(
            range_scan.serve_chunk_range, static_argnames=("cfg",)).lower(
            arg((1, q, dim), jnp.float32), arg((1, q), jnp.int32),
            arg((1, q), jnp.float32),
            arg((tiles, 8192, dim), jnp.uint8), arg((tiles, 8192), jnp.int32),
            arg((tiles, 8192), jnp.float32), arg((), jnp.bool_),
            arg((dim,), jnp.float32), cfg=cfg).compile()
    hlo = compiled.as_text()
    stack = rf"u8\[{tiles},8192,{dim}\]"
    defs = [ln for ln in hlo.splitlines()
            if re.search(rf"= {stack}\{{", ln)
            and not re.search(r"parameter\(|get-tuple-element\(", ln)]
    assert not defs, defs[:3]
    # no piece of the stack either: what the second path reads is a tile
    pieces = [ln for ln in hlo.splitlines()
              if re.search(rf"= u8\[{tiles},\d+,{dim}\]", ln)
              and not re.search(r"parameter\(|get-tuple-element\(", ln)]
    assert not pieces, pieces[:3]
    kernels = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
               and range_scan.SCAN_SCOPE in ln]
    assert len(kernels) == 1 and re.search(stack, kernels[0]), kernels
    assert range_scan.OVERFLOW_SCOPE in hlo and range_scan.FINISH_SCOPE in hlo
    limit = re.search(r'vmem_limit_bytes[\\"]*:\s*[\\"]*(\d+)', kernels[0])
    want = fused_scan_vmem_bytes(q, 8192, dim, range_scan.RANGE_DEPTH,
                                 itemsize=1, ranged=True)
    assert want <= 64 << 20
    if limit:
        assert int(limit.group(1)) == want + _VMEM_HEADROOM
    # two flat pieces of a batch's answers and the loop's copy of them
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2 * 2**30
