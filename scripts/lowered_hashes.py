#!/usr/bin/env python
"""Hash the lowered program of every lint cell of a checkout, to compare
two commits: which cells' programs a change touched, and which it left byte
for byte as they were.

    python scripts/lowered_hashes.py <checkout> <out.json> [<dir for the texts>]
    python scripts/lowered_hashes.py --cells <checkout> <out.json> [<dir>]
    python scripts/lowered_hashes.py --diff <a.json> <b.json>

The text hashed is the StableHLO of ``jax.stages.Lowered.as_text()``: it
holds no source locations, so an edit that moves lines changes nothing. The
cells are ``analysis.lowering.default_targets()``, lowered as the lint
lowers them, on eight virtual CPU devices; the checkout is put first on
``sys.path``, so run it once per checkout (a parent unpacked by ``git
archive`` and the working tree), then ``--diff`` the two files.

``--cells`` hashes the programs of the benchmark's cells instead, at the
cells' own shapes: the lint's shapes are too small for what engages by
shape (the row bound, the kernel that walks the stack). What a cell runs is
asked of the code that runs it, over abstract operands (nothing is
allocated): a serving cell's programs are its index kind's batch program
(the index's layout, as ``serve.engine`` lowers it: dense, dense with tags
where the configuration names ``max_query_tags``, clustered where it names
``partitions``) at every bucket its traffic warms (``warm_sizes``), a dense
L2 one and a clustered one with and without the one-pass operand; a
one-shot cell's is
``backends.serial._search_stack`` at its traffic's ``slice_rows``; a ring cell's the sharded call. Rows, width and
``knn`` are the configuration file's. The text is the jaxpr
(``jax.make_jaxpr``) traced as the chip traces it — ``jax.default_backend``
answers "tpu", so the kernels are Mosaic calls and the ring carries them —
kernel bodies included, source locations not.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys


# what a v5e's ``memory_stats()["bytes_limit"]`` reads (chip run, PR 49)
V5E_USABLE_BYTES = 16_909_336_064


def v5e_free_at_build(rows: int, dim: int, metric: str) -> int:
    """Bytes a v5e has free when a serving cell's build asks how wide its
    stack may rest (``serve/index.py rest_width``, ISSUE 49) — a MODEL of
    what the benchmark's launchers hold then, not a reading: the (rows,
    dim) float32 array they hand over and, under L2, its centred copy.
    The one place the model is written: the cells' programs here and the
    tests that ask the rule at the cells' shapes use it."""
    return V5E_USABLE_BYTES - (2 if metric == "l2" else 1) * rows * dim * 4


def _record(out: dict, texts_dir: str | None, label: str, text: str,
            suffix: str) -> None:
    """``out[label]`` = the text's hash; the text under ``texts_dir``."""
    out[label] = hashlib.sha256(text.encode()).hexdigest()
    if texts_dir:
        os.makedirs(texts_dir, exist_ok=True)
        name = label.replace("/", "_") + suffix
        with open(os.path.join(texts_dir, name), "w") as f:
            f.write(text)


def hashes(root: str, texts_dir: str | None) -> dict:
    sys.path.insert(0, os.path.abspath(root))
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from mpi_knn_tpu.analysis import lowering

    out = {}
    for t in lowering.default_targets():
        try:
            if t.mutate:
                lowered = lowering._lower_mutate(t)[0]
            elif t.serve:
                lowered = lowering._lower_serve(t)[0]
            else:
                lowered = lowering._LOWERERS[t.backend](t)[0]
        except lowering.UnsupportedTarget:
            out[t.label] = "unsupported"  # float64 without x64, and the like
            continue
        _record(out, texts_dir, t.label, lowered.as_text(), ".mlir")
    return out


def cell_hashes(root: str, texts_dir: str | None) -> dict:
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpi_knn_tpu.backends import ring, serial
    from mpi_knn_tpu.config import KNNConfig
    from mpi_knn_tpu.ivf import index as ivf_index
    from mpi_knn_tpu.serve import index as serve_index
    from mpi_knn_tpu.parallel.partition import pad_to_multiple

    jax.default_backend = lambda: "tpu"
    arg = jax.ShapeDtypeStruct
    def stack(cfg, rows, dim, c_tile, fact=True, tagged=False):
        """The resident stack, its id and norm planes, the one-pass
        verdict: the arguments every serial program ends with. The stack
        is as wide as the checkout's build would rest it on a v5e
        (:func:`v5e_free_at_build`; ``serve/index.py rest_width``, ISSUE
        49; an older checkout rests every stack at its rows' width)."""
        tiles = pad_to_multiple(
            int(np.ceil(rows * (1 + cfg.bucket_headroom))), c_tile) // c_tile
        if hasattr(serve_index, "rest_width"):
            dim = serve_index.rest_width(
                cfg, dim, c_tile, tiles * c_tile, onepass=fact,
                tagged=tagged,
                free_bytes=v5e_free_at_build(rows, dim, cfg.metric))[0]
        # (a byte stack where the configuration rests one: ISSUE 48)
        rest = jnp.uint8 if cfg.dtype == "uint8" else jnp.float32
        return (arg((tiles, c_tile, dim), rest),
                arg((tiles, c_tile), jnp.int32),
                arg((tiles, c_tile), jnp.float32), arg((), jnp.bool_))

    def dense(cfg, rows, dim, tagged):
        """The dense index of the configuration's shape, with and without
        the one-pass fact; ``tagged``: built with tags, as the
        configuration states by naming ``max_query_tags``."""
        c_tile = serial.effective_tiles(cfg, rows, cfg.query_tile)[1]
        layout = serve_index.TAGGED_SERIAL if tagged else serve_index.SERIAL
        more = {}
        if cfg.dtype == "uint8":  # its offset is one more resident operand
            layout = serve_index.BYTE_SERIAL
            more["rest_offset"] = arg((dim,), jnp.float32)
        for has_fact in ((True, False) if cfg.metric == "l2" else (False,)):
            *resident, fact = stack(cfg, rows, dim, c_tile, has_fact, tagged)
            onepass = fact if has_fact else None
            tags = types.SimpleNamespace(
                # (the planes' count is the data's: any traces the same
                # text)
                tag_bits=arg((cfg.max_query_tags + 1, resident[0].shape[0],
                              c_tile // 32), jnp.uint32)) if tagged else None
            yield "-nofact" if onepass is None else "", (
                serve_index.CorpusIndex(
                    cfg, "serial", rows, dim, c_tile, None, layout,
                    *resident, onepass=onepass, tags=tags, **more))

    def clustered(cfg, rows, dim):
        """The clustered index the configuration states: its lists, their
        height and the probe count are all in ``knn``; with and without
        the store's one-pass fact."""
        lists, cap = cfg.partitions, cfg.bucket_cap
        store = (arg((lists, dim), jnp.float32), arg((lists,), jnp.float32),
                 arg((lists, cap, dim), jnp.float32),
                 arg((lists, cap), jnp.int32), arg((lists, cap), jnp.float32))
        # (a checkout from before PR 46 knows no fact: its one program
        # goes under "-nofact", beside the later checkouts' own)
        if hasattr(ivf_index.IVFIndex, "onepass"):
            yield "", ivf_index.IVFIndex(
                cfg, rows, dim, lists, cap, cfg.nprobe, None, *store,
                onepass=arg((), jnp.bool_),
                mean_frac=arg((dim,), jnp.float32))
        yield "-nofact", ivf_index.IVFIndex(
            cfg, rows, dim, lists, cap, cfg.nprobe, None, *store)

    def served(indexes, cfg, buckets):
        """The batch programs of an index, as its layout lowers them."""
        for suffix, index in indexes:
            layout = index.layout
            for bucket in buckets:
                q_pad, q_tile = layout.bucket_shapes(index, cfg, bucket)
                yield f"bucket{bucket}{suffix}", (
                    jax.make_jaxpr(functools.partial(
                        layout.jit(False), **layout.statics(
                            index, cfg, bucket)))(
                        *layout.query_side(index, cfg, q_pad, q_tile),
                        *layout.resident(index)))

    def one_shot(cfg, rows, dim, nq):
        q_tile, c_tile = serial.effective_tiles(cfg, rows, nq)
        yield "call", jax.make_jaxpr(functools.partial(
            serial._search_stack, cfg=cfg, q_tile=q_tile))(
            arg((nq, dim), jnp.float32), arg((nq,), jnp.int32),
            *stack(cfg, rows, dim, c_tile))

    def ring_call(cfg, rows, dim, nq):
        mesh = Mesh(np.asarray(jax.devices()[:cfg.num_devices]),
                    (cfg.mesh_axis,))
        by_rows = NamedSharding(mesh, P(cfg.mesh_axis))
        yield "call", jax.make_jaxpr(
            lambda *a: ring._ring_knn_sharded(
                *a, cfg, True, mesh, cfg.mesh_axis, cfg.query_tile,
                cfg.corpus_tile, onepass=jnp.asarray(True)))(
            arg((nq, dim), jnp.float32, sharding=by_rows),
            arg((nq,), jnp.int32, sharding=by_rows),
            arg((rows, dim), jnp.float32, sharding=by_rows),
            arg((rows,), jnp.int32, sharding=by_rows))

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    out = {}
    for cell in bench["workloads"]:
        with open(os.path.join(root, files[cell["config"]])) as f:
            config = json.load(f)
        with open(os.path.join(
                root, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        cfg = KNNConfig(**config["knn"])
        shape = cfg, config["rows"], config["dim"]
        if "warm_sizes" in mix:
            indexes = (clustered(*shape) if cfg.partitions else
                       dense(*shape, "max_query_tags" in config))
            programs = served(indexes, cfg, mix["warm_sizes"])
        elif cfg.backend.startswith("ring"):
            programs = ring_call(*shape, mix["slice_rows"])
        else:
            programs = one_shot(*shape, mix["slice_rows"])
        for name, jaxpr in programs:
            _record(out, texts_dir, f"{cell['name']}/{name}", str(jaxpr),
                    ".jaxpr")
    return out


def diff(a_path: str, b_path: str) -> int:
    with open(a_path) as f:
        a = json.load(f)
    with open(b_path) as f:
        b = json.load(f)
    changed = sorted(k for k in a.keys() | b.keys() if a.get(k) != b.get(k))
    print(f"{len(a.keys() & b.keys()) - len(changed)} cells equal, "
          f"{len(changed)} differ")
    for k in changed:
        print(f"  {k}")
    return 1 if changed else 0


def main(argv: list[str]) -> int:
    if len(argv) == 3 and argv[0] == "--diff":
        return diff(argv[1], argv[2])
    make = hashes
    if argv and argv[0] == "--cells":
        make, argv = cell_hashes, argv[1:]
    if len(argv) not in (2, 3):
        print(__doc__, file=sys.stderr)
        return 2
    out = make(argv[0], argv[2] if len(argv) == 3 else None)
    with open(argv[1], "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(f"{len(out)} cells -> {argv[1]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
