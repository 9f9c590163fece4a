"""Lower every registered backend configuration to HLO text — on CPU, no
TPU needed.

The engine's subjects are the framework's own jitted cores, lowered with
the exact static arguments the production wrappers would pass for a
small-but-structured problem (multiple query tiles per device, multiple
corpus tiles per ring block, a full 8-way ring on the virtual CPU mesh).
Both pipeline stages are captured in-process from one lowering:

- ``before_opt``: ``Lowered.compiler_ir("hlo").as_hlo_text()`` — the
  module XLA receives (where the blocking barrier is still visible);
- ``after_opt``: ``Compiled.as_text()`` — the module XLA will run (where
  fusion/DCE/partitioning have had their say).

No ``--xla_dump_to`` subprocess dance: the old artifact script needed one
process per variant because dump flags are process-wide XLA_FLAGS; the
in-process APIs have no such coupling, so the full matrix runs in one
process and the results are cached per configuration.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.config import BACKENDS, KNNConfig

STAGES = ("before_opt", "after_opt")
LINT_DTYPES = ("float32", "bfloat16", "float64")
LINT_POLICIES = ("exact", "mixed")
# the quantization axis (ISSUE 9): "" = unquantized; "xfer-int8" = the
# int8 block-scaled RING TRANSFER (mixed-policy ring cells — config.py
# refuses exact); "int8"/"int4" = the clustered store's block-scaled
# AT-REST levels. Quantized cells run the quant/dequant dtype contract
# (R3), wire-priced budgets (R2 gather bytes, R4 permute/all-to-all
# payloads), and the usual donation/probe-discipline rules.
LINT_QUANTS = ("xfer-int8", "int8", "int4")
# the dense (full-scan) backends sweep the whole metric × dtype product;
# the clustered "ivf" / "ivf-sharded" cells are appended explicitly
# (l2/float32 only — the IVF path's own contract) but share the CLI
# filter namespace
DENSE_LINT_BACKENDS = tuple(b for b in BACKENDS if b != "auto")
# the metrics every dense backend, dtype, policy and schedule takes: the
# distances. The inner product ("ip") reaches the serial backend at
# float32 / exact alone (config.py refuses the rest), so its cells are
# appended explicitly, as the clustered ones are
DISTANCE_METRICS = ("l2", "cosine")
LINT_BACKENDS = DENSE_LINT_BACKENDS + ("ivf", "ivf-sharded")

# Small but structurally faithful: 8 query tiles, 8 corpus tiles, an 8-way
# ring with one (q_tile × c_tile) block tile per device per round — every
# loop the production shapes have, at compile-in-seconds size.
LINT_M, LINT_NQ, LINT_D, LINT_K = 128, 64, 32, 4
LINT_QUERY_TILE, LINT_CORPUS_TILE = 8, 16
# Mixed-policy cells need tiles WIDER than the 4k overfetch, or the two-pass
# pipeline would degenerate to the exact fallback and R3's compress/rerank
# dot contract would be vacuously unverifiable: a 2× corpus and a 32-wide
# tile keep 4k=16 < c_tile=32 even on the 8-way ring (256/8 = 32 per block).
LINT_M_MIXED, LINT_CORPUS_TILE_MIXED = 256, 32


@dataclasses.dataclass(frozen=True)
class LintTarget:
    """One cell of the backend × metric × dtype × precision-policy ×
    ring-schedule × serve × ladder matrix (``schedule`` only varies for
    ring backends; ``serve`` cells lint the per-batch program the serving
    engine's executable cache compiles instead of the one-shot core;
    ``ladder`` cells lint the program a degradation-ladder rung would
    serve — ``"bucket"`` halves the row bucket, ``"nprobe"`` drops the
    clustered probe count to 1 — so R5's donation contract and R2's
    strict probed-bytes budget are re-certified on exactly what the
    ladder lowers, retry paths introducing no new copies)."""

    backend: str
    metric: str
    dtype: str
    policy: str = "exact"
    schedule: str = "uni"
    serve: bool = False
    ladder: str = ""  # "" | "bucket" | "nprobe" — serve cells only
    quant: str = ""  # "" | "xfer-int8" (ring) | "int8" | "int4" (at-rest)
    # frontend=True (serve cells only): the batch is formed by the
    # serving front end's PRODUCTION coalescer (multi-tenant requests,
    # round-robin drain — mpi_knn_tpu.frontend.coalesce) before lowering
    # through lower_bucket, certifying that coalesced dispatch compiles
    # exactly a cell of the existing bucket grid: the front end adds NO
    # new programs, only fills existing buckets (R1–R5 re-certify on
    # what it fills)
    frontend: bool = False
    # mutate != "" (ISSUE 14): the cell lints a LIVE-MUTATION program —
    # "upsert" / "delete" / "compact" — lowered through the production
    # serve.mutate.lower_mutation (the exact object the mutation
    # executable cache compiles). R5 certifies the donated in-place
    # store update (every output aliased, no corpus-sized copy) and
    # R2-strict budgets the TOUCHED working set (the mutation chunk, or
    # the whole store for a compact — never more), with the in-place
    # scatter/dynamic-update-slice forms exempted as buffer-forwarding
    # plumbing (meta strict_exempt_ops)
    mutate: str = ""

    @property
    def label(self) -> str:
        base = f"{self.backend}/{self.metric}/{self.dtype}"
        if self.policy != "exact":
            base = f"{base}/{self.policy}"
        if self.schedule != "uni":
            base = f"{base}/{self.schedule}"
        if self.quant:
            base = f"{base}/{self.quant}"
        if self.serve:
            base = f"{base}/serve"
        if self.ladder:
            base = f"{base}/ladder-{self.ladder}"
        if self.frontend:
            base = f"{base}/frontend"
        if self.mutate:
            base = f"{base}/mutate-{self.mutate}"
        return base


RING_BACKENDS = ("ring", "ring-overlap")


def default_targets() -> list[LintTarget]:
    return [
        LintTarget(b, m, d)
        for b in DENSE_LINT_BACKENDS
        for m in DISTANCE_METRICS
        for d in LINT_DTYPES
    ] + [
        # the inner product: the one-shot program and the serve-cache form
        # of the one layout that takes it (no norm plane among the
        # operands; R5 certifies the scratch donation without it)
        LintTarget("serial", "ip", "float32"),
        LintTarget("serial", "ip", "float32", serve=True),
    ] + [
        # the mixed compress-and-rerank policy: float32 only (config.py
        # validation), every backend × metric
        LintTarget(b, m, "float32", "mixed")
        for b in DENSE_LINT_BACKENDS
        for m in DISTANCE_METRICS
    ] + [
        # the bidirectional ring schedule: ring backends only, float32, both
        # policies — R4 certifies the counter-directed permute accounting
        # (2 per direction) and R1 re-certifies overlap/blocking sequencing
        # on the two-traveler step body
        LintTarget(b, m, "float32", p, "bidir")
        for b in RING_BACKENDS
        for m in DISTANCE_METRICS
        for p in ("exact", "mixed")
    ] + [
        # the serving engine's per-batch programs (mpi_knn_tpu.serve):
        # every backend at l2/float32 plus the mixed serial cell — R5
        # certifies the scratch donation (input_output_alias/buffer_donor)
        # and the no-resident-corpus-copy property; R1–R4 re-run on the
        # serve lowering (same tile/rotation bodies, so the sequencing,
        # memory, dtype and collective contracts must survive the serving
        # wrapper unchanged)
        LintTarget(b, "l2", "float32", serve=True)
        for b in DENSE_LINT_BACKENDS
    ] + [
        LintTarget("serial", "l2", "float32", "mixed", serve=True),
    ] + [
        # the clustered (IVF) cells — one-shot and serve-cache forms, both
        # policies: R6 certifies the probe-gather-feeds-the-only-exact-dot
        # contract and R2 runs in STRICT mode (the probed-bytes bound
        # nprobe·bucket_cap·d per query row replaces the largest-input
        # floor, so a full-corpus materialization is a finding even though
        # the whole corpus is a program input); the serve cells add R5's
        # donation/no-corpus-copy contract on the bucket-cache program
        LintTarget("ivf", "l2", "float32"),
        LintTarget("ivf", "l2", "float32", "mixed"),
        LintTarget("ivf", "l2", "float32", serve=True),
        LintTarget("ivf", "l2", "float32", "mixed", serve=True),
    ] + [
        # the SHARDED clustered cells (ivf/sharded.py): the routed
        # candidate exchange over a 4-shard CPU mesh — R2-strict's
        # probed-bytes budget is enforced PER SHARD (the exchange buffers
        # + rerank working set of one shard's resident tile, never the
        # global corpus), R4 accounts the exchange all-to-alls (count,
        # full-ring replica groups, payload bytes ≤ the declared per-tile
        # budget), R6 re-certifies the probe discipline on the routed
        # gathers, and the serve cells add R5's every-output-aliased
        # donation contract (three outputs, three donated scratches)
        LintTarget("ivf-sharded", "l2", "float32"),
        LintTarget("ivf-sharded", "l2", "float32", "mixed"),
        LintTarget("ivf-sharded", "l2", "float32", serve=True),
        LintTarget("ivf-sharded", "l2", "float32", "mixed", serve=True),
    ] + [
        # the degradation-ladder rung programs (resilience/ladder.py):
        # under sustained deadline breach ServeSession serves smaller-
        # nprobe / mixed / smaller-bucket cells of the SAME executable
        # cache — the mixed rung is already certified by the mixed serve
        # cells above; these add the bucket/2 rung (serial + ivf) and the
        # nprobe→1 rung (ivf, where R2-strict's probed-bytes budget
        # SHRINKS with the rung — the budget is re-derived from the rung
        # cfg, so a rung program materializing more than its own smaller
        # bound is a finding), each under R5's donation/no-corpus-copy
        # contract: degrading must never cost the donation or introduce
        # corpus-sized copies
        LintTarget("serial", "l2", "float32", serve=True, ladder="bucket"),
        LintTarget("ivf", "l2", "float32", serve=True, ladder="bucket"),
        LintTarget("ivf", "l2", "float32", serve=True, ladder="nprobe"),
        # the sharded nprobe rung: the resilience ladder's first shed on
        # a sharded session — at the safe route cap the exchange buffers
        # scale with nprobe, so R2-strict's per-shard budget here is
        # HALF the full rung's (re-derived from the rung cfg; a rung
        # program materializing beyond its own smaller bound is a
        # finding), with R5's donation contract intact on degraded cells
        LintTarget("ivf-sharded", "l2", "float32", serve=True,
                   ladder="nprobe"),
    ] + [
        # the serving FRONT END's hot path (ISSUE 11): a coalesced
        # multi-tenant batch formed by the production Coalescer
        # (mpi_knn_tpu.frontend), lowered through the SAME production
        # lower_bucket as every serve cell. The cell's claim is that
        # coalescing adds no new programs — the coalesced batch compiles
        # exactly the serve grid cell its row count buckets to (asserted
        # in the lowering: formed rows == the bucket the serve cell
        # lints) — with R5's donation and R1–R4 re-certified on what
        # coalesced dispatch actually compiles
        LintTarget("serial", "l2", "float32", serve=True, frontend=True),
    ] + [
        # the LIVE-MUTATION cells (ISSUE 14): the donated in-place
        # upsert/delete/compact programs of the mutable layouts, lowered
        # through the production serve.mutate.lower_mutation. R5's
        # every-output-aliased contract and copy census run on exactly
        # what sustained churn executes (an un-donated store or a
        # corpus-sized copy is a finding — injected counterexamples in
        # tests/test_hlo_lint.py fire through this same rule path), and
        # R2-strict's budget is the TOUCHED working set: the mutation
        # chunk for upsert/delete (a full-store gather — the headroom-
        # overflow shape — is a finding), the store itself only for the
        # compact rebuild
        LintTarget("serial", "l2", "float32", mutate="upsert"),
        LintTarget("serial", "l2", "float32", mutate="delete"),
        LintTarget("ivf", "l2", "float32", mutate="upsert"),
        LintTarget("ivf", "l2", "float32", mutate="delete"),
        LintTarget("ivf", "l2", "float32", mutate="compact"),
        # the sharded store mutates through the SAME donated scatters
        # under GSPMD — the donation/no-copy contract must survive the
        # partitioner (R4's exchange accounting does not apply: mutation
        # has no candidate exchange, and the partitioner owns whatever
        # plumbing it emits)
        LintTarget("ivf-sharded", "l2", "float32", mutate="upsert"),
    ] + [
        # the QUANTIZED cells (ISSUE 9). Ring transfer at int8 — mixed
        # policy only (config.py refuses exact): R3 certifies the
        # quant/dequant contract (exactly one dequant convert + scale
        # multiply feeding each compress dot; no dot touches raw codes),
        # R4 counts THREE permutes per direction (codes + scales + ids)
        # and prices every permute payload at the wire dtype, R1
        # re-certifies overlap/blocking sequencing with the scale row in
        # the rotation (possible only because quantization happens at
        # shard time, OUTSIDE the compiled rotation).
        LintTarget("ring", "l2", "float32", "mixed", quant="xfer-int8"),
        LintTarget("ring-overlap", "l2", "float32", "mixed",
                   quant="xfer-int8"),
        LintTarget("ring-overlap", "l2", "float32", "mixed", "bidir",
                   quant="xfer-int8"),
        LintTarget("ring-overlap", "l2", "float32", "mixed", serve=True,
                   quant="xfer-int8"),
    ] + [
        # clustered at-rest int8/int4: R2-strict keeps the element budget
        # AND adds the wire-priced gather bound (the probe gather must
        # move code lanes, 4–8× under the f32 bytes — dequantize AFTER
        # the gather), R6's probe discipline re-certifies on the code
        # gathers, R3 checks the dequant contract, and the serve cell
        # re-certifies R5's donation on a quantized bucket-cache program.
        LintTarget("ivf", "l2", "float32", quant="int8"),
        LintTarget("ivf", "l2", "float32", "mixed", quant="int8"),
        LintTarget("ivf", "l2", "float32", quant="int4"),
        LintTarget("ivf", "l2", "float32", "mixed", serve=True,
                   quant="int8"),
        # sharded at-rest int8: the candidate returns ride the exchange
        # as code lanes + a FIFTH (scales) all-to-all — R4 pins the count
        # and holds the payload to the wire-priced budget; R2-strict's
        # per-shard gather bound covers the owner-side exchange gather.
        LintTarget("ivf-sharded", "l2", "float32", "mixed", quant="int8"),
        LintTarget("ivf-sharded", "l2", "float32", "mixed", serve=True,
                   quant="int8"),
    ]


class UnsupportedTarget(Exception):
    """This configuration is rejected by the backend itself (a registered
    restriction, not a lint failure) or cannot lower in this process."""


def _base_cfg(target: LintTarget) -> KNNConfig:
    mixed = target.policy == "mixed"
    return KNNConfig(
        k=LINT_K,
        metric=target.metric,
        dtype=target.dtype,
        query_tile=LINT_QUERY_TILE,
        corpus_tile=(
            LINT_CORPUS_TILE_MIXED if mixed else LINT_CORPUS_TILE
        ),
        precision_policy=target.policy,
        ring_schedule=target.schedule,
        ring_transfer_dtype=(
            "int8" if target.quant == "xfer-int8" else None
        ),
    )


def _lint_m(target: LintTarget) -> int:
    return LINT_M_MIXED if target.policy == "mixed" else LINT_M


def _mixed_meta(target: LintTarget, q_tile: int, c_tile: int):
    """R2 budget extension for mixed cells: the rerank legitimately gathers
    a (q_tile, 4k, d) block of survivor rows — account for it explicitly
    instead of riding on the input-size floor."""
    if target.policy != "mixed":
        return {}
    from mpi_knn_tpu.ops.rerank import overfetch_width

    return {"extra_elems": q_tile * overfetch_width(LINT_K, c_tile) * LINT_D}


def _dense_cost(target: LintTarget, q: int, c: int, c_tile: int, *,
                queries: int, sites: int = 1, trips: int = 1) -> dict:
    """R8's declared FLOP facts for a dense cell (analysis/cost.py's
    ``dense`` scheme): the padded per-execution distance-dot extents,
    the schedule's site/trip structure, and — on mixed cells — the
    rerank overfetch width and how many rerank blocks run per site-trip
    (one per corpus tile). ``queries`` is the REAL (unpadded) queries
    answered per execution — the roofline's q/s numerator."""
    from mpi_knn_tpu.ops.rerank import overfetch_width

    facts = {"scheme": "dense", "q": int(q), "c": int(c), "d": LINT_D,
             "sites": sites, "trips": trips, "queries": int(queries)}
    if target.policy == "mixed":
        facts["w"] = overfetch_width(LINT_K, c_tile)
        facts["rblocks"] = int(c) // int(c_tile)
    return facts


def _ivf_cost(index, cfg: KNNConfig, q: int, *, queries: int) -> dict:
    """R8's declared FLOP facts for a clustered cell (analysis/cost.py's
    ``ivf`` scheme): centroid scoring plus the probed-width gather dot,
    with ``q`` the per-device padded query rows (per-shard for the
    sharded layout — the after-opt module is the per-device program)."""
    from mpi_knn_tpu.ops.rerank import mixed_applies, overfetch_width

    v = cfg.nprobe * index.bucket_cap
    facts = {
        "scheme": "ivf", "q": int(q), "d": index.dim,
        "partitions": index.partitions, "nprobe": cfg.nprobe,
        "bucket_cap": index.bucket_cap, "queries": int(queries),
    }
    if cfg.precision_policy == "mixed" and mixed_applies(cfg.k, v):
        facts["w"] = overfetch_width(cfg.k, v)
        facts["rblocks"] = 1
    return facts


def _acc_bytes(dtype: str) -> int:
    return 8 if dtype == "float64" else 4


def _require_x64(target: LintTarget) -> None:
    if target.dtype == "float64" and not jax.config.jax_enable_x64:
        # flipping the global here would silently change unrelated tracing
        # in the host process; the lint CLI opts in explicitly instead
        raise UnsupportedTarget(
            "float64 targets need jax_enable_x64 (the lint CLI enables it; "
            "in-process callers must opt in)"
        )


def hlo_texts(lowered) -> dict[str, str]:
    """Both pipeline stages from one ``jax.stages.Lowered``."""
    texts, _ = hlo_texts_and_memory(lowered)
    return texts


def hlo_texts_and_memory(lowered):
    """Both pipeline stages PLUS the compiled executable's PJRT memory
    stats (args/outputs/alias/temp bytes) from the SAME compile — the
    honesty anchor R7's liveness analyzer is cross-checked against
    (analysis.memory); capturing it here costs zero extra compiles."""
    from mpi_knn_tpu.analysis.memory import pjrt_memory_stats

    compiled = lowered.compile()
    texts = {
        "before_opt": lowered.compiler_ir(dialect="hlo").as_hlo_text(),
        "after_opt": compiled.as_text(),
    }
    return texts, pjrt_memory_stats(compiled)


def _lower_serial(target: LintTarget):
    from mpi_knn_tpu.backends.serial import (
        effective_tiles,
        knn_chunk_update,
        prepare_tiles,
    )
    from mpi_knn_tpu.ops.topk import init_topk

    _require_x64(target)
    cfg = _base_cfg(target)
    m = _lint_m(target)
    q_tile, c_tile = effective_tiles(cfg, m, LINT_NQ)
    q_tiles, qid_tiles, c_tiles, c_tile_ids, q_pad = prepare_tiles(
        np.zeros((m, LINT_D), np.float32),
        np.zeros((LINT_NQ, LINT_D), np.float32),
        np.full(LINT_NQ, -1, np.int32),
        cfg,
        q_tile,
        c_tile,
    )
    acc = jnp.float64 if target.dtype == "float64" else jnp.float32
    carry_d, carry_i = init_topk(q_pad, cfg.k, dtype=acc)
    qt = q_pad // q_tile
    lowered = knn_chunk_update.lower(
        q_tiles,
        qid_tiles,
        c_tiles,
        c_tile_ids,
        carry_d.reshape(qt, q_tile, cfg.k),
        carry_i.reshape(qt, q_tile, cfg.k),
        cfg,
    )
    m_pad = int(c_tiles.shape[0]) * c_tile
    meta = {"q_tile": q_tile, "c_tile": c_tile,
            "acc_bytes": _acc_bytes(target.dtype),
            "cost": _dense_cost(target, q_pad, m_pad, c_tile,
                                queries=LINT_NQ),
            **_mixed_meta(target, q_tile, c_tile)}
    if target.dtype == "bfloat16":
        # R7 allowance, named and measured (ISSUE 15): the bf16-at-rest
        # corpus and queries upcast ONCE to the f32 accumulation dtype —
        # XLA materializes both converted arrays whole, so the liveness
        # peak legitimately carries (m + nq)·d f32 elements beyond the
        # tile working set. This is exactly the residency cost DESIGN.md
        # §6 already documents for compute over compressed stores; the
        # allowance makes it a declared budget line instead of a
        # largest-input coincidence (the R2-floor audit's point).
        meta["peak_extra_elems"] = (m + LINT_NQ) * LINT_D
    return lowered, cfg, meta


def _lower_ring(target: LintTarget):
    from mpi_knn_tpu.backends.ring import (
        _ring_knn_sharded,
        parse_ring_mesh,
        ring_tiles,
    )
    from mpi_knn_tpu.parallel.mesh import make_ring_mesh

    _require_x64(target)
    if len(jax.devices()) < 2:
        raise UnsupportedTarget(
            "ring targets need a multi-device mesh (force the CPU platform "
            "with virtual devices first, as the lint CLI does)"
        )
    cfg = _base_cfg(target)
    m = _lint_m(target)
    mesh = make_ring_mesh(cfg.num_devices, axis_name=cfg.mesh_axis)
    q_axis, axis, dp, ring_n = parse_ring_mesh(mesh)
    q_tile, c_tile, q_pad, c_pad = ring_tiles(cfg, m, LINT_NQ, dp, ring_n)
    dtype = jnp.dtype(cfg.dtype)
    quantized = target.quant == "xfer-int8"
    corpus_args = (
        # the quantized driver quantizes at shard time: the rotation
        # program's corpus inputs ARE int8 codes + the per-row scales
        dict(corpus=jnp.zeros((c_pad, LINT_D), jnp.int8),
             corpus_scale=jnp.zeros((c_pad,), jnp.float32))
        if quantized
        else dict(corpus=jnp.zeros((c_pad, LINT_D), dtype),
                  corpus_scale=None)
    )
    lowered = _ring_knn_sharded.lower(
        jnp.zeros((q_pad, LINT_D), dtype),
        jnp.zeros((q_pad,), jnp.int32),
        corpus_args["corpus"],
        jnp.zeros((c_pad,), jnp.int32),
        cfg,
        target.backend == "ring-overlap",
        mesh,
        axis,
        q_tile,
        c_tile,
        q_axis=q_axis,
        corpus_scale=corpus_args["corpus_scale"],
    )
    meta = {
        "q_tile": q_tile,
        "c_tile": c_tile,
        "acc_bytes": _acc_bytes(target.dtype),
        "ring_n": ring_n,
        "ring_schedule": target.schedule,
        # the corpus block and its global-id row rotate together (a
        # quantized block adds its scale row — three permutes per
        # direction); the bidir schedule doubles that per torus direction,
        # with counter-directed source_target_pairs (R4 checks both the
        # count and the direction split)
        "expected_permutes": (
            (6 if target.schedule == "bidir" else 3) if quantized
            else (4 if target.schedule == "bidir" else 2)
        ),
        # per-device FLOP facts: queries shard over the ring (1-D mesh)
        # or the dp axis (2-D), the corpus block rotates; bidir runs two
        # dot sites (both travelers) for ⌊P/2⌋+1 scan trips
        "cost": _dense_cost(
            target,
            q_pad // (dp if q_axis is not None else ring_n),
            c_pad // ring_n,
            c_tile,
            queries=LINT_NQ,
            sites=2 if target.schedule == "bidir" else 1,
            trips=(ring_n // 2 + 1 if target.schedule == "bidir"
                   else ring_n),
        ),
        **_mixed_meta(target, q_tile, c_tile),
    }
    if quantized:
        meta["quantized"] = True
        # wire pricing: the largest rotation payload is the int8 code
        # block — (c_pad/ring_n rows × d) at 1 byte (ids/scales are d×
        # smaller); a permute above this is rotating float-width rows
        meta["permute_bytes_budget"] = (c_pad // ring_n) * LINT_D
    if target.schedule == "bidir":
        # R2: the second resident traveler is a REGISTERED intermediate —
        # two (c_pad/ring_n, d) blocks live per device instead of one. The
        # entry-input floor (the whole padded corpus) already dominates at
        # lint shapes, but the budget must name the allowance rather than
        # ride on that coincidence.
        block_elems = (c_pad // ring_n) * LINT_D
        meta["extra_elems"] = max(
            meta.get("extra_elems", 0), 2 * block_elems
        )
    return lowered, cfg, meta


def serve_resident_bytes(index) -> int:
    """R5's copy-census threshold for one resident index. For float
    stores this is the resident payload itself. A QUANTIZED store is
    4–8× smaller than the working set its own probe gather legitimately
    materializes (each query row gathers its own copy of its probed
    buckets — code-lane bytes, inside R2's wire-priced gather budget),
    so the census prices quantized cells at the f32-EQUIVALENT store
    bytes instead: "re-paying the corpus" means corpus-of-values-sized
    copies, and the wire-width gather staying under the f32 store is
    exactly the byte win the quantization bought."""
    n = index.nbytes_resident
    if getattr(index, "bucket_scales", None) is not None:
        n = max(n, index.partitions * index.bucket_cap * index.dim * 4)
    return n


# IVF lint shapes: 256 deterministic rows over 8 partitions probed at 2 —
# balanced buckets hold ~32 rows, so the probed width v = nprobe·cap ≥ 64
# keeps the mixed overfetch 4k=16 strictly narrower than v (the R3/R6
# contracts stay non-vacuous) while the probe bound stays well under the
# corpus (2/8 of it), making R2's strict budget a real claim.
LINT_M_IVF, LINT_PARTITIONS, LINT_NPROBE = 256, 8, 2


def _ivf_cfg(target: LintTarget) -> KNNConfig:
    return KNNConfig(
        k=LINT_K,
        query_tile=LINT_QUERY_TILE,
        precision_policy=target.policy,
        partitions=LINT_PARTITIONS,
        nprobe=LINT_NPROBE,
        kmeans_iters=2,  # lint cares about the search program, not fit
        # the at-rest quantization axis rides cfg.dtype (the bf16-store
        # convention): the lint index is genuinely quantized — codes,
        # scales, dequantized norms — so the cells certify the real store
        dtype=(target.quant if target.quant in ("int8", "int4")
               else "float32"),
    )


@functools.lru_cache(maxsize=None)
def _ivf_lint_index(cfg: KNNConfig):
    """One small trained IVFIndex per config — k-means on deterministic
    rows (seeded rng), shared by the one-shot and serve cells."""
    from mpi_knn_tpu.ivf import build_ivf_index

    rng = np.random.default_rng(0)
    data = (rng.standard_normal((LINT_M_IVF, LINT_D)) * 3).astype(np.float32)
    return build_ivf_index(data, cfg)


def _ivf_meta(index, cfg: KNNConfig, q_tile: int, q_pad: int,
              queries: int) -> dict:
    v = cfg.nprobe * index.bucket_cap
    meta = {
        "q_tile": q_tile,
        "c_tile": v,
        "acc_bytes": 4,
        "partitions": index.partitions,
        "dim": index.dim,
        "cost": _ivf_cost(index, cfg, q_pad, queries=queries),
        # R2 STRICT mode: the probe gather is the declared budget — the
        # program must not materialize beyond nprobe·bucket_cap·d per
        # query row (the sublinear claim, machine-checked)
        "budget_elems": q_tile * v * index.dim,
    }
    if index.bucket_scales is not None:
        meta["quantized"] = True
        # wire-priced gather bound: the probe gather moves CODE lanes
        # ((q_tile, nprobe, cap, packed_dim) int8 — 2× headroom for the
        # mixed path's survivor-row f32 gather, which is 4k/v of the
        # probed width at 4 bytes); an f32-sized bucket gather means the
        # store was dequantized before the gather
        meta["quant_gather_bytes"] = (
            2 * q_tile * v * index.buckets.shape[-1]
        )
    return meta


def _lower_ivf(target: LintTarget):
    from mpi_knn_tpu.ivf.search import (
        PROBE_FIELDS,
        _ivf_serve_jit,
        ivf_query_shapes,
    )
    from mpi_knn_tpu.ops.topk import init_topk_tiles

    if target.metric != "l2" or target.dtype != "float32":
        raise UnsupportedTarget(
            "the clustered (IVF) path is l2/float32 by its own contract "
            "(ivf/index.py rejects other combinations)"
        )
    cfg = _ivf_cfg(target)
    index = _ivf_lint_index(cfg)
    cfg = index.compatible_cfg(cfg)
    q_tile, q_pad = ivf_query_shapes(
        cfg, cfg.nprobe, index.bucket_cap, index.dim, LINT_NQ
    )
    qt = q_pad // q_tile
    carry_d, carry_i = init_topk_tiles(qt, q_tile, cfg.k, dtype=jnp.float32)
    lowered = _ivf_serve_jit.lower(
        jnp.zeros((qt, q_tile, index.dim), jnp.float32),
        jnp.full((qt, q_tile), -1, jnp.int32),
        carry_d,
        carry_i,
        jnp.zeros(PROBE_FIELDS, jnp.int32),
        index.centroids,
        index.centroid_sqs,
        index.buckets,
        index.bucket_ids,
        index.bucket_sqs,
        index.bucket_scales,
        index.onepass,
        index.mean_frac,
        cfg,
        cfg.nprobe,
    )
    return lowered, cfg, _ivf_meta(index, cfg, q_tile, q_pad, LINT_NQ)


# sharded-IVF lint shapes: the same trained 256-row/8-partition index,
# distributed over a 4-shard CPU mesh at the SAFE route cap (None →
# q_tile·nprobe — the default configuration users get; the exchange
# buffers then scale with nprobe, which is what makes the ladder's
# nprobe rung re-lint against a genuinely SMALLER per-shard budget)
LINT_IVF_SHARDS = 4


def _sharded_cfg(target: LintTarget) -> KNNConfig:
    return _ivf_cfg(target).replace(ivf_shards=LINT_IVF_SHARDS)


@functools.lru_cache(maxsize=None)
def _ivf_sharded_lint_index(cfg: KNNConfig):
    """The lint IVFIndex distributed over the 4-shard mesh — shared by
    the one-shot, serve, and ladder sharded cells."""
    from mpi_knn_tpu.ivf import shard_ivf_index

    plain = _ivf_lint_index(cfg.replace(ivf_shards=None, ivf_route_cap=None))
    return shard_ivf_index(plain, shards=cfg.ivf_shards)


def _ivf_sharded_meta(index, cfg: KNNConfig, q_tile: int,
                      route_cap: int, q_pad: int, queries: int) -> dict:
    from mpi_knn_tpu.ivf.sharded import (
        exchange_bytes_per_tile,
        exchange_elems,
        exchange_wire_args,
        expected_exchange_alltoalls,
    )

    v = cfg.nprobe * index.bucket_cap
    wire_dim, wire_itemsize, wire_scale = exchange_wire_args(index)
    meta = {
        "q_tile": q_tile,
        "c_tile": v,
        "acc_bytes": 4,
        "partitions": index.partitions,
        "dim": index.dim,
        "shards": index.shards,
        "route_cap": route_cap,
        # per-SHARD FLOP facts: q_pad is the global padded batch, every
        # shard runs the same program over its q_pad/shards slice
        "cost": _ivf_cost(index, cfg, q_pad // index.shards,
                          queries=queries),
        # R4: the candidate exchange is exactly these all-to-alls
        # (request table + rows/ids/norms returns; a quantized store adds
        # the scales return), full-ring groups, payload bytes inside this
        # declared per-tile budget — priced at the WIRE width (a
        # quantized store's rows are int8 code lanes)
        "expected_alltoalls": expected_exchange_alltoalls(index),
        "exchange_bytes_tile": exchange_bytes_per_tile(
            index.shards, route_cap, index.bucket_cap, wire_dim,
            wire_itemsize, wire_scale,
        ),
        # R2 STRICT, per shard: one resident tile's rerank working set or
        # its exchange buffers, whichever is larger — NOT the shard's
        # resident slice and never the global corpus
        "budget_elems": max(
            q_tile * v * index.dim,
            exchange_elems(
                index.shards, route_cap, index.bucket_cap, index.dim
            ),
        ),
    }
    if index.bucket_scales is not None:
        meta["quantized"] = True
        # wire-priced gather bound, per shard: the larger of the home
        # probe width and the owner-side exchange gather, in code-lane
        # bytes (2× headroom for the survivor f32 gather of the mixed
        # finish)
        meta["quant_gather_bytes"] = 2 * max(
            q_tile * v,
            index.shards * route_cap * index.bucket_cap,
        ) * index.buckets.shape[-1]
    return meta


def _require_sharded_mesh() -> None:
    if len(jax.devices()) < LINT_IVF_SHARDS:
        raise UnsupportedTarget(
            f"sharded-ivf targets need a {LINT_IVF_SHARDS}-device mesh "
            "(force the CPU platform with virtual devices first, as the "
            "lint CLI does)"
        )


def _lower_ivf_sharded(target: LintTarget):
    from jax.sharding import NamedSharding, PartitionSpec
    from mpi_knn_tpu.ivf.sharded import (
        N_STATS,
        _ivf_sharded_jit,
        sharded_query_shapes,
    )

    if target.metric != "l2" or target.dtype != "float32":
        raise UnsupportedTarget(
            "the clustered (IVF) path is l2/float32 by its own contract "
            "(ivf/index.py rejects other combinations)"
        )
    _require_sharded_mesh()
    cfg = _sharded_cfg(target)
    index = _ivf_sharded_lint_index(cfg)
    cfg = index.compatible_cfg(cfg)
    q_tile, q_pad, route_cap = sharded_query_shapes(
        cfg, cfg.nprobe, index.bucket_cap, index.dim, LINT_NQ, index.shards
    )
    qt = q_pad // q_tile
    qsh = NamedSharding(index.mesh, PartitionSpec(index.axis))
    sds = jax.ShapeDtypeStruct
    lowered = _ivf_sharded_jit.lower(
        sds((qt, q_tile, index.dim), jnp.float32, sharding=qsh),
        sds((qt, q_tile), jnp.int32, sharding=qsh),
        sds((qt, q_tile, cfg.k), jnp.float32, sharding=qsh),
        sds((qt, q_tile, cfg.k), jnp.int32, sharding=qsh),
        sds((N_STATS * index.shards,), jnp.int32, sharding=qsh),
        index.centroids,
        index.centroid_sqs,
        index.buckets,
        index.bucket_ids,
        index.bucket_sqs,
        index.bucket_scales,
        cfg,
        cfg.nprobe,
        index.mesh,
        index.axis,
        index.shards,
        route_cap,
    )
    return lowered, cfg, _ivf_sharded_meta(index, cfg, q_tile, route_cap,
                                           q_pad, LINT_NQ)


def _lower_serve(target: LintTarget):
    """Lower the serving engine's per-batch program for one cell through
    the PRODUCTION path: a real (small) CorpusIndex is built and
    ``serve.engine.lower_bucket`` emits the exact Lowered the executable
    cache would compile — a parallel lint-only reimplementation could
    drift and certify a program nobody serves."""
    from mpi_knn_tpu.serve import build_index
    from mpi_knn_tpu.serve.engine import lower_bucket

    # degradation-ladder rung programs are ordinary cells of the same
    # cache, lowered at the rung's knob values: the bucket/2 rung halves
    # the row bucket, the nprobe rung probes a single partition (which
    # also SHRINKS R2-strict's probed-bytes budget below — the rung must
    # fit its own smaller bound, not ride on the full rung's)
    bucket = LINT_NQ // 2 if target.ladder == "bucket" else LINT_NQ

    if target.backend == "ivf-sharded":
        # the sharded clustered serve cells lower through the production
        # lower_bucket like every other backend; the nprobe ladder rung
        # drops to 1 probe, and at the safe route cap that HALVES both
        # the exchange budget and the rerank working set — the rung must
        # fit its own smaller per-shard bound
        from mpi_knn_tpu.ivf.sharded import sharded_query_shapes

        if target.metric != "l2" or target.dtype != "float32":
            raise UnsupportedTarget(
                "the clustered (IVF) path is l2/float32 by its own "
                "contract (ivf/index.py rejects other combinations)"
            )
        _require_sharded_mesh()
        cfg = _sharded_cfg(target).replace(query_bucket=bucket, donate=True)
        if target.ladder == "nprobe":
            cfg = cfg.replace(nprobe=1)
        index = _ivf_sharded_lint_index(_sharded_cfg(target))
        cfg = index.compatible_cfg(cfg)
        lowered, q_pad, q_tile = lower_bucket(index, cfg, bucket)
        _, _, route_cap = sharded_query_shapes(
            cfg, cfg.nprobe, index.bucket_cap, index.dim, bucket,
            index.shards,
        )
        meta = {
            **_ivf_sharded_meta(index, cfg, q_tile, route_cap, q_pad,
                                bucket),
            "serve": True,
            "donated_params": (
                index.layout.donate_argnums if cfg.donate else ()
            ),
            "resident_bytes": serve_resident_bytes(index),
        }
        return lowered, cfg, meta

    if target.backend == "ivf":
        # the clustered index serves through the SAME bucket cache; its
        # per-batch program is lowered via the production lower_bucket so
        # R5's donation contract and R2/R6's probe discipline certify the
        # exact executable the cache compiles
        if target.metric != "l2" or target.dtype != "float32":
            raise UnsupportedTarget(
                "the clustered (IVF) path is l2/float32 by its own "
                "contract (ivf/index.py rejects other combinations)"
            )
        cfg = _ivf_cfg(target).replace(query_bucket=bucket, donate=True)
        if target.ladder == "nprobe":
            cfg = cfg.replace(nprobe=1)
        index = _ivf_lint_index(_ivf_cfg(target))
        cfg = index.compatible_cfg(cfg)
        lowered, q_pad, q_tile = lower_bucket(index, cfg, bucket)
        meta = {
            **_ivf_meta(index, cfg, q_tile, q_pad, bucket),
            "serve": True,
            "donated_params": (
                index.layout.donate_argnums if cfg.donate else ()
            ),
            "resident_bytes": serve_resident_bytes(index),
        }
        return lowered, cfg, meta

    if target.backend in RING_BACKENDS and len(jax.devices()) < 2:
        raise UnsupportedTarget(
            "ring serve targets need a multi-device mesh (force the CPU "
            "platform with virtual devices first, as the lint CLI does)"
        )
    _require_x64(target)
    # the one-shot lowerers call their backend core directly, but the
    # serving path resolves cfg.backend itself — pin it (the default
    # "auto" would quietly build every cell a ring-overlap index)
    cfg = _base_cfg(target).replace(
        backend=target.backend, query_bucket=bucket, donate=True
    )
    m = _lint_m(target)
    index = build_index(np.zeros((m, LINT_D), np.float32), cfg)
    frontend_meta = {}
    if target.frontend:
        # the front-end cell: the batch is formed by the PRODUCTION
        # coalescer — four tenant streams round-robined into one fill-
        # triggered batch — and the bucket lowered is the one THAT batch
        # selects. The no-new-programs contract is checked right here:
        # the coalesced batch must land on exactly the serve cell's
        # bucket (a mismatch means the front end would compile a program
        # the plain serve matrix never certified — a hard failure, not a
        # skip)
        from mpi_knn_tpu.frontend.coalesce import Coalescer
        from mpi_knn_tpu.serve.engine import bucket_rows

        co = Coalescer(max_batch_rows=bucket, max_wait_s=0.001)
        for i in range(4):
            co.admit(f"tenant-{i}", None, bucket // 4, now=0.0)
        cb = co.pop_ready(now=0.0)
        if cb is None or bucket_rows(cb.rows, cfg.query_bucket) != bucket:
            raise AssertionError(
                "front-end coalescing selected a bucket outside the "
                f"serve grid: coalesced {getattr(cb, 'rows', None)} rows "
                f"vs expected bucket {bucket} — the no-new-programs "
                "contract is broken"
            )
        frontend_meta = {
            "frontend": True,
            "coalesced_rows": cb.rows,
            "coalesced_requests": len(cb.parts),
            "coalesced_tenants": len(cb.tenants),
        }
    lowered, q_pad, q_tile = lower_bucket(index, index.cfg, bucket)
    if target.backend in RING_BACKENDS:
        q_axis, _raxis, dp, ring_n = index.ring_meta
        cost = _dense_cost(
            target,
            q_pad // (dp if q_axis is not None else ring_n),
            index.corpus_sharded.shape[0] // ring_n,
            index.c_tile,
            queries=bucket,
            sites=2 if target.schedule == "bidir" else 1,
            trips=(ring_n // 2 + 1 if target.schedule == "bidir"
                   else ring_n),
        )
    else:
        cost = _dense_cost(target, q_pad,
                           int(index.tiles.shape[0]) * index.c_tile,
                           index.c_tile, queries=bucket)
    meta = {
        "q_tile": q_tile,
        "c_tile": index.c_tile,
        "acc_bytes": _acc_bytes(target.dtype),
        "cost": cost,
        "serve": True,
        # R5: the scratch params MUST carry the donation in the header,
        # and nothing in the batch program may copy the resident corpus
        "donated_params": (
            index.layout.donate_argnums if index.cfg.donate else ()
        ),
        "resident_bytes": serve_resident_bytes(index),
        **_mixed_meta(target, q_tile, index.c_tile),
        **frontend_meta,
    }
    if target.backend in RING_BACKENDS:
        ring_n = index.ring_meta[3]
        quantized = target.quant == "xfer-int8"
        meta.update(
            ring_n=ring_n,
            ring_schedule=target.schedule,
            expected_permutes=(
                (6 if target.schedule == "bidir" else 3) if quantized
                else (4 if target.schedule == "bidir" else 2)
            ),
        )
        if quantized:
            meta["quantized"] = True
            meta["permute_bytes_budget"] = (
                index.corpus_sharded.shape[0] // ring_n * LINT_D
            )
    return lowered, index.cfg, meta


# one mutation chunk at lint scale: small, but several scatter rows per
# bucket so the in-place update is structurally faithful
LINT_MUTATE_CHUNK = 32


def _lower_mutate(target: LintTarget):
    """Lower one live-mutation cell through the PRODUCTION
    ``serve.mutate.lower_mutation`` — the exact Lowered the mutation
    executable cache compiles (the lower_bucket stance). Meta wires
    R5's donation contract (donated params per kind + the copy-census
    threshold) and R2-strict's touched-working-set budget, with the
    in-place scatter forms registered as buffer-forwarding plumbing."""
    from mpi_knn_tpu.serve import mutate as serve_mutate
    from mpi_knn_tpu.ivf import mutate as ivf_mutate

    kind = target.mutate
    if target.metric != "l2" or target.dtype != "float32":
        raise UnsupportedTarget(
            "the mutation cells lint the l2/float32 layouts (the quant "
            "and dtype axes ride the same programs)"
        )
    if target.backend == "serial":
        if kind == "compact":
            raise UnsupportedTarget(
                "the serial layout has no compact program (tombstones "
                "reclaim in place)"
            )
        from mpi_knn_tpu.serve import build_index

        cfg = _base_cfg(target).replace(backend="serial")
        index = build_index(np.zeros((LINT_M, LINT_D), np.float32), cfg)
        donated = (serve_mutate.SERIAL_UPSERT_DONATED
                   if kind == "upsert" else ivf_mutate.DELETE_DONATED)
    elif target.backend == "ivf":
        cfg = _ivf_cfg(target)
        index = _ivf_lint_index(cfg)
        cfg = index.compatible_cfg(cfg)
        donated = {
            "upsert": ivf_mutate.UPSERT_DONATED,
            "delete": ivf_mutate.DELETE_DONATED,
            "compact": ivf_mutate.COMPACT_DONATED,
        }[kind]
    elif target.backend == "ivf-sharded":
        _require_sharded_mesh()
        cfg = _sharded_cfg(target)
        index = _ivf_sharded_lint_index(cfg)
        cfg = index.compatible_cfg(cfg)
        donated = {
            "upsert": ivf_mutate.UPSERT_DONATED,
            "delete": ivf_mutate.DELETE_DONATED,
        }[kind]
    else:
        raise UnsupportedTarget(
            f"the {target.backend!r} layout refuses live mutation "
            "(serve.mutate raises — a registered restriction)"
        )
    bucket = (index.bucket_cap if kind == "compact"
              else LINT_MUTATE_CHUNK)
    lowered = serve_mutate.lower_mutation(index, cfg, bucket, kind)
    if kind == "compact":
        store = index.buckets
        budget = int(store.shape[0]) * index.bucket_cap * LINT_D
        q_tile, c_tile = index.bucket_cap, LINT_D
    elif kind == "delete":
        budget = bucket  # two small index vectors — nothing else
        q_tile, c_tile = bucket, 1
    else:
        budget = bucket * LINT_D  # the chunk rows (+ the same-sized
        # at-rest cast / norms intermediates, inside the slack)
        q_tile, c_tile = bucket, LINT_D
    meta = {
        "q_tile": q_tile,
        "c_tile": c_tile,
        "acc_bytes": 4,
        "mutate": kind,
        # mutation programs move rows, they do not score them: no dots
        # by design, and R8 certifies exactly that
        "cost": {"scheme": "zero", "queries": bucket},
        # R5: the donated store params MUST alias every output, and the
        # program must not copy the resident corpus
        "donated_params": donated,
        "resident_bytes": serve_resident_bytes(index),
        # R2 STRICT: the touched working set replaces the largest-input
        # floor — a mutation program materializing store-sized payload
        # (the headroom-overflow full-store gather) is a finding
        "budget_elems": budget,
        # the in-place update forms forward the donated buffer rather
        # than materialize new payload (XLA aliases them in place —
        # exactly what R5 certifies); everything that COMPUTES bytes
        # (gather, dot, broadcast, concatenate, copy) stays on the hook
        "strict_exempt_ops": (
            "scatter", "dynamic-update-slice", "fusion", "bitcast",
            "reshape",
        ),
    }
    return lowered, cfg, meta


_LOWERERS = {
    "serial": _lower_serial,
    "ring": _lower_ring,
    "ring-overlap": _lower_ring,
    "ivf": _lower_ivf,
    "ivf-sharded": _lower_ivf_sharded,
}


@functools.lru_cache(maxsize=None)
def lower_target(target: LintTarget):
    """(texts_by_stage, cfg, meta) for one matrix cell, cached — the test
    matrix and the CLI share lowerings within a process. Meta carries the
    compiled executable's PJRT memory stats (``pjrt_memory``) so R7's
    liveness analysis is cross-checked against the runtime's own
    accounting from the very compile that produced the after-opt text."""
    if target.mutate:
        lowered, cfg, meta = _lower_mutate(target)
    elif target.serve:
        lowered, cfg, meta = _lower_serve(target)
    else:
        try:
            lowerer = _LOWERERS[target.backend]
        except KeyError:
            raise UnsupportedTarget(
                f"no lowering registered for backend {target.backend!r}"
            ) from None
        lowered, cfg, meta = lowerer(target)
    texts, pjrt = hlo_texts_and_memory(lowered)
    if pjrt is not None:
        meta["pjrt_memory"] = pjrt
    return texts, cfg, meta


# ---------------------------------------------------------------------------
# Ring-driver lowerings for the overlap artifact (scripts/dump_ring_hlo.py):
# the resumable single-round jit alongside the headline scan driver, at the
# artifact's historical shapes.


def lower_ring_driver(driver: str, variant: str, schedule: str = "uni"):
    """HLO texts for one (driver, variant, schedule) of the ring-overlap
    artifact.

    ``driver``: ``"scan"`` (the headline ``lax.scan`` ring) or
    ``"one_round"`` (the resumable single-round jit). ``variant``:
    ``"overlap"`` or ``"blocking"``. ``schedule``: ``"uni"`` or ``"bidir"``
    (the full-duplex rotation; the one_round form is lowered at a
    non-degenerate round, ``merge_bwd=True``, where both travelers merge).
    """
    from mpi_knn_tpu.backends.ring import (
        _ring_knn_sharded,
        parse_ring_mesh,
        ring_tiles,
    )
    from mpi_knn_tpu.backends.ring_resumable import (
        _ring_one_round,
        _ring_one_round_bidir,
    )
    from mpi_knn_tpu.ops.topk import init_topk
    from mpi_knn_tpu.parallel.mesh import make_ring_mesh

    mesh = make_ring_mesh(None)
    q_axis, axis, dp, ring_n = parse_ring_mesh(mesh)
    cfg = KNNConfig(k=4, query_tile=8, corpus_tile=16,
                    ring_schedule=schedule)
    m, nq, d = LINT_M, LINT_NQ, LINT_D
    q_tile, c_tile, q_pad, c_pad = ring_tiles(cfg, m, nq, dp, ring_n)
    overlap = variant == "overlap"
    data = (
        jnp.zeros((q_pad, d), jnp.float32),
        jnp.zeros((q_pad,), jnp.int32),
        jnp.zeros((c_pad, d), jnp.float32),
        jnp.zeros((c_pad,), jnp.int32),
    )
    if driver == "one_round" and schedule == "bidir":
        lowered = _ring_one_round_bidir.lower(
            *data[:2],
            data[2],
            data[3],
            data[2],
            data[3],
            *init_topk(q_pad, cfg.k, dtype=jnp.float32),
            cfg,
            overlap,
            mesh,
            axis,
            q_tile,
            c_tile,
            q_axis=q_axis,
            rotate=True,
            merge_bwd=True,
        )
    elif driver == "one_round":
        lowered = _ring_one_round.lower(
            *data,
            *init_topk(q_pad, cfg.k, dtype=jnp.float32),
            cfg,
            overlap,
            mesh,
            axis,
            q_tile,
            c_tile,
            q_axis=q_axis,
            rotate=True,
        )
    else:
        lowered = _ring_knn_sharded.lower(
            *data, cfg, overlap, mesh, axis, q_tile, c_tile, q_axis=q_axis
        )
    return hlo_texts(lowered)
