"""Configuration for the framework.

Everything the reference hardcodes becomes a field here with the reference's
value as the default: ``k=30`` (``#define NN 30``, ``/root/reference/knn-serial.c:8``),
``num_classes=10`` (``#define max 10``, ``knn-serial.c:9``), zero-distance
self-exclusion (``knn-serial.c:86``). Changing k in the reference required
recompiling (SURVEY.md C12); here it is a dataclass field / CLI flag.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

BACKENDS = ("auto", "serial", "ring", "ring-overlap")
METRICS = ("l2", "cosine", "ip")
# dtypes a corpus block may travel the ring at (None = the compute dtype):
# bfloat16 halves the ICI bytes per hop; int8 is the block-scaled
# quantized level (codes + per-row f32 scales, ops/quant.py) at ~4× fewer
# bytes — and requires precision_policy="mixed" so the exact HIGHEST
# rerank finish absorbs the quantization noise (see __post_init__).
RING_TRANSFER_DTYPES = (None, "bfloat16", "float32", "int8")
TOPK_METHODS = ("exact", "approx", "approx-rerank", "block", "bf16")
PRECISION_POLICIES = ("exact", "mixed")
MERGE_SCHEDULES = ("stream", "twolevel")
RING_SCHEDULES = ("uni", "bidir")
TIE_BREAKS = ("nearest", "lowest", "quirk-serial", "quirk-mpi")
KMEANS_INITS = ("kmeans++", "random")


@dataclasses.dataclass(frozen=True)
class KNNConfig:
    """All knobs for an all-kNN run.

    Attributes:
      k: neighbors per query (reference: compile-time ``NN=30``).
      metric: ``l2`` (compared in squared space — same order, SURVEY.md Q10),
        ``cosine`` (1 − cosine similarity) or ``ip`` (maximum inner
        product: the engine's one ordering is "the k smallest, ascending",
        so the distance under ``ip`` is the NEGATED inner product
        ``-<q, c>``; a score, not a distance — no norms, no centring, no
        clamp at zero and no zero test: ``exclude_zero`` reads False under
        it whatever was passed, because a zero inner product is
        orthogonality, not identity. The dense ``serial`` backend only).
      backend: ``serial`` (single device), ``ring`` (blocking-parity ppermute
        ring), ``ring-overlap`` (pipelined ring with compute/comm overlap —
        the capability the reference's non-blocking variant intended but never
        achieved, SURVEY.md Q7), or ``auto``.
      query_tile / corpus_tile: on-device tiling of the (q × c) distance
        computation. Tiles are MXU-aligned (multiples of 128 recommended).
      dtype: input compute dtype. float32 default; bfloat16 for peak MXU
        throughput; float64 as the tie-adjudication debug mode (SURVEY.md Q10);
        ``uint8`` is the dense ``serial`` backend's LOSSLESS at-rest form for
        whole-number rows in [0, 255]: the tile stack rests one byte an
        element, every tile step widens its tile and computes in float32
        (``compute_dtype``), and the answers are the float32 index's.
      exclude_self: mask a candidate whose global id equals the query's own id
        (exact replacement for leave-one-out; robust under fp, unlike the
        reference's value test).
      exclude_zero: additionally mask candidates at (numerically) zero
        distance — the reference's semantics, which also drops exact duplicate
        points (``sqrt(S) != 0``, ``/root/reference/knn-serial.c:86``).
      zero_eps: threshold for ``exclude_zero`` in squared-distance space.
      topk_method: ``exact`` (the exact k smallest: per tile, for a small k
        over a wide tile, the lane-bin selection — per-lane partial
        selection on the VPU, an exactness certificate, ``lax.top_k`` only
        as a rare per-tile fallback; ``lax.top_k`` everywhere else — the
        choice follows the shapes alone, same values either way), ``approx``
        (``lax.approx_min_k``, the TPU-optimized partial reduction from the
        TPU-KNN paper — see PAPERS.md), ``approx-rerank`` (the paper's
        peak-FLOPs recipe: unaggregated approx preselect of 4k candidates
        at ``recall_target`` — which may sit far below the final recall
        you need, overfetch covers the gap — then an exact f32 rerank),
        ``block`` (exact two-level reduction via narrow per-block sorts),
        or ``bf16`` (near-exact half-width-key preselect + exact f32
        finish) — ops/topk.py ``smallest_k``.
      recall_target: recall target for ``approx`` / the preselect of
        ``approx-rerank``.
      topk_block: first-level sort width for ``block``.
      merge_schedule: ``stream`` (carry merged per corpus tile) or
        ``twolevel`` (local top-k per tile, one cascade merge at the end) —
        how the serial core combines per-tile candidates.
      tie_break: vote tie-break. ``nearest`` = correct majority vote with
        nearest-neighbor tie-break; ``lowest`` = lowest class id wins ties;
        ``quirk-serial`` / ``quirk-mpi`` bit-replicate the reference's buggy
        vote loops for parity experiments (SURVEY.md Q4).
      mesh_axis: name of the ring mesh axis for distributed backends.
      num_devices: ring size; None = all visible devices.
    """

    k: int = 30
    metric: str = "l2"
    backend: str = "auto"
    query_tile: int = 1024
    corpus_tile: int = 2048
    dtype: str = "float32"
    # None = auto: HIGHEST for f32/f64 inputs (recall-parity anchor; TPU's
    # DEFAULT truncates f32 operands to bf16 — measured ~0.3% recall@10 loss),
    # DEFAULT for bf16 inputs. Explicit "default"/"high"/"highest" overrides.
    # Where both centred operands of a tile step are bf16 numbers already
    # (whole-number rows: ops/distance.py bf16_exact, observed in the data,
    # no setting) the multi-pass dot would multiply zeros and one pass
    # returns the same sums: backends/serial.py masked_dist_tile.
    matmul_precision: Optional[str] = None
    # distance-pipeline precision structure (ops/rerank.py):
    # "exact"  — one-pass distances with the dot at matmul_precision
    #            (today's behavior, HIGHEST by default for f32);
    # "mixed"  — the TPU-KNN compress-and-rerank recipe: pass 1 computes the
    #            tile's distances with a single-pass bf16 MXU dot
    #            (Precision.DEFAULT, f32 accumulation) and overfetches 4k
    #            candidates per query; pass 2 gathers only the survivors'
    #            corpus rows and recomputes their distances exactly
    #            (HIGHEST, mask_tile semantics re-applied on exact values)
    #            before the final top-k. The O(q·c·d) FLOPs run at full MXU
    #            rate; only O(q·4k·d) runs multi-pass. Requires
    #            dtype="float32" and matmul_precision=None (the policy owns
    #            both dots' precisions); the recall gate measures the loss
    #            (>= 0.999 recall@10 on the tier-1 synthetic gate).
    precision_policy: str = "exact"
    # mean-center data before L2 distance computation (host-side, one pass).
    # L2 distances are translation-invariant, so results are mathematically
    # unchanged — but cancellation error in the matmul form scales with the
    # *centered* norms, which keeps fp noise (and the relative zero-distance
    # threshold) tight even when the data sits far from the origin. A
    # whole-number corpus is centred by its mean rounded to whole numbers.
    center: bool = True
    exclude_self: bool = True
    exclude_zero: bool = True
    zero_eps: float = 0.0
    topk_method: str = "exact"
    recall_target: float = 0.95
    # first-level sort width for topk_method="block" (an EXACT method: per-
    # block top-k then top-k over survivors — narrow VPU sorts instead of one
    # corpus-tile-wide sort; see ops/topk.py smallest_k)
    topk_block: int = 128
    # how the serial/resumable core combines per-corpus-tile candidates:
    # "stream" = carry threaded through the tile scan, one (carry ‖ tile)-wide
    # top-k per tile (the reference's accumulate-as-you-go shape,
    # /root/reference/knn-serial.c:86-91, batched); "twolevel" = local top-k
    # per tile, then ONE narrow cascade merge over all n_tiles·k survivors —
    # fewer wide reductions, chosen by on-chip A/B (BASELINE.md r3).
    merge_schedule: str = "twolevel"
    tie_break: str = "nearest"
    num_classes: int = 10
    mesh_axis: str = "ring"
    num_devices: Optional[int] = None
    # dtype of the corpus block while it circulates the ring. None = the
    # compute dtype (no cast). "bfloat16" halves the bytes every ppermute
    # moves over ICI/DCN (the EQuARX-style compressed-collective idea,
    # PAPERS.md) at the cost of one rounding of the block values per run
    # (blocks are cast ONCE before rotation, upcast for each round's
    # distance compute — error does not compound per hop). On integer-
    # valued data (raw pixels ≤ 255) the cast is exact; on centered data
    # it costs about what DEFAULT matmul precision costs (~0.3% recall@10,
    # BASELINE.md) — the recall gate measures it either way.
    # "int8" is the block-scaled quantized level (ops/quant.py): the block
    # is quantized ONCE at shard time to (int8 codes, f32 per-row scales),
    # BOTH circulate every schedule's permutes (~4× fewer wire bytes than
    # f32; R4 prices the payload at the wire dtype), and each round
    # dequantizes directly into the compress dot. Requires
    # precision_policy="mixed": the rerank is exact w.r.t. the
    # DEQUANTIZED rows, which bounds the loss at the measured gate
    # (>= 0.99 recall@10, tests/test_quant.py; the bytes-vs-recall
    # ladder is tabulated in DESIGN.md §6) — under "exact" there is no
    # rerank at all, so that combination is refused loudly.
    ring_transfer_dtype: Optional[str] = None
    # rotation schedule of the ring backends:
    # "uni"   — the reference's one-directional ring (rank → rank+1,
    #           mpi-knn-parallel_blocking.c:131): P rounds, each moving every
    #           block one hop, using HALF of each full-duplex ICI link.
    # "bidir" — full-duplex: every block circulates in BOTH torus directions
    #           at once (a +1 and a −1 ppermute issued in the same scan
    #           step), so at round r a device holds blocks i−r and i+r and
    #           merges both into its carry. Rounds drop from P to ⌊P/2⌋+1;
    #           total block-hops stay ~P·(P−1) but run concurrently over the
    #           two link directions, halving the exposed communication
    #           critical path (the EQuARX bidirectional-ring trick,
    #           PAPERS.md). Degenerate rounds merge ONCE: round 0 both
    #           travelers are the own block, and at even P the antipodal
    #           block arrives from both sides on the final round. Results
    #           are bit-identical to "uni" and to serial (property-tested);
    #           composes with overlap, ring_transfer_dtype, and
    #           precision_policy because the per-round block merge is the
    #           same shared tile reduction.
    ring_schedule: str = "uni"
    # one legal value, "xla": the ring is the ppermute ring with the
    # shared tile step in its rounds. The field stays only because
    # benchmark/configs/mnist8m-784-l2-ring4.json names it and the
    # benchmark builds KNNConfig(**fields) (ROADMAP Design 5).
    ring_fusion: str = "xla"
    # hard cap on query_tile × corpus_tile elements of one distance tile —
    # the HBM-resident intermediate a backend may materialize. 2^28 f32
    # elements = 1 GiB, safely inside a 16 GiB chip alongside the corpus.
    # Oversized configs are clamped by shrinking corpus_tile (see
    # backends.serial.cap_corpus_tile, shared with the ring backend), which
    # is what makes "corpus_tile = whole corpus" requests safe at SIFT1M
    # scale. query_tile is never clamped by this cap — keep it modest.
    max_tile_elems: int = 1 << 28
    # --- serving knobs (mpi_knn_tpu.serve) -------------------------------
    # base row bucket of the query-serving engine: every query batch is
    # padded up to the smallest query_bucket·2^j rows, and each (bucket,
    # config) pair is AOT-compiled exactly once — steady-state serving
    # issues zero recompiles because batch shapes quantize to a handful of
    # buckets instead of one executable per raw batch size.
    query_bucket: int = 1024
    # how many batches the streaming engine may dispatch ahead of the
    # oldest unconsumed result: depth 2 overlaps batch t+1's H2D transfer
    # with batch t's compute (double buffering); 1 is fully synchronous.
    dispatch_depth: int = 2
    # --- clustered (IVF) index knobs (mpi_knn_tpu.ivf) -------------------
    # partitions: number of k-means partitions of a clustered index — the
    # axis that makes per-query work SUBLINEAR in the corpus (TPU-KNN,
    # arXiv 2206.14286): queries score `partitions` centroids, then scan
    # only the `nprobe` nearest partitions with an exact rerank, so probed
    # bytes per query are nprobe/partitions of the corpus instead of all
    # of it. None = no clustering (every existing backend scans the full
    # corpus; nothing changes).
    partitions: Optional[int] = None
    # partitions probed per query. None = auto-tune at index build: the
    # smallest nprobe whose measured recall@k on a held-out corpus sample
    # reaches `recall_target` against the brute-force (nprobe=partitions)
    # oracle. nprobe == partitions degenerates to an exact full scan.
    nprobe: Optional[int] = None
    # k-means training knobs (ivf/kmeans.py): a FIXED Lloyd iteration count
    # (static scan length — the whole trainer lowers to one executable),
    # init scheme, and the PRNG seed threaded through init and any
    # re-seeding so training is bit-deterministic per seed.
    kmeans_iters: int = 25
    kmeans_init: str = "kmeans++"
    ivf_seed: int = 0
    # rows the partitioner trains on: a seeded draw of this many distinct
    # corpus rows (ivf/kmeans.py sample_rows, by ivf_seed), every row then
    # assigned to its nearest trained centroid — FAISS's rule for an IVF
    # coarse quantiser is at most 256 points a centroid. None = every row
    # (training and assignment one program, as before the field existed).
    kmeans_sample: Optional[int] = None
    # slots of one padded list of a clustered store, stated. The store is
    # (partitions, bucket_cap, d) whatever the lists hold, so its height
    # IS its memory and its programs' shapes; unstated it is the largest
    # list of THIS corpus (with headroom), an extreme value of thousands
    # that Lloyd's objective does not see (2.3 ... 3.7 x the mean over 24
    # seeds of one law). Stated, the build honours it: in every training
    # round but the last two a list over 5/6 of it is split in two and one
    # of the smallest given up for it (ivf/kmeans.py _split_largest; at
    # most partitions/64 a round), and the store is this tall whatever the
    # data. A list that outgrows it all the same raises the height (no row
    # is ever dropped). None = plain Lloyd rounds, the largest list
    # decides, as before the field existed.
    bucket_cap: Optional[int] = None
    # --- sharded clustered index (mpi_knn_tpu.ivf.sharded) ---------------
    # ivf_shards: distribute the clustered index's bucket store over this
    # many ring-mesh devices (TPU-KNN's deployment shape): each device
    # owns a contiguous, capacity-balanced slice of the trained partitions
    # at the same static bucket_cap layout, the (P, d) centroid table is
    # replicated on every shard, and each query tile is scored at its home
    # shard, routed to the devices owning its top-nprobe clusters via a
    # static all-to-all candidate exchange, and reranked exactly at home.
    # Corpus capacity scales with devices while per-query work stays
    # sublinear — the first configuration that does both. None = the
    # single-device clustered index (nothing changes). The shard layout is
    # DERIVED from (partitions, shards), never stored: one saved index
    # serves on any shard count.
    ivf_shards: Optional[int] = None
    # ivf_route_cap: static per-(home, owner)-shard route capacity of the
    # candidate exchange, PER QUERY TILE. The all-to-all's shape must be
    # static, so ragged routes pad up to this cap; probes beyond it are
    # DROPPED (id −1 mask semantics — graceful recall loss, counted by the
    # serving metrics as probe-cap overflow drops, never wrong answers).
    # None = the safe cap q_tile·nprobe (no probe can ever drop, at the
    # cost of a shards× exchange buffer); an explicit int trades bounded
    # exchange memory (shards·cap·bucket_bytes per tile — what lint R2's
    # per-shard strict budget prices) against drop risk under routing
    # skew.
    ivf_route_cap: Optional[int] = None
    # --- live mutation knobs (mpi_knn_tpu.serve.mutate) ------------------
    # bucket_headroom: fractional spare capacity built into every bucket
    # (clustered stores: bucket_cap = pad(max_cluster · (1+headroom));
    # serial tile stacks: extra padded rows beyond the corpus). Headroom
    # is what buys STATIC-SHAPE mutation: upserts land in pre-allocated
    # free slots via an in-place donated scatter instead of growing (and
    # therefore recompiling) the store. The default is 0.0 — headroom is
    # RENT (every padded slot rides the full fixed-shape FLOPs and
    # gather bytes; 0.5 measured ≈0.6× dense serve throughput on the
    # bench baseline), so a frozen corpus pays nothing and a mutable one
    # opts in explicitly (0.25–0.5 recommended; deletes/updates-in-place
    # need none, and a headroom-less index that overflows compacts-and-
    # grows under the session rather than failing).
    bucket_headroom: float = 0.0
    # base row bucket of the mutation executables: upsert/delete chunks
    # pad to the smallest mutation_bucket·2^j rows, so sustained churn at
    # ragged sizes quantizes to a handful of (bucket, kind) executables
    # in the same AOT cache as serve — zero steady-state compiles.
    mutation_bucket: int = 256
    # background re-cluster/compact triggers (serve.mutate.Compactor):
    # fire when ANY bucket's fill fraction reaches compact_fill_threshold
    # (headroom nearly exhausted — the next upsert burst would overflow)
    # or when tombstoned slots reach compact_tombstone_fraction of the
    # live rows (deletes have outpaced reuse; centroids drift from the
    # live set). Host-side pacing only — never reaches a lowering.
    compact_fill_threshold: float = 0.9
    compact_tombstone_fraction: float = 0.3
    # donate the per-batch top-k scratch to the serving executable
    # (donate_argnums): XLA aliases the scratch buffers to the outputs
    # (machine-checked from the module's input_output_alias by lint rule
    # R5), so steady-state serving reuses the same carry memory in place
    # instead of allocating per batch. Off only for debugging (donated
    # inputs are invalidated after the call).
    donate: bool = True
    # tags a query row may carry against an index built with tags
    # (``build_index(..., tags=)``, serve/tags.py): the static width of the
    # filtered batch program's per-row operand. An index without tags
    # never reads it, and it is no part of such an index's executable
    # fingerprint (serve/aotcache.py).
    max_query_tags: int = 2
    # RANGE SEARCH (backends/range_scan.py): 0 — off, an index answers
    # k-NN alone. N > 0 — the dense ``serial`` index under L2 over
    # whole-number rows also answers requests that name a radius with
    # EVERY live row at a squared distance strictly under it, and N is the
    # most one query row may be answered with: a row with more is refused
    # by name with its true count, never cut. A request without a radius
    # is answered with its k nearest, as ever.
    range_cap: int = 0

    def __post_init__(self):
        if self.backend not in BACKENDS:
            gone = (
                " — the Pallas backend is gone: backend='serial' runs the "
                "Mosaic scan kernel wherever the shapes allow"
                if self.backend == "pallas" else ""
            )
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {self.backend!r}{gone}"
            )
        if self.metric not in METRICS:
            raise ValueError(f"metric must be one of {METRICS}, got {self.metric!r}")
        if self.metric == "ip":
            self._refuse_under_ip()
            # the metric decides: a score of 0 is two orthogonal rows, not
            # a row met again, so there is no zero test to switch on
            object.__setattr__(self, "exclude_zero", False)
        if self.topk_method not in TOPK_METHODS:
            raise ValueError(
                f"topk_method must be one of {TOPK_METHODS}, got {self.topk_method!r}"
            )
        if self.tie_break not in TIE_BREAKS:
            raise ValueError(
                f"tie_break must be one of {TIE_BREAKS}, got {self.tie_break!r}"
            )
        if self.ring_transfer_dtype not in RING_TRANSFER_DTYPES:
            # the error text enumerates the ACCEPTED set (RING_TRANSFER_
            # DTYPES) instead of hand-listing values: a hand-written list
            # already drifted once when int8 landed (ISSUE 9 satellite)
            raise ValueError(
                f"ring_transfer_dtype must be one of {RING_TRANSFER_DTYPES}, "
                f"got {self.ring_transfer_dtype!r}"
            )
        if (
            self.ring_transfer_dtype == "int8"
            and self.precision_policy != "mixed"
        ):
            raise ValueError(
                "ring_transfer_dtype='int8' requires precision_policy="
                "'mixed': the block-scaled quantized block is dequantized "
                "into the compress dot and the exact HIGHEST rerank finish "
                "absorbs the quantization noise — under precision_policy="
                f"{self.precision_policy!r} there is no rerank, so int8 "
                "transfer would silently degrade every distance instead of "
                "only the preselect keys"
            )
        if self.ring_schedule not in RING_SCHEDULES:
            raise ValueError(
                f"ring_schedule must be one of {RING_SCHEDULES}, got "
                f"{self.ring_schedule!r}"
            )
        if self.ring_fusion != "xla":
            raise ValueError(
                f"ring_fusion must be 'xla', got {self.ring_fusion!r}: the "
                "fused Pallas ring is gone — backend='ring-overlap' is the "
                "ring, and its rounds take the Mosaic scan kernel wherever "
                "the shapes allow"
            )
        if self.merge_schedule not in MERGE_SCHEDULES:
            raise ValueError(
                f"merge_schedule must be one of {MERGE_SCHEDULES}, got "
                f"{self.merge_schedule!r}"
            )
        if self.precision_policy not in PRECISION_POLICIES:
            raise ValueError(
                f"precision_policy must be one of {PRECISION_POLICIES}, got "
                f"{self.precision_policy!r}"
            )
        if self.dtype in ("int8", "int4") and self.partitions is None:
            raise ValueError(
                f"dtype={self.dtype!r} is the clustered (IVF) store's "
                "block-scaled, lossy AT-REST compression (ivf/index.py): "
                "the dense backends' one narrow form is the lossless "
                "dtype='uint8' (whole-number rows in [0, 255], one byte an "
                "element, widened in every tile step), and they would "
                "score raw codes — set partitions to build a clustered "
                "index, dtype='uint8' for a byte-valued corpus, or use "
                "ring_transfer_dtype='int8' for wire-only compression"
            )
        if self.dtype == "uint8":
            self._refuse_under_uint8()
        if self.range_cap:
            self._refuse_under_range()
        if self.precision_policy == "mixed":
            if self.dtype not in ("float32", "int8", "int4"):
                raise ValueError(
                    "precision_policy='mixed' requires dtype='float32' "
                    "(or the clustered store's at-rest 'int8'/'int4', "
                    "whose dequantized candidates the compress dot "
                    f"consumes in f32) — got {self.dtype!r}: bf16 inputs "
                    "already run the single-pass dot everywhere, and the "
                    "f64 debug mode must not downcast"
                )
            if self.matmul_precision is not None:
                raise ValueError(
                    "precision_policy='mixed' owns both dot precisions "
                    "(DEFAULT compress, HIGHEST rerank); matmul_precision "
                    f"must be None, got {self.matmul_precision!r}"
                )
        if self.query_bucket < 1:
            raise ValueError(
                f"query_bucket must be >= 1, got {self.query_bucket}"
            )
        if self.dispatch_depth < 1:
            raise ValueError(
                f"dispatch_depth must be >= 1, got {self.dispatch_depth}"
            )
        if self.kmeans_init not in KMEANS_INITS:
            raise ValueError(
                f"kmeans_init must be one of {KMEANS_INITS}, got "
                f"{self.kmeans_init!r}"
            )
        if self.partitions is not None and self.partitions < 1:
            raise ValueError(
                f"partitions must be >= 1, got {self.partitions}"
            )
        if self.nprobe is not None:
            if self.partitions is None:
                raise ValueError(
                    "nprobe without partitions is meaningless: nprobe "
                    "selects how many of the clustered index's partitions "
                    "to scan — set partitions too"
                )
            if not 1 <= self.nprobe <= self.partitions:
                raise ValueError(
                    f"nprobe must be in [1, partitions={self.partitions}], "
                    f"got {self.nprobe}"
                )
        if self.partitions is not None and self.metric != "l2":
            raise ValueError(
                "a clustered (IVF) index supports metric='l2' only: the "
                "k-means partitioner and the centroid score are L2 "
                f"geometry (got metric={self.metric!r}) — leave partitions "
                "unset for the exact dense index"
            )
        if self.kmeans_sample is not None and self.kmeans_sample < max(
                1, self.partitions or 1):
            raise ValueError(
                "kmeans_sample must be at least the partitions it trains "
                f"({self.partitions}), got {self.kmeans_sample}"
            )
        if self.bucket_cap is not None and self.bucket_cap < 1:
            raise ValueError(
                f"bucket_cap must be >= 1 slot, got {self.bucket_cap}")
        if self.kmeans_iters < 1:
            raise ValueError(
                f"kmeans_iters must be >= 1, got {self.kmeans_iters}"
            )
        if self.ivf_shards is not None:
            if self.partitions is None:
                raise ValueError(
                    "ivf_shards without partitions is meaningless: sharding "
                    "distributes a clustered index's partition buckets over "
                    "the ring mesh — set partitions too"
                )
            if self.ivf_shards < 1:
                raise ValueError(
                    f"ivf_shards must be >= 1, got {self.ivf_shards}"
                )
        if self.ivf_route_cap is not None:
            if self.ivf_shards is None:
                raise ValueError(
                    "ivf_route_cap without ivf_shards is meaningless: the "
                    "route cap bounds the sharded candidate exchange — on "
                    "a single-device clustered index nothing is routed"
                )
            if self.ivf_route_cap < 1:
                raise ValueError(
                    f"ivf_route_cap must be >= 1, got {self.ivf_route_cap}"
                )
        if not self.bucket_headroom >= 0.0:
            raise ValueError(
                f"bucket_headroom must be >= 0, got {self.bucket_headroom}"
            )
        if self.mutation_bucket < 1:
            raise ValueError(
                f"mutation_bucket must be >= 1, got {self.mutation_bucket}"
            )
        if not 0.0 < self.compact_fill_threshold <= 1.0:
            raise ValueError(
                "compact_fill_threshold must be in (0, 1], got "
                f"{self.compact_fill_threshold}"
            )
        if not self.compact_tombstone_fraction > 0.0:
            raise ValueError(
                "compact_tombstone_fraction must be > 0, got "
                f"{self.compact_tombstone_fraction}"
            )
        if self.max_query_tags < 1:
            raise ValueError(
                f"max_query_tags must be >= 1, got {self.max_query_tags}")
        if self.topk_block < 1:
            raise ValueError(f"topk_block must be >= 1, got {self.topk_block}")
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")

    def _refuse_under_ip(self):
        """What ``metric="ip"`` cannot be combined with yet, each with its
        reason and what to pass instead (ROADMAP Reach keeps the list by
        mechanism). A clustered index is refused where every metric but
        L2 is (``partitions``, below)."""
        if self.backend in ("ring", "ring-overlap"):
            raise ValueError(
                f"metric='ip' does not run on backend={self.backend!r}: a "
                "ring round norms its travelling block for L2 or "
                "normalises it for cosine and has no form that does "
                "neither, and no reference or measurement holds one — use "
                "backend='serial'"
            )
        if self.precision_policy != "exact":
            raise ValueError(
                "metric='ip' requires precision_policy='exact': the "
                "compress pass of 'mixed' overfetches by a bf16 L2 or "
                "cosine key (ops/rerank.py) and has no inner-product form"
            )
        if self.dtype not in ("float32", "float64"):
            raise ValueError(
                f"metric='ip' requires dtype='float32' (or the 'float64' "
                f"debug mode), got {self.dtype!r}: a bfloat16 stack under "
                "an unbounded score has no measured recall, and the "
                "quantised at-rest levels ('int8'/'int4') belong to the "
                "clustered store, which is L2's"
            )
        if self.bucket_headroom:
            raise ValueError(
                "an index under metric='ip' is frozen (serve/mutate.py "
                f"IP_FROZEN): bucket_headroom={self.bucket_headroom} "
                "reserves slots for upserts it would refuse — build with "
                "bucket_headroom=0"
            )

    def _refuse_under_uint8(self):
        """What ``dtype="uint8"`` — the dense ``serial`` stack at one byte
        an element — cannot be combined with yet, each with its reason and
        what to pass instead (ROADMAP Reach keeps the list by mechanism)."""
        if self.metric != "l2":
            raise ValueError(
                f"dtype='uint8' requires metric='l2', got {self.metric!r}: "
                "a byte stack keeps the CENTRED rows' squared norms and its "
                "tile steps widen by a whole-number offset; cosine's "
                "inverse norms and an inner product's uncentred dot have "
                "no widened form, reference or measurement — use "
                "dtype='float32'"
            )
        if self.backend in ("ring", "ring-overlap"):
            raise ValueError(
                f"dtype='uint8' does not run on backend={self.backend!r}: "
                "a ring round norms and rotates a float block and has no "
                "form that carries bytes and their offset (the narrow wire "
                "is ring_transfer_dtype) — use backend='serial'"
            )
        if self.partitions is not None:
            raise ValueError(
                "dtype='uint8' is the DENSE index's lossless at-rest form: "
                "a clustered (IVF) store compresses at rest with the "
                "block-scaled dtype='int8'/'int4' — leave partitions unset, "
                "or pick one of those"
            )
        if self.precision_policy != "exact":
            raise ValueError(
                "dtype='uint8' requires precision_policy='exact': whole-"
                "number rows are ranked exactly in ONE bf16 pass already, "
                "and the compress pass of 'mixed' gathers float32 rows "
                "from the stack for its rerank (ops/rerank.py)"
            )
        if self.bucket_headroom:
            raise ValueError(
                "an index at dtype='uint8' is frozen (serve/mutate.py "
                f"U8_FROZEN): bucket_headroom={self.bucket_headroom} "
                "reserves slots for upserts it would refuse — build with "
                "bucket_headroom=0"
            )

    def _refuse_under_range(self):
        """What range search (``range_cap`` > 0) does not run on yet, each
        with its reason and what to pass instead (ROADMAP Reach keeps the
        list by mechanism). What only the DATA can say is refused where the
        data is seen: fractional float32 rows and a width whose sums could
        pass 2^24 at the build (``serve/index.py refuse_range_build``),
        a predicate there too, every write in ``serve/mutate.py
        RANGE_FROZEN``."""
        if self.range_cap < 0:
            raise ValueError(
                f"range_cap must be >= 0, got {self.range_cap}")
        if self.metric != "l2":
            raise ValueError(
                f"range search requires metric='l2', got {self.metric!r}: a "
                "radius is a squared L2 distance; cosine's 1 - similarity "
                "and an inner product's negated score live on other scales "
                "and have no reference, radius or measurement here — leave "
                "range_cap at 0 under them"
            )
        if self.backend in ("ring", "ring-overlap"):
            raise ValueError(
                f"range search does not run on backend={self.backend!r}: a "
                "ring round merges a fixed k a row into a carried (rows, k) "
                "pair and has no form that hands on lists of no fixed "
                "length — use backend='serial'"
            )
        if self.partitions is not None:
            raise ValueError(
                "range search runs over the DENSE index: a clustered (IVF) "
                "store's probe visits nprobe lists and would miss rows "
                "within the radius in the others, which 'every row' "
                "forbids — leave partitions unset"
            )
        if self.precision_policy != "exact":
            raise ValueError(
                "range search requires precision_policy='exact': the "
                "compress pass of 'mixed' ranks by a rounded key and a row "
                "near the radius needs the exact value"
            )
        if self.dtype not in ("uint8", "float32"):
            raise ValueError(
                f"range search requires dtype='uint8' or 'float32' over "
                f"whole-number rows, got {self.dtype!r}: a bfloat16 or "
                "quantised stack rounds its rows, and a row near the "
                "radius needs the exact value"
            )
        if self.bucket_headroom:
            raise ValueError(
                "an index that answers range search is frozen "
                "(serve/mutate.py RANGE_FROZEN): bucket_headroom="
                f"{self.bucket_headroom} reserves slots for upserts it "
                "would refuse — build with bucket_headroom=0"
            )

    @property
    def compute_dtype(self) -> str:
        """The dtype query rows come in and tile steps compute in: ``dtype``
        itself, but float32 over a ``uint8`` stack, whose bytes are an
        at-rest form and no arithmetic's."""
        return "float32" if self.dtype == "uint8" else self.dtype

    def replace(self, **kw) -> "KNNConfig":
        return dataclasses.replace(self, **kw)


class RangeCapError(ValueError):
    """Query rows whose results pass ``range_cap``: ``rows`` is ``[(row,
    true count), ...]``. Refused by name, never cut."""

    def __init__(self, rows, cap: int):
        self.rows, self.cap = list(rows), int(cap)
        shown = ", ".join(f"row {r}: {n}" for r, n in self.rows[:8])
        more = "" if len(self.rows) <= 8 else (
            f" and {len(self.rows) - 8} more")
        super().__init__(
            f"{len(self.rows)} query row(s) have more than range_cap="
            f"{cap} results within the radius ({shown}{more}); nothing is "
            "cut: narrow the radius, or serve with a larger --range-cap")
