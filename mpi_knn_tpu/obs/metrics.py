"""Process-wide metrics registry — counters, gauges, fixed-bucket
histograms, and the central ``jax.monitoring`` compile capture.

Why fixed buckets: serving percentiles must be *assertable* — a test (or
a CI gate) that says "p99 under 50 ms" needs the same answer from the
same observations every time, on every platform. A fixed-bucket histogram
quantizes each observation into a predetermined bucket, so
:meth:`Histogram.percentile` is a deterministic function of the counts
(it returns the upper bound of the bucket the quantile falls in), never
an interpolation over a float stream.

Why one registry: before this module, the compile-counter machinery was
hand-rolled three times (``tests/test_serve.py``, ``tests/test_ivf.py``,
``tests/test_resilience.py``) and the serve/bench/resilience layers each
kept private ad-hoc counters. :func:`get_registry` is the single
process-wide sink; :func:`install_jax_compile_listener` routes the XLA
backend-compile events (count + duration histogram) into it exactly
once, so "zero steady-state compiles" is a registry fact any consumer
(tests, ``mpi-knn metrics``, the doctor verdict) can read.

Export: :meth:`MetricsRegistry.snapshot` is the JSON form;
:func:`to_prometheus` renders a snapshot as Prometheus text exposition
format, and :func:`parse_prometheus` is the strict re-parser the CI gate
uses to prove the exposition is well-formed.

Labels (the multi-tenant front end's axis): counters and gauges accept a
``labels`` dict — the metric is registered under its canonical sample
name (``name{key="value"}``, keys sorted), so every (name, labels)
combination is its own monotonic series and the exposition emits one
``HELP``/``TYPE`` header per base name. Histograms do NOT take labels:
a labeled histogram's ``_bucket`` suffix belongs after the base name in
the exposition (``name_bucket{le=...,tenant=...}``), which this
registry's name-keyed storage cannot express — per-tenant latency lives
in ``ServeSession.tenant_stats`` instead.

No jax import at module load (the resilience supervisors import through
here); jax is touched only inside :func:`install_jax_compile_listener`.
"""

from __future__ import annotations

import contextlib
import json
import math
import threading

# latency histograms (seconds): sub-ms serving batches up to the
# multi-second compile/build tail; +Inf overflow bucket is implicit
DEFAULT_LATENCY_BUCKETS_S = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
    0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)
# compile durations reach minutes on first-touch TPU lowering
COMPILE_BUCKETS_S = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0)

# tile steps by the distance dot's path (MetricsRegistry.count_dist_steps)
DIST_STEPS = "knn_dist_tile_steps_total"
# a count's columns
DIST_PATHS = ("onepass", "multipass", "cosine", "fused", "ip", "u8",
              "fused_screen")
# query-tile merges by what became of the selection their scans carried
# (MetricsRegistry.count_select_tiles)
SELECT_TILES = "knn_select_query_tiles_total"
SELECT_PATHS = ("carried", "rescanned")  # a count's columns
# chunks of the distance tiles by what became of them in *bins* under the
# row bound (MetricsRegistry.count_bins_chunks)
BINS_CHUNKS = "knn_select_bins_chunks_total"
BINS_PATHS = ("inserted", "skipped")  # a count's columns
# query rows by the verdict of the screen's certificate
# (MetricsRegistry.count_screen_rows)
SCREEN_ROWS = "knn_screen_rows_total"
SCREEN_RESULTS = ("certified", "flagged")  # a count's columns

JAX_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a program found in jax's persistent compilation cache (at jax 0.9.0 the
# compile event above fires around the lookup too, hit or miss)
JAX_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _valid_metric_name(name: str) -> bool:
    # letters, digits, "_" and ":", not starting with a digit. One C call
    # a test, not a Python loop over the characters: every labelled
    # lookup on the serving path comes through here
    return not name[:1].isdigit() and (
        name.replace("_", "a").replace(":", "a").isalnum()
    )


def sample_name(name: str, labels: dict | None = None) -> str:
    """The canonical exposition sample name for (name, labels):
    ``name`` bare, or ``name{k="v",...}`` with keys sorted so the same
    label set always produces the same registry key. Label values that
    would need exposition escaping (quotes, backslashes, newlines) are
    rejected loudly — a tenant id is an identifier, not free text."""
    if not _valid_metric_name(name):
        raise ValueError(f"bad metric name {name!r}")
    if not labels:
        return name
    parts = []
    for k in sorted(labels):
        if not _valid_metric_name(k) or ":" in k:
            raise ValueError(f"bad label name {k!r} for metric {name!r}")
        v = str(labels[k])
        if '"' in v or "\\" in v or "\n" in v:
            raise ValueError(
                f"label value {v!r} for {name}{{{k}}} needs escaping; "
                "use plain identifier-like values"
            )
        parts.append(f'{k}="{v}"')
    return name + "{" + ",".join(parts) + "}"


class Counter:
    """Monotonic counter. Negative increments are a caller bug and raise
    (a counter that can go down silently corrupts every rate read off
    it)."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, n: float = 1.0) -> None:
        if not (n >= 0.0) or not math.isfinite(n):
            raise ValueError(f"counter {self.name}: bad increment {n!r}")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def snapshot(self) -> dict:
        # under the lock (host-lint H1): /metrics scrapes race inc()
        # from serving threads, and an unguarded read here is the torn-
        # snapshot bug the host concurrency lint exists to catch
        with self._lock:
            return {"kind": self.kind, "help": self.help,
                    "value": self._value}


class Gauge:
    """Last-set value (queue depth, current ladder rung index, …)."""

    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v: float) -> None:
        if not math.isfinite(v):
            raise ValueError(f"gauge {self.name}: non-finite value {v!r}")
        with self._lock:
            self._value = float(v)

    def add(self, n: float) -> None:
        if not math.isfinite(n):
            raise ValueError(f"gauge {self.name}: non-finite delta {n!r}")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def snapshot(self) -> dict:
        with self._lock:
            return {"kind": self.kind, "help": self.help,
                    "value": self._value}


class Histogram:
    """Fixed-bucket histogram (upper bounds + implicit +Inf overflow).

    Percentiles are deterministic: the quantile's bucket upper bound, a
    pure function of the counts — assertable in tests and stable across
    runs/platforms, which a streaming-quantile sketch is not.
    """

    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets=DEFAULT_LATENCY_BUCKETS_S):
        bounds = tuple(float(b) for b in buckets)
        if not bounds or any(not math.isfinite(b) for b in bounds) or \
                list(bounds) != sorted(set(bounds)):
            raise ValueError(
                f"histogram {name}: buckets must be finite, strictly "
                f"increasing and non-empty, got {buckets!r}"
            )
        self.name = name
        self.help = help
        self.buckets = bounds
        self._lock = threading.Lock()
        self._counts = [0] * (len(bounds) + 1)  # last = +Inf overflow
        self._sum = 0.0
        self._count = 0

    def observe(self, v: float) -> None:
        v = float(v)
        if not math.isfinite(v):
            # a NaN latency is an upstream bug; swallowing it would make
            # every percentile read off this histogram silently wrong
            raise ValueError(f"histogram {self.name}: non-finite {v!r}")
        i = len(self.buckets)
        for j, b in enumerate(self.buckets):
            if v <= b:
                i = j
                break
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def percentile(self, q: float) -> float:
        """The upper bound of the bucket holding the q-th percentile
        (q in [0, 100]); +Inf when it falls in the overflow bucket,
        NaN when the histogram is empty."""
        if not 0.0 <= q <= 100.0:
            raise ValueError(f"percentile {q!r} not in [0, 100]")
        with self._lock:
            count = self._count
            counts = list(self._counts)
        if count == 0:
            return math.nan
        rank = max(1, math.ceil(count * q / 100.0))
        cum = 0
        for j, c in enumerate(counts):
            cum += c
            if cum >= rank:
                return (
                    self.buckets[j] if j < len(self.buckets) else math.inf
                )
        return math.inf  # unreachable

    def snapshot(self) -> dict:
        # counts/sum/count must come from ONE critical section: a scrape
        # racing observe() otherwise exports counts summing to count±1 —
        # a torn histogram no strict re-parser can detect (the numbers
        # are each individually plausible)
        with self._lock:
            return {
                "kind": self.kind,
                "help": self.help,
                "buckets": list(self.buckets),
                "counts": list(self._counts),
                "sum": self._sum,
                "count": self._count,
            }


class MetricsRegistry:
    """Name → metric, get-or-create. A name re-requested with a
    different kind (or different histogram buckets) raises — two call
    sites silently sharing a name across kinds would corrupt both."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict[str, object] = {}
        # base family name -> metric class: the kind-collision guard must
        # key on the part BEFORE the label set, or a labeled counter and
        # a bare gauge sharing one base would coexist and render a
        # mixed-kind family under a single TYPE header (malformed
        # exposition a real scraper mis-types)
        self._kinds: dict[str, type] = {}
        # callables run at the head of every snapshot (on_snapshot)
        self._settlers: list = []

    def on_snapshot(self, settle) -> None:
        """Run ``settle(registry)`` at the head of every :meth:`snapshot`
        (so of every ``/metrics`` read and every written report): for
        totals that are kept where no lock may be taken — the garbage
        collector's hook, ``obs/host.py`` — and carried into their
        counters when somebody looks. Registered once however often it is
        asked; :meth:`clear` keeps it."""
        with self._lock:
            if settle not in self._settlers:
                self._settlers.append(settle)

    def _get_or_create(self, cls, name, help, **kw):
        base = name.split("{", 1)[0]
        with self._lock:
            known = self._kinds.get(base)
            if known is not None and known is not cls:
                raise ValueError(
                    f"metric family {base!r} already registered as "
                    f"{known.kind}, requested {cls.kind}"
                )
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help=help, **kw)
                self._metrics[name] = m
                self._kinds[base] = cls
                return m
        if not isinstance(m, cls):
            raise ValueError(
                f"metric {name!r} already registered as {m.kind}, "
                f"requested {cls.kind}"
            )
        if kw.get("buckets") is not None and \
                tuple(float(b) for b in kw["buckets"]) != m.buckets:
            raise ValueError(
                f"histogram {name!r} already registered with different "
                "buckets"
            )
        return m

    def counter(self, name: str, help: str = "",
                labels: dict | None = None) -> Counter:
        return self._get_or_create(Counter, sample_name(name, labels), help)

    def gauge(self, name: str, help: str = "",
              labels: dict | None = None) -> Gauge:
        return self._get_or_create(Gauge, sample_name(name, labels), help)

    def histogram(self, name: str, help: str = "",
                  buckets=DEFAULT_LATENCY_BUCKETS_S,
                  labels: dict | None = None) -> Histogram:
        if labels:
            raise ValueError(
                f"histogram {name!r}: labels are not supported (the "
                "_bucket suffix belongs between the base name and the "
                "label set, which name-keyed storage cannot express) — "
                "keep per-label latency in caller state instead"
            )
        return self._get_or_create(
            Histogram, sample_name(name), help, buckets=buckets
        )

    def snapshot(self) -> dict:
        """JSON-able snapshot of every metric (sorted by name — the
        stable on-disk form ``mpi-knn metrics`` renders)."""
        with self._lock:
            settlers = list(self._settlers)
        for settle in settlers:
            settle(self)
        with self._lock:
            items = sorted(self._metrics.items())
        return {
            "schema": "mpi_knn_tpu.obs.metrics/1",
            "metrics": {name: m.snapshot() for name, m in items},
        }

    def to_prometheus(self) -> str:
        return to_prometheus(self.snapshot())

    def clear(self) -> None:
        """Drop every metric (test isolation / a fresh reporting
        window)."""
        with self._lock:
            self._metrics.clear()
            self._kinds.clear()

    def count_dist_steps(self, steps) -> None:
        """Add a dispatch's tile steps to ``knn_dist_tile_steps_total
        {path="onepass"|"multipass"|"cosine"|"fused"|"ip"|"u8"|"fused_screen"}``.
        ``steps`` is what
        the tile programs report with their answer (``KNNResult.dist_steps``,
        ``BatchResult.dist_steps``): ints ``[one-pass, multi-pass]`` — from
        a cosine program ``[0, 0, cosine]``, a static count; from a program
        whose one-pass steps run inside the kernel that walks the stack
        ``[0, multi-pass, 0, fused]``, from an inner-product program
        ``[0, 0, 0, 0, ip]``, from one whose screened steps run inside that
        kernel's three-pass form ``[0, 0, 0, 0, 0, 0, fused_screen]`` — one
        row a device. The device decides the L2 path, so call this where the
        answer has been fetched (a server's retire, a job's end): reading
        a count that is not ready waits for its program."""
        self._count_columns(
            DIST_STEPS, DIST_PATHS, steps,
            "tile steps dispatched, by the path of the distance "
            "dot: one bf16 pass (both operands bf16 numbers), the "
            "configured multi-pass dot, the cosine dot (a scaled dot at "
            "the configured precision over prepared operands), the one "
            "bf16 pass inside the kernel that walks the whole stack, or the "
            "inner-product dot (the dot alone at the configured precision, "
            "negated), or the one bf16 pass over a byte stack's widened "
            "tiles (dtype=uint8: kernel or tile steps), or the screen's "
            "three bf16 passes inside that kernel (fractional float32 rows "
            "on the lane grid under L2; the XLA screen's steps count under "
            "multipass)",
        )

    def count_select_tiles(self, tiles) -> None:
        """Add a dispatch's query-tile merges to
        ``knn_select_query_tiles_total{path="carried"|"rescanned"}``.
        ``tiles`` is ``KNNResult.select_tiles`` / ``BatchResult
        .select_tiles``: ints ``[carried, rescanned]``, one row a device,
        from a program whose scans carry the lane-bin lists. The device
        decides, so call this where :meth:`count_dist_steps` is called."""
        self._count_columns(
            SELECT_TILES, SELECT_PATHS, tiles,
            "query-tile merges whose scan over the corpus tiles carried "
            "the lane-bin lists, by what became of the selection: the "
            "carried answer kept, or rows that failed the certificate "
            "answered again by a re-scan",
        )

    def count_bins_chunks(self, chunks) -> None:
        """Add a dispatch's chunks to ``knn_select_bins_chunks_total
        {path="inserted"|"skipped"}``. ``chunks`` is ``KNNResult
        .bins_chunks`` / ``BatchResult.bins_chunks``: ints ``[inserted,
        skipped]``, one row a device, from a program whose scans carry a
        row bound beside the lane-bin lists. The device decides, so call
        this where :meth:`count_dist_steps` is called."""
        self._count_columns(
            BINS_CHUNKS, BINS_PATHS, chunks,
            "chunks (16 rows x 1024 columns) of the distance tiles of "
            "scans that carry the lane-bin lists, by what became of them "
            "in bins: inserted through the compare-exchange network, or "
            "skipped because no value was at or under its row's bound",
        )

    def count_screen_rows(self, rows) -> None:
        """Add a dispatch's query rows to ``knn_screen_rows_total
        {result="certified"|"flagged"}``. ``rows`` is ``KNNResult
        .screen_rows`` / ``BatchResult.screen_rows``: ints ``[certified,
        flagged]``, from a program whose scans rank in three bf16 passes
        (``backends/serial.py screen_rule``). The device decides, so call
        this where :meth:`count_dist_steps` is called."""
        self._count_columns(
            SCREEN_ROWS, SCREEN_RESULTS, rows,
            "query rows (padding included) of scans that rank in three "
            "bf16 passes and finish k' candidates a row at the configured "
            "precision, by the screen's certificate: certified (the "
            "finished k are provably the stack's), or flagged (answered "
            "again by the re-scan at the configured precision)",
            label="result",
        )

    def count_ivf_probe(self, probed) -> None:
        """Add a clustered batch's probe counts (``KNNResult.ivf_probe`` /
        ``BatchResult.ivf_probe``: ints ``[probes, bucket_cap, live rows,
        distinct partitions, their live rows, work items walked, those
        walked in one bf16 pass]``, ``ivf/search.py probe_counts``) to
        ``ivf_probe_slots_total``,
        ``ivf_probe_live_rows_total``, ``ivf_probe_partitions_total
        {kind="probes"|"distinct"}``, ``ivf_probe_distinct_live_rows
        _total``, ``ivf_probe_groups_total``, ``ivf_probe_groups_onepass
        _total`` and ``ivf_probe_batches_total
        {path="bucket_major"|"row_major"}`` (the program that walked no
        work item is the row-major one). The device counts, so call this
        where :meth:`count_dist_steps` is called."""
        import numpy as np

        probes, cap, live, distinct, distinct_live, walked, onepass = (
            int(n) for n in np.asarray(probed).reshape(-1)[:7])
        self.counter(
            "ivf_probe_slots_total",
            help="padded bucket slots the probes scan: query rows of the "
            "padded batches x nprobe x bucket_cap",
        ).inc(probes * cap)
        self.counter(
            "ivf_probe_live_rows_total",
            help="live corpus rows among the probed slots: the sum of "
            "the probed partitions' rows, a (query row, probe) pair each",
        ).inc(live)
        for kind, n, what in (
            ("probes", probes, "probes issued (query rows x nprobe)"),
            ("distinct", distinct, "distinct partitions a batch touched, "
             "summed over batches"),
        ):
            self.counter(
                "ivf_probe_partitions_total",
                help="partitions probed by clustered batches: " + what,
                labels={"kind": kind},
            ).inc(n)
        self.counter(
            "ivf_probe_distinct_live_rows_total",
            help="live rows of the distinct partitions a batch touched, "
            "summed over batches: what any implementation reads once a "
            "batch",
        ).inc(distinct_live)
        self.counter(
            "ivf_probe_groups_total",
            help="work items the bucket-major probe walked: a partition "
            "and a group of at most ivf/search.py PROBE_GROUP query rows "
            "that probe it, the partition fetched once however many; "
            "probes over (groups x PROBE_GROUP) is the groups' fill",
        ).inc(walked)
        self.counter(
            "ivf_probe_groups_onepass_total",
            help="work items of ivf_probe_groups_total whose distance keys "
            "came from one bf16 x bf16 pass: the store and the batch's "
            "query rows were bf16 numbers (ivf_index_onepass), so the "
            "pass returns what float32's six return",
        ).inc(onepass)
        self.counter(
            "ivf_probe_batches_total",
            help="clustered batches by the program that answered them: "
            "over the touched partitions (bucket_major) or over the query "
            "rows, each gathering its partitions (row_major)",
            labels={"path": "bucket_major" if walked else "row_major"},
        ).inc(1)

    def _count_columns(self, name, paths, counts, help,
                       label: str = "path") -> None:
        import numpy as np

        counts = np.asarray(counts)
        by_path = counts.reshape(-1, counts.shape[-1]).sum(axis=0)
        for path, n in zip(paths, by_path):
            self.counter(name, help=help, labels={label: path}).inc(int(n))


_default_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide registry every instrumented layer writes to."""
    return _default_registry


# ---------------------------------------------------------------------------
# Prometheus text exposition


def _prom_num(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    return repr(float(v))


def to_prometheus(snapshot: dict) -> str:
    """Render a :meth:`MetricsRegistry.snapshot` document as Prometheus
    text exposition format (histograms as cumulative ``_bucket{le=...}``
    series plus ``_sum``/``_count``)."""
    out = []
    # labeled series share one HELP/TYPE header per BASE name (the part
    # before the label set) — duplicate TYPE lines for one metric family
    # are malformed exposition
    seen_bases: set[str] = set()
    for name, m in snapshot.get("metrics", {}).items():
        kind = m["kind"]
        base = name.split("{", 1)[0]
        if base not in seen_bases:
            seen_bases.add(base)
            if m.get("help"):
                out.append(f"# HELP {base} {m['help']}")
            out.append(f"# TYPE {base} {kind}")
        if kind in ("counter", "gauge"):
            out.append(f"{name} {_prom_num(m['value'])}")
        elif kind == "histogram":
            cum = 0
            for b, c in zip(m["buckets"], m["counts"]):
                cum += c
                out.append(f'{name}_bucket{{le="{_prom_num(b)}"}} {cum}')
            cum += m["counts"][-1]
            out.append(f'{name}_bucket{{le="+Inf"}} {cum}')
            out.append(f"{name}_sum {_prom_num(m['sum'])}")
            out.append(f"{name}_count {m['count']}")
        else:
            raise ValueError(f"unknown metric kind {kind!r} for {name!r}")
    return "\n".join(out) + ("\n" if out else "")


def parse_prometheus(text: str) -> dict[str, float]:
    """Strict parser for the exposition format this module emits —
    the CI gate's proof that the export is machine-readable, not just
    printable. Returns ``{sample_name[{labels}]: value}``; malformed
    lines raise ValueError."""
    samples: dict[str, float] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        if not name:
            raise ValueError(f"line {lineno}: no sample name: {line!r}")
        base = name.split("{", 1)[0]
        if not base or not all(
            c.isalnum() or c in "_:" for c in base
        ) or base[0].isdigit():
            raise ValueError(f"line {lineno}: bad metric name {base!r}")
        if "{" in name and not name.endswith("}"):
            raise ValueError(f"line {lineno}: unterminated labels: {name!r}")
        try:
            v = float(value)
        except ValueError:
            raise ValueError(
                f"line {lineno}: bad sample value {value!r}"
            ) from None
        if name in samples:
            raise ValueError(f"line {lineno}: duplicate sample {name!r}")
        samples[name] = v
    if not samples:
        raise ValueError("no samples in exposition")
    return samples


def load_snapshot(path: str) -> dict:
    """Read a snapshot JSON written by ``--metrics-out`` (or any
    ``snapshot()`` dump); schema-checked so the CLI fails loudly on a
    file that merely looks like JSON. A doctor VERDICT nests the
    registry snapshot under its own ``"metrics"`` key — unwrap it by its
    schema marker, so ``mpi-knn metrics verdict.json`` works as the CLI
    help documents."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        inner = doc.get("metrics")
        if isinstance(inner, dict) and str(
            inner.get("schema", "")
        ).startswith("mpi_knn_tpu.obs.metrics/"):
            doc = inner
    if not isinstance(doc, dict) or not isinstance(
        doc.get("metrics"), dict
    ) or not all(
        isinstance(m, dict) and "kind" in m for m in doc["metrics"].values()
    ):
        raise ValueError(f"{path}: not a metrics snapshot (no 'metrics' map)")
    return doc


# ---------------------------------------------------------------------------
# central jax.monitoring capture

_jax_lock = threading.Lock()
_jax_listener_installed = False


def _jax_compile_listener(name: str, secs: float, **kw) -> None:
    if name == JAX_CACHE_LOAD_EVENT:
        get_registry().counter(
            "jax_cache_loads_total",
            help="programs loaded from jax's persistent compilation cache",
        ).inc()
        return
    if name != JAX_COMPILE_EVENT:
        return
    reg = get_registry()
    reg.counter(
        "jax_compiles_total",
        help="XLA backend compiles observed via jax.monitoring",
    ).inc()
    try:
        reg.histogram(
            "jax_compile_seconds",
            help="XLA backend compile durations",
            buckets=COMPILE_BUCKETS_S,
        ).observe(secs)
    except ValueError:
        # a non-finite duration from the runtime must not crash the
        # listener (it runs inside the compiler); count it instead
        reg.counter(
            "jax_compile_bad_duration_total",
            help="compile events whose duration was non-finite",
        ).inc()


def install_jax_compile_listener(force: bool = False) -> bool:
    """Route XLA backend-compile events into the default registry.
    Idempotent; returns True iff a listener was (re-)registered. With
    ``force=True`` re-registers even if bookkeeping says installed —
    the recovery path after ``jax.monitoring.clear_event_listeners()``
    (jax has no per-listener unregister)."""
    global _jax_listener_installed
    with _jax_lock:
        if _jax_listener_installed and not force:
            return False
        from jax import monitoring  # lazy: supervisors never import jax

        monitoring.register_event_duration_secs_listener(
            _jax_compile_listener
        )
        _jax_listener_installed = True
        return True


@contextlib.contextmanager
def watch_compiles():
    """Count XLA backend compiles over a scope — the one machine check
    behind every "cache hit really compiled nothing" assertion
    (previously hand-rolled in three test files). Yields a list that
    grows by one event name per compile, so existing assertions
    (``counts == []``, ``len(counts)``, ``counts.clear()``) keep their
    exact shape; the same events also feed the shared registry.

    Teardown calls ``jax.monitoring.clear_event_listeners()`` (jax has
    nothing finer) and then force-reinstalls the central registry
    listener, so scoped counting can never silently kill the
    process-wide capture."""
    global _jax_listener_installed
    from jax import monitoring

    install_jax_compile_listener()
    events: list[str] = []

    def listener(name, secs, **kw):
        if name == JAX_COMPILE_EVENT:
            events.append(name)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        yield events
    finally:
        monitoring.clear_event_listeners()
        with _jax_lock:
            _jax_listener_installed = False
        install_jax_compile_listener()
