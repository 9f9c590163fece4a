"""Static cost certification: per-cell FLOP/byte/roofline model over
after-opt HLO, the committed cost ledger, and lint rule **R8-cost**
(ISSUE 16).

R7 made peak residency a statically certified, CI-gated number; this
module does the same for *work*. For every matrix cell it computes, from
the after-opt module text alone:

- **MXU FLOPs** — every ``dot``/``convolution``, priced as
  ``2 · |result| · |contraction|`` (shapes read from the printed operand
  types and the ``lhs_contracting_dims`` attribute), multiplied by the
  instruction's static execution count: the product of the trip counts
  of every enclosing ``while`` along its call chain, with trip counts
  read from the compare-against-constant in each loop's condition
  computation (the same reader R4 uses for the rotation scan). This is
  the honest count of what the machine executes — including, e.g., the
  bidirectional ring's duplicated middle block.
- **HBM traffic** — the modeled bytes moved: every materializing
  instruction (R7's forwarding model decides what materializes — pointer
  shuffles and in-place update forms are free) writes its result buffer
  once and reads each operand buffer once, scaled by the same execution
  multiplicities; fusion bodies and per-element appliers are collapsed
  (fused intermediates live in registers — only the fusion's result and
  operands touch HBM). A documented traffic *model*, not a hardware
  counter.
- **ICI bytes** — the wire-priced collective census: each collective's
  result buffer bytes × its execution multiplicity, over a closed
  registry of priced collective opcodes. A collective opcode OUTSIDE the
  registry is a finding ("unpriced collective"), not a silent zero —
  bytes-on-wire is a certified budget elsewhere (R4) and must never
  leak.

The FLOP side carries the same honesty contract R7 holds against PJRT:
the HLO-derived count must EXACTLY equal a closed-form analytical count
derived from the cell's own declared configuration facts
(``meta["cost"]``, written by each lowerer) — a dense tile is
``2·q·c·d`` plus its rerank term, a clustered probe is the centroid
score plus ``2·q·nprobe·cap·d``. Disagreement in either direction is a
finding: HLO > analytical means the program does work the model cannot
name; HLO < analytical means the counter lost a loop or a dot.

A declared **device profile** (peak FLOP/s, HBM bandwidth, ICI
bandwidth — shipped as data in ``device_profiles.json``, never code)
turns the three totals into a roofline lower bound on wall-clock per
batch and an upper bound on queries/s. Per-cell results land in the
committed ``artifacts/lint/cost_ledger.json`` with the same drift gate
the memory ledger uses (shared machinery: analysis/ledger.py) — growth
beyond tolerance is a perf regression naming the culprit op, shrinkage
is a stale ledger hiding a banked win. ``mpi_knn_tpu/plan.py`` inverts
these same functions into the capacity planner; it calls THIS module
(shared code path), never a re-derivation.
"""

from __future__ import annotations

import json
import pathlib
import re
from dataclasses import dataclass

from mpi_knn_tpu.analysis import ledger as _ledger
from mpi_knn_tpu.analysis.memory import _is_forwarding, total_buffer_bytes
from mpi_knn_tpu.utils.hlo_graph import HloModule

# ---------------------------------------------------------------------------
# device profiles — data, not code

_PROFILES_PATH = pathlib.Path(__file__).parent / "device_profiles.json"
DEFAULT_PROFILE = "cpu-test"


def load_profiles() -> dict:
    """The shipped device profiles, keyed by name. Each profile declares
    ``peak_flops`` (FLOP/s), ``hbm_bw`` / ``ici_bw`` (bytes/s), and
    ``hbm_bytes`` (per-device capacity, used by the planner)."""
    doc = json.loads(_PROFILES_PATH.read_text())
    return {k: v for k, v in doc.items() if not k.startswith("_")}


def get_profile(name: str) -> dict:
    profiles = load_profiles()
    if name not in profiles:
        raise KeyError(
            f"unknown device profile {name!r} (shipped: "
            f"{', '.join(sorted(profiles))})"
        )
    return profiles[name]


def profile_for_platform(platform: str, device_kind: str = "") -> str | None:
    """Best-effort map from a running JAX platform / device kind to a
    shipped profile name — ``None`` for hardware we ship no numbers for
    (absent, never a guessed profile)."""
    kind = device_kind.lower()
    if platform == "cpu":
        return "cpu-test"
    if platform == "tpu":
        if "v5 lite" in kind or "v5e" in kind or "v5litepod" in kind:
            return "tpu-v5e"
        if "v4" in kind:
            return "tpu-v4"
    return None


def detected_profile() -> dict | None:
    """The declared profile facts for the RUNNING process (lazy jax
    import — this module stays importable jax-free): ``{"name", ...}``
    with the profile's numbers inlined, or ``None`` for a device off the
    map (a failure to reach the device propagates — it is not "no
    profile"). This is what ``/healthz`` and the serve ``--report`` stamp,
    so an operator reads a deployment's measured throughput next to the
    declared roofline inputs the planner predicted it under."""
    import jax

    dev = jax.devices()[0]
    name = profile_for_platform(dev.platform, dev.device_kind)
    if name is None:
        return None
    return {"name": name, **get_profile(name)}


# ---------------------------------------------------------------------------
# execution multiplicities: how many times each computation runs per
# entry execution, statically

_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_WHILE_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_INT_CONST_RE = re.compile(r"^\s*(-?\d+)\s*$")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

# computations called by these opcodes execute once PER ELEMENT of their
# caller's operand — a static per-program count does not exist for them,
# so a dot inside one is unpriceable (a finding, never a guess)
_PER_ELEMENT_CALLERS = frozenset(
    {"reduce", "reduce-window", "sort", "scatter", "select-and-scatter",
     "map", "reduce-scatter", "all-reduce"}
)


_KNOWN_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def while_trip_count(module: HloModule, instr) -> int | None:
    """The static trip count of one ``while``: the integer constant its
    condition compares the induction variable against with ``LT`` —
    counted loops lowered from ``lax`` scans/maps/fori all print this
    form. Where the condition does not show it (an XLA:CPU build that
    wraps the compare in a fusion of its own, ``wrapped_compare``: met on
    this sandbox's host in PR 51, where every lint cell lost its count),
    the compiler's own annotation on the loop,
    ``backend_config={"known_trip_count":{"n":...}}``. ``None`` when the
    bound is not statically visible in either."""
    mc = _WHILE_COND_RE.search(instr.attrs)
    cond = module.computations.get(mc.group(1)) if mc else None
    for ci in (cond.instructions.values() if cond is not None else ()):
        if ci.opcode != "compare" or "direction=LT" not in ci.attrs:
            continue
        for op in ci.operands:
            src = cond.instructions.get(op)
            if src is not None and src.opcode == "constant":
                m = _INT_CONST_RE.match(src.operand_text)
                if m:
                    return int(m.group(1))
    known = _KNOWN_TRIP_RE.search(instr.attrs)
    return int(known.group(1)) if known else None


def computation_multiplicities(module: HloModule) -> dict:
    """Static execution count per computation, from the entry down the
    call graph: a ``while`` body runs ``trip`` times per caller
    execution (its condition ``trip + 1``), fusion/call/conditional
    bodies run once per caller execution, and per-element appliers get
    ``None`` (unpriceable — see ``_PER_ELEMENT_CALLERS``). A ``while``
    whose bound is not statically readable also propagates ``None``."""
    entry = next(
        (n for n, c in module.computations.items() if c.is_entry), None
    )
    mult: dict = {entry: 1}
    changed = True
    guard = 0
    while changed and guard < len(module.computations) + 2:
        changed = False
        guard += 1
        for cname, comp in module.computations.items():
            base = mult.get(cname, "absent")
            if base == "absent":
                continue
            for ins in comp.instructions.values():
                if ins.opcode == "while":
                    trip = while_trip_count(module, ins)
                    mb = _WHILE_BODY_RE.search(ins.attrs)
                    mc = _WHILE_COND_RE.search(ins.attrs)
                    updates = []
                    if mb:
                        updates.append(
                            (mb.group(1),
                             None if (base is None or trip is None)
                             else base * trip)
                        )
                    if mc:
                        updates.append(
                            (mc.group(1),
                             None if (base is None or trip is None)
                             else base * (trip + 1))
                        )
                    for callee, val in updates:
                        if mult.get(callee, "absent") != val:
                            mult[callee] = val
                            changed = True
                else:
                    per_element = ins.opcode in _PER_ELEMENT_CALLERS
                    for callee in ins.called:
                        val = None if per_element else base
                        if mult.get(callee, "absent") != val:
                            mult[callee] = val
                            changed = True
    return mult


# ---------------------------------------------------------------------------
# MXU FLOPs from dot shapes × multiplicities

def _shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str or "")
    if not m:
        return []
    return ([int(x) for x in m.group(2).split(",")]
            if m.group(2) else [])


@dataclass(frozen=True)
class DotSite:
    computation: str
    instruction: str
    opcode: str
    flops_each: int
    multiplicity: int
    flops: int


def dot_inventory(module: HloModule):
    """Every ``dot``/``convolution`` in the module with its per-execution
    FLOPs and static multiplicity. Returns ``(sites, problems)`` —
    problems are dots whose execution count is not statically priceable
    (inside a per-element applier or an unbounded loop): those can never
    reconcile with a closed form and must surface as findings."""
    mult = computation_multiplicities(module)
    sites, problems = [], []
    for cname, comp in module.computations.items():
        for ins in comp.instructions.values():
            if ins.opcode not in ("dot", "convolution"):
                continue
            out_elems = 1
            for d in _shape_dims(ins.type_str):
                out_elems *= d
            lhs = (comp.instructions.get(ins.operands[0])
                   if ins.operands else None)
            lhs_dims = _shape_dims(lhs.type_str if lhs else "")
            mcd = _CONTRACT_RE.search(ins.attrs)
            cdims = ([int(x) for x in mcd.group(1).split(",")]
                     if mcd and mcd.group(1) else [])
            contract = 1
            for d in cdims:
                contract *= lhs_dims[d] if d < len(lhs_dims) else 0
            m = mult.get(cname, 0)
            if m is None:
                problems.append(
                    f"dot {ins.name!r} in computation {cname!r} has no "
                    "static execution count (per-element applier or "
                    "unbounded loop) — its FLOPs cannot be certified"
                )
                continue
            each = 2 * out_elems * contract
            sites.append(
                DotSite(cname, ins.name, ins.opcode, each, m, each * m)
            )
    return sites, problems


def hlo_mxu_flops(module: HloModule):
    """``(total_flops, largest_site, problems)`` for one module."""
    sites, problems = dot_inventory(module)
    total = sum(s.flops for s in sites)
    largest = max(sites, key=lambda s: s.flops, default=None)
    return total, largest, problems


# ---------------------------------------------------------------------------
# ICI bytes: the wire-priced collective census

# the closed registry of collective opcodes this census knows how to
# price (result buffer bytes × multiplicity); ``-done`` halves of async
# pairs are skipped — their ``-start`` carries the payload
PRICED_COLLECTIVES = frozenset(
    {"collective-permute", "all-to-all", "all-gather", "all-reduce",
     "reduce-scatter", "collective-broadcast"}
)
# "ragged-" catches ragged-all-to-all, whose spelling does not start
# with a priced family prefix — without the marker it would be
# invisible to the census instead of an unpriced-collective finding
_COLLECTIVE_MARKERS = ("all-", "collective-", "reduce-scatter", "ragged-")


def _collective_base(opcode: str) -> str | None:
    """The registry key for a collective-family opcode (``-start``
    variants fold onto their base), ``None`` for ``-done`` halves and
    for non-collective opcodes."""
    if opcode.endswith("-done"):
        return None
    base = opcode[:-6] if opcode.endswith("-start") else opcode
    if any(base.startswith(p) for p in _COLLECTIVE_MARKERS):
        return base
    return None


def collective_census(module: HloModule):
    """``(ici_bytes, problems)``: modeled bytes each device puts on the
    interconnect per execution — every priced collective's result buffer
    bytes × its static multiplicity. A collective-family opcode missing
    from :data:`PRICED_COLLECTIVES` is a problem (an unpriced collective
    would silently zero its wire cost), as is a priced collective with
    no static execution count."""
    mult = computation_multiplicities(module)
    total = 0
    problems = []
    for cname, comp in module.computations.items():
        for ins in comp.instructions.values():
            base = _collective_base(ins.opcode)
            if base is None:
                continue
            if base not in PRICED_COLLECTIVES:
                problems.append(
                    f"unpriced collective {ins.opcode!r} at {ins.name!r}"
                    f" in {cname!r} — not in the wire-price registry, "
                    "its ICI bytes would silently vanish from the census"
                )
                continue
            m = mult.get(cname, 0)
            if m is None:
                problems.append(
                    f"collective {ins.opcode!r} at {ins.name!r} in "
                    f"{cname!r} has no static execution count — its ICI "
                    "bytes cannot be certified"
                )
                continue
            total += total_buffer_bytes(ins.type_str) * m
    return total, problems


# ---------------------------------------------------------------------------
# HBM traffic model

def _collapsed_computations(module: HloModule) -> set:
    """Computations whose instructions do NOT individually touch HBM:
    fusion bodies (fused intermediates live in registers) and
    per-element appliers — their caller instruction accounts for the
    traffic. While/call/conditional bodies DO materialize."""
    out = set()
    for comp in module.computations.values():
        for ins in comp.instructions.values():
            if ins.opcode == "while":
                continue
            if ins.opcode == "fusion" or ins.opcode in _PER_ELEMENT_CALLERS:
                out.update(ins.called)
    return out


def hbm_traffic_bytes(module: HloModule) -> int:
    """Modeled HBM bytes moved per execution: every materializing
    instruction (R7's forwarding model) writes its result once and reads
    each operand buffer once, × its static multiplicity; collapsed
    scopes are skipped. Unpriceable multiplicities contribute zero —
    the FLOP/ICI sides already surface them as findings."""
    mult = computation_multiplicities(module)
    collapsed = _collapsed_computations(module)
    total = 0
    for cname, comp in module.computations.items():
        m = mult.get(cname, 0)
        if cname in collapsed or not m:
            continue
        for ins in comp.instructions.values():
            if ins.opcode == "parameter" or _is_forwarding(module, ins):
                continue
            bytes_moved = total_buffer_bytes(ins.type_str)
            for op in ins.operands:
                src = comp.instructions.get(op)
                if src is not None:
                    bytes_moved += total_buffer_bytes(src.type_str)
            total += bytes_moved * m
    return total


# ---------------------------------------------------------------------------
# the analytical side of the honesty contract: closed-form MXU FLOPs
# from the cell's own declared configuration facts (meta["cost"])

def analytical_mxu_flops(facts: dict) -> int:
    """Closed-form MXU FLOPs from declared configuration facts.

    Schemes (all counts are per program execution, per device for SPMD
    programs — exactly what the per-device after-opt module runs):

    - ``zero``: mutation programs — no dots by design.
    - ``dense``: ``sites·trips·(2·q·c·d + 2·q·rblocks·w·d)`` — the tile
      distance dot over a ``(q, c)`` block plus, on mixed cells, the
      survivor rerank of ``w`` overfetched rows per rerank block. The
      one-shot dense backends are ``sites=trips=1`` with ``c`` the
      (padded) corpus; the ring schedules set ``sites`` (1, or 2 for
      bidir's forward+backward travelers), ``trips`` (``P`` uni,
      ``⌊P/2⌋+1`` bidir — the duplicated middle block is counted
      because the machine honestly executes it), and ``c`` the rotating
      corpus block.
    - ``ivf``: ``2·q·partitions·d`` centroid scoring plus
      ``2·q·v·d`` over the probed width ``v = nprobe·bucket_cap`` plus
      the mixed rerank ``2·q·rblocks·w·d``; the sharded layout runs the
      same program at its per-shard ``q``.
    """
    scheme = facts["scheme"]
    if scheme == "zero":
        return 0
    d = facts["d"]
    q = facts["q"]
    w = facts.get("w", 0)
    rblocks = facts.get("rblocks", 0)
    if scheme == "dense":
        sites = facts.get("sites", 1)
        trips = facts.get("trips", 1)
        return sites * trips * (
            2 * q * facts["c"] * d + 2 * q * rblocks * w * d
        )
    if scheme == "ivf":
        v = facts["nprobe"] * facts["bucket_cap"]
        return (
            2 * q * facts["partitions"] * d
            + 2 * q * v * d
            + 2 * q * rblocks * w * d
        )
    raise ValueError(f"unknown cost scheme {scheme!r}")


# ---------------------------------------------------------------------------
# roofline

def roofline(flops: int, hbm_bytes: int, ici_bytes: int, queries: int,
             profile: dict) -> dict:
    """The roofline lower bound on wall-clock for one execution under a
    declared device profile, and the queries/s upper bound it implies.
    ``bound`` names the binding resource — the planner surfaces it as
    the thing to buy more of."""
    legs = {
        "mxu": flops / profile["peak_flops"],
        "hbm": hbm_bytes / profile["hbm_bw"],
        "ici": (ici_bytes / profile["ici_bw"]) if ici_bytes else 0.0,
    }
    bound = max(legs, key=lambda k: legs[k])
    wall_s = legs[bound]
    return {
        "wall_s": wall_s,
        "qps": (queries / wall_s) if wall_s > 0 else float("inf"),
        "bound": bound,
    }


# ---------------------------------------------------------------------------
# the cost ledger (shared machinery: analysis/ledger.py)

COST_SCHEMA_VERSION = 1
DEFAULT_COST_LEDGER = pathlib.Path("artifacts/lint/cost_ledger.json")
COST_TOL_REL = 0.02
COST_TOL_ABS = 4096


def _dot_culprit(cell: dict) -> str:
    culprit = cell.get("largest_dot") or {}
    return (
        f"largest dot {culprit.get('flops')}FLOP "
        f"{culprit.get('op')!r} at {culprit.get('instruction')!r} "
        f"(×{culprit.get('multiplicity')})"
    )


LEDGER_SPEC = _ledger.LedgerSpec(
    kind="cost",
    schema_version=COST_SCHEMA_VERSION,
    source="mpi_knn_tpu.analysis.cost",
    regen_cmd="mpi-knn lint --cost",
    tol_rel=COST_TOL_REL,
    tol_abs=COST_TOL_ABS,
    metrics=(
        _ledger.MetricSpec(
            key="mxu_flops", noun="MXU work", unit="FLOPs",
            culprit=_dot_culprit,
        ),
        _ledger.MetricSpec(key="hbm_bytes", noun="HBM traffic",
                           unit="bytes"),
        _ledger.MetricSpec(key="ici_bytes", noun="ICI traffic",
                           unit="bytes"),
    ),
)


def load_cost_ledger(path) -> dict | None:
    return _ledger.load_ledger(path, LEDGER_SPEC)


def save_cost_ledger(path, cells: dict, merge_into: dict | None = None):
    return _ledger.save_ledger(path, cells, LEDGER_SPEC,
                               merge_into=merge_into)


def cost_ledger_drift(
    committed: dict, current: dict, *, full_matrix: bool,
    skipped_labels: frozenset | set = frozenset(),
) -> list[str]:
    return _ledger.ledger_drift(
        committed, current, LEDGER_SPEC,
        full_matrix=full_matrix, skipped_labels=skipped_labels,
    )


# ---------------------------------------------------------------------------
# the cell cost entry + R8 as a lint rule (rules.py wraps it — rules →
# cost is the only import direction, mirroring R7)

def cost_entry(module: HloModule, facts: dict,
               profile_name: str = DEFAULT_PROFILE):
    """``(ledger_entry, problems)`` for one after-opt module under its
    declared cost facts. The entry is what the cost ledger commits; the
    problems are R8 findings (exactness breaches, unpriced collectives,
    unpriceable multiplicities)."""
    flops, largest, problems = hlo_mxu_flops(module)
    ici_bytes, ici_problems = collective_census(module)
    problems = list(problems) + ici_problems
    hbm_bytes = hbm_traffic_bytes(module)
    analytical = analytical_mxu_flops(facts)
    if flops != analytical:
        direction = (
            "does work the closed form cannot name"
            if flops > analytical
            else "lost a loop or a dot the closed form prices"
        )
        problems.append(
            f"HLO MXU FLOPs {flops} != analytical {analytical} from "
            f"declared facts {facts!r} — the counter {direction} "
            "(exactness is the contract: both sides read the same "
            "configuration)"
        )
    profile = get_profile(profile_name)
    queries = facts.get("queries", facts.get("q", 1))
    entry = {
        "mxu_flops": flops,
        "analytical_flops": analytical,
        "hbm_bytes": hbm_bytes,
        "ici_bytes": ici_bytes,
        "intensity": (
            round(flops / hbm_bytes, 6) if hbm_bytes else 0.0
        ),
        "queries": queries,
        "largest_dot": (
            {
                "flops": largest.flops,
                "op": largest.opcode,
                "instruction": largest.instruction,
                "computation": largest.computation,
                "multiplicity": largest.multiplicity,
            }
            if largest is not None else None
        ),
        "profile": profile_name,
        "roofline": roofline(flops, hbm_bytes, ici_bytes, queries,
                             profile),
    }
    return entry, problems


def r8_check(ctx, stage: str, module: HloModule, finding_cls) -> list:
    """The R8-cost check body (rules.py wraps it in the Rule class):
    after-opt only — the cost of the program XLA will RUN; the
    before-opt module still carries fusion-bait the machine never
    executes."""
    if stage != "after_opt":
        return []
    facts = ctx.meta.get("cost")
    if facts is None:
        return [
            finding_cls(
                "R8-cost",
                ctx.target.label,
                stage,
                "cell declares no cost facts (meta['cost']) — the "
                "analytical side of the FLOP exactness contract is "
                "missing, so the cell's work cannot be certified",
                {},
            )
        ]
    entry, problems = cost_entry(module, facts)
    # stash for the engine's ledger collection (meta is a per-run copy)
    ctx.meta["r8_analysis"] = entry
    return [
        finding_cls(
            "R8-cost", ctx.target.label, stage, msg,
            {"mxu_flops": entry["mxu_flops"],
             "analytical_flops": entry["analytical_flops"],
             "ici_bytes": entry["ici_bytes"]},
        )
        for msg in problems
    ]
