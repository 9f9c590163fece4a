"""Static peak-HBM certification: an aliasing-aware liveness analyzer
over after-opt HLO, the per-cell memory ledger, and lint rule
**R7-peak-memory** (ISSUE 15).

The serving north star dies on the first OOM, and before this module
nothing bounded what a compiled cell actually holds LIVE: R2 caps the
largest *single* buffer, which cannot see an un-donated scratch doubling
residency (two medium buffers, each under the cap) or a corpus-sized
temp hiding under R2's largest-input floor. This module computes **peak
live bytes** per compiled cell from the after-opt module text — the
program XLA will actually run — and makes it a CI-gated regression axis
exactly like recall and bytes-on-wire already are.

The liveness model (``analyze_module``):

- Every instruction's result occupies a buffer sized from its printed
  result type (tuples sum their elements; a tuple-shaped value adds the
  8-byte-per-element pointer table XLA allocates for it — measured, not
  guessed: PJRT's ``output_size_in_bytes`` includes it).
- **Forwarding ops allocate nothing.** ``tuple``/``get-tuple-element``/
  ``bitcast``/``opt-barrier`` are pointer shuffles; a ``while`` aliases
  its state onto the init operand (XLA's forced while aliasing), so the
  state bytes are counted where the init elements were materialized and
  live as long as anything reads the loop's results; in-place update
  forms (``scatter``/``dynamic-update-slice``, and fusions whose body
  root is one — the mutation cells' donated store updates) write into
  operand 0's buffer. Liveness is tracked on the resolved ALLOCATING
  instruction, so plumbing can neither hide a buffer nor double it.
- **Def-use intervals, event-swept.** An allocating instruction's buffer
  is live from its definition to the last instruction whose operands
  resolve to it (the entry root and output definers live to program
  end). Peak = the maximum over program points of the live-set byte sum.
- **Called computations are loop-resident.** A ``while``/``call``/
  ``conditional`` executes with its callee's own internal peak on top of
  the caller's live set (conditional: the max across branches); fusion
  bodies are collapsed (fused intermediates live in registers — only the
  fusion's result materializes).
- **Aliasing folded in.** Output elements declared in the module
  header's ``input_output_alias`` (R5's reader) write into donated input
  buffers: the donated scratch counts ONCE, not twice — the analyzer
  discounts the aliased bytes from the output's defining instruction.

Honesty check: every cell's analysis is cross-checked against PJRT's own
``compiled.memory_analysis()`` (captured at compile time by
``analysis.lowering``, zero extra compiles). The structural components
(args / outputs / aliased bytes) must match EXACTLY — a mismatch means
the parser or the model is wrong, loudly. The temp peak is a model of a
heap the compiler packs with its own cost function (the analyzer cannot
see XLA's elementwise-reuse trick, so it deliberately over-estimates),
so the TOTAL peak is held to a declared ASYMMETRIC band instead:
measured across the whole matrix analyzer/PJRT ∈ [0.90, 1.72]; the band
is [−15%, +80%], tight on the dangerous direction (an under-estimate is
a buffer the model lost). Disagreement beyond the band is itself a
finding — an analyzer bug or an XLA surprise, either way something a
human must look at.

The ledger (``artifacts/lint/memory_ledger.json``) commits every default
cell's numbers; ``mpi-knn lint --memory --ledger-check`` recomputes and
fails on drift beyond tolerance in EITHER direction (growth is a
regression; shrinkage is a stale ledger hiding a banked win), on a
vanished cell (a silently dropped certification), while a NEW cell
simply extends the ledger.
"""

from __future__ import annotations

import json
import pathlib
import re
from dataclasses import dataclass, field

from mpi_knn_tpu.analysis import ledger as _ledger
from mpi_knn_tpu.utils.hlo_graph import HloModule, parse_hlo
from mpi_knn_tpu.utils.pjrt import pjrt_memory_stats  # noqa: F401 — its old home

# ---------------------------------------------------------------------------
# shape pricing (kept self-contained: rules.py imports THIS module for R7,
# so this module must not import rules)

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
# XLA materializes an index table of 8-byte pointers for tuple-shaped
# buffers; PJRT's output_size_in_bytes includes it, so the analyzer must
# too or the exact-match cross-check would be off by 8·arity everywhere
_TUPLE_PTR_BYTES = 8

# result buffer IS (part of) an operand buffer — never a new allocation
_FORWARD_OPS = (
    "tuple", "get-tuple-element", "bitcast", "opt-barrier", "copy-done",
    "transpose-bitcast", "while",
)
# in-place update forms: XLA writes the update into operand 0's buffer
# (the donated-store mutation scatters; R2-strict exempts the same set)
_INPLACE_OPS = ("scatter", "dynamic-update-slice")


def total_buffer_bytes(type_str: str) -> int:
    """All bytes of an HLO result type (tuple elements summed, plus the
    tuple pointer table) — what the value occupies, as opposed to R2's
    ``max_buffer_bytes`` (the largest single buffer)."""
    tot = 0
    n_elems = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        sz = _DTYPE_BYTES.get(dt)
        n_elems += 1
        if sz is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        tot += n * sz
    if type_str.lstrip().startswith("(") and n_elems:
        tot += _TUPLE_PTR_BYTES * n_elems
    return tot


def _elem_sizes(type_str: str) -> list[int]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append(n * _DTYPE_BYTES.get(dt, 0))
    return out


# ---------------------------------------------------------------------------
# header readers (self-contained copies of R5's tiny regexes — see the
# import-direction note above)

_ALIAS_ENTRY_RE = re.compile(
    r"\{\s*(\d*)\s*\}\s*:\s*\(\s*(\d+)\s*,\s*\{[^}]*\}\s*,"
    r"\s*(?:may|must)-alias\s*\)"
)


def _header_aliases(header: str) -> dict[int, int]:
    start = header.find("input_output_alias={")
    if start < 0:
        return {}
    seg = header[start:]
    depth = 0
    for j, ch in enumerate(seg):
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                seg = seg[: j + 1]
                break
    return {
        int(out or 0): int(param)
        for out, param in _ALIAS_ENTRY_RE.findall(seg)
    }


# ---------------------------------------------------------------------------
# the liveness analyzer


@dataclass
class MemoryAnalysis:
    """Peak live bytes of one compiled module, with attribution."""

    args_bytes: int
    output_bytes: int
    aliased_bytes: int
    temp_peak_bytes: int
    peak_bytes: int  # args + output − aliased + temp peak
    # the largest single temp buffer anywhere in the module (loop bodies
    # included) — the culprit a regression report names
    largest_temp_bytes: int = 0
    largest_temp_op: str = ""
    largest_temp_name: str = ""
    # where (entry instruction name) the temp peak occurs
    peak_at: str = ""
    # attribution: resident store / donated scratch / temps / collective
    # exchange buffers — context for a human reading the ledger (the
    # categories overlap the totals above, they do not sum to peak)
    categories: dict = field(default_factory=dict)

    def to_json(self) -> dict:
        return {
            "args_bytes": self.args_bytes,
            "output_bytes": self.output_bytes,
            "aliased_bytes": self.aliased_bytes,
            "temp_peak_bytes": self.temp_peak_bytes,
            "peak_bytes": self.peak_bytes,
            "largest_temp": {
                "bytes": self.largest_temp_bytes,
                "op": self.largest_temp_op,
                "instruction": self.largest_temp_name,
            },
            "peak_at": self.peak_at,
            "categories": self.categories,
        }


def _is_inplace_fusion(module: HloModule, instr) -> bool:
    """A fusion whose body root is (a tuple of only) in-place update ops
    writes into its operand buffers — forwarding, not allocation (the
    mutation cells' donated-store scatter fusions)."""
    if instr.opcode != "fusion" or not instr.called:
        return False
    comp = module.computations.get(instr.called[0])
    if comp is None or comp.root is None:
        return False
    root = comp.instructions.get(comp.root)
    if root is None:
        return False
    if root.opcode in _INPLACE_OPS:
        return True
    if root.opcode == "tuple":
        kids = [comp.instructions.get(o) for o in root.operands]
        return bool(kids) and all(
            k is not None and k.opcode in _INPLACE_OPS for k in kids
        )
    return False


def _is_forwarding(module: HloModule, instr) -> bool:
    return (
        instr.opcode in _FORWARD_OPS
        or instr.opcode in _INPLACE_OPS
        or instr.opcode == "parameter"
        or _is_inplace_fusion(module, instr)
    )


_GTE_IDX_RE = re.compile(r"index=(\d+)")

# resolution paths deeper than this fall back to whole-value (flat)
# resolution — real programs nest state tuples one or two deep; the cap
# only guards against a pathological printer loop
_MAX_PATH = 8


def _resolve_sources(module, comp, cache, name, path=()) -> frozenset:
    """The set of ALLOCATING instructions whose buffers this value (or
    the tuple element named by ``path``, a stack of indices innermost
    first) may occupy, within ``comp``. Parameters resolve to nothing —
    their bytes belong to the caller. Element-precise through
    ``tuple``/``get-tuple-element``/``while`` chains, exactly like
    ``hlo_graph.backward_slice``'s index stack: without this, a gte
    reading the scan carry would keep the whole loop-state tuple's
    sources (the resident traveler blocks included) alive to program
    end and overstate the peak. Any shape the tracker does not
    understand falls back to flat (all operands), which only EXTENDS
    lifetimes — the peak stays an upper-ish bound, never silently
    loses a buffer."""
    key = (name, path)
    if key in cache:
        return cache[key]
    cache[key] = frozenset()  # cycle guard
    i = comp.instructions.get(name)
    if i is None or i.opcode == "parameter":
        out = frozenset()
    elif i.opcode == "get-tuple-element" and i.operands:
        m = _GTE_IDX_RE.search(i.attrs)
        if m and len(path) < _MAX_PATH:
            out = _resolve_sources(
                module, comp, cache, i.operands[0],
                (int(m.group(1)),) + path,
            )
        else:
            out = _resolve_sources(module, comp, cache, i.operands[0])
    elif i.opcode == "tuple":
        if path and path[0] < len(i.operands):
            out = _resolve_sources(
                module, comp, cache, i.operands[path[0]], path[1:]
            )
        else:  # whole-tuple use (or malformed index): all elements
            srcs = set()
            for o in i.operands:
                srcs |= _resolve_sources(module, comp, cache, o)
            out = frozenset(srcs)
    elif _is_forwarding(module, i):
        # while aliases its state onto the init operand; bitcast/
        # opt-barrier/copy-done pass the path through; a bare scatter/
        # dus writes into operand 0 (an in-place FUSION unions all its
        # operands — which one the fused update writes into is not
        # visible from the call site, and a union only extends)
        ops = (
            i.operands[:1] if i.opcode in _INPLACE_OPS else i.operands
        )
        srcs = set()
        for o in ops:
            srcs |= _resolve_sources(module, comp, cache, o, path)
        out = frozenset(srcs)
    else:
        out = frozenset([name])
    cache[key] = out
    return out


def _sweep(module, comp, memo, stack, discount, out_defs):
    """Event-swept liveness peak of one computation. Returns
    ``(peak_bytes, largest (bytes, label, opcode), peak_at)`` where
    ``largest`` merges the callee bodies' largest temps (loop-body
    buffers are where the real culprits live)."""
    instrs = list(comp.instructions.values())
    order = {i.name: t for t, i in enumerate(instrs)}
    cache: dict = {}
    last: dict = {}
    for t, i in enumerate(instrs):
        for o in i.operands + i.controls:
            for s in _resolve_sources(module, comp, cache, o):
                last[s] = max(last.get(s, order[s]), t)
    end = len(instrs)
    if comp.root:
        for s in _resolve_sources(module, comp, cache, comp.root):
            last[s] = end
    for s in out_defs:
        if s in order:
            last[s] = end
    deltas = [0] * (end + 2)
    extras = [0] * (end + 1)
    largest = (0, "", "")
    for t, i in enumerate(instrs):
        if not _is_forwarding(module, i):
            b = max(0, total_buffer_bytes(i.type_str)
                    - discount.get(i.name, 0))
            if b:
                deltas[t] += b
                deltas[last.get(i.name, t) + 1] -= b
                if i.name not in out_defs and b > largest[0]:
                    largest = (b, f"{comp.name}::{i.name}", i.opcode)
        if i.opcode == "fusion":
            continue  # fused intermediates live in registers
        for callee in i.called:
            sub_peak, sub_largest = _computation_peak(
                module, callee, memo, stack
            )
            extras[t] = max(extras[t], sub_peak)
            if sub_largest[0] > largest[0]:
                largest = sub_largest
    run = 0
    peak = 0
    peak_at = ""
    for t in range(end + 1):
        run += deltas[t]
        cand = run + (extras[t] if t < end else 0)
        if cand > peak:
            peak = cand
            peak_at = instrs[t].name if t < end else "<exit>"
    return peak, largest, peak_at


def _computation_peak(module, cname, memo, stack=()):
    """Internal liveness peak of a non-entry computation (memoized;
    cycles — impossible in valid HLO — resolve to 0 rather than hang)."""
    if cname in memo:
        return memo[cname]
    if cname in stack or cname not in module.computations:
        return 0, (0, "", "")
    peak, largest, _ = _sweep(
        module, module.computations[cname], memo, stack + (cname,),
        discount={}, out_defs=frozenset(),
    )
    memo[cname] = (peak, largest)
    return memo[cname]


def _chase_output(comp, name):
    """Resolve a root element to its defining instruction through
    bitcast/copy-done/gte chains (tracking tuple indices through
    matched tuple/gte pairs)."""
    seen = set()
    while name in comp.instructions and name not in seen:
        seen.add(name)
        i = comp.instructions[name]
        if i.opcode in ("bitcast", "copy-done") and i.operands:
            name = i.operands[0]
            continue
        if i.opcode == "get-tuple-element" and i.operands:
            m = re.search(r"index=(\d+)", i.attrs)
            src = comp.instructions.get(i.operands[0])
            if (
                src is not None and src.opcode == "tuple" and m
                and int(m.group(1)) < len(src.operands)
            ):
                name = src.operands[int(m.group(1))]
                continue
            name = i.operands[0]
            continue
        break
    return name


def _entry(module: HloModule):
    for c in module.computations.values():
        if c.is_entry:
            return c
    raise ValueError("module has no ENTRY computation")


def analyze_module(module_or_text) -> MemoryAnalysis:
    """Peak live bytes of one after-opt module (see the module
    docstring for the model). Accepts parsed or raw HLO text."""
    module = (
        module_or_text
        if isinstance(module_or_text, HloModule)
        else parse_hlo(module_or_text)
    )
    entry = _entry(module)
    aliases = _header_aliases(module.header)
    args = sum(
        total_buffer_bytes(i.type_str)
        for i in entry.instructions.values()
        if i.opcode == "parameter"
    )
    root = entry.instructions[entry.root]
    out_elems = _elem_sizes(root.type_str)
    is_tuple = root.type_str.lstrip().startswith("(")
    out_bytes = sum(out_elems) + (
        _TUPLE_PTR_BYTES * len(out_elems) if is_tuple else 0
    )
    # output-defining instructions: their bytes leave the temp sweep
    # entirely — outputs are accounted FLAT via output_bytes (they
    # occupy their allocation for the whole execution, which is how
    # PJRT splits output_size from temp_size too). Aliased output
    # elements additionally subtract from the total: they write into
    # donated input buffers already counted in args (once, not twice).
    if root.opcode == "tuple":
        defs = [_chase_output(entry, o) for o in root.operands]
    else:
        defs = [_chase_output(entry, root.name)]
    aliased = 0
    discount: dict = {}
    out_def_names: set = set()
    cache: dict = {}
    for k, dname in enumerate(defs):
        srcs = _resolve_sources(module, entry, cache, dname)
        out_def_names.update(srcs if srcs else {dname})
        # the discount lands on the ALLOCATING source when it is
        # unambiguous (the chased name may still be a forwarding op);
        # with several candidate sources it stays on the chased name —
        # an over-count, never a lost buffer
        key = next(iter(srcs)) if len(srcs) == 1 else dname
        if k < len(out_elems):
            discount[key] = discount.get(key, 0) + out_elems[k]
            if k in aliases:
                aliased += out_elems[k]
    memo: dict = {}
    temp_peak, largest, peak_at = _sweep(
        module, entry, memo, ("<entry>",), discount,
        frozenset(out_def_names),
    )
    exchange = sum(
        total_buffer_bytes(module.instr(c, n).type_str)
        for op in ("collective-permute", "all-to-all")
        for c, n in module.find(op)
        if not module.instr(c, n).opcode.endswith("-done")
    )
    return MemoryAnalysis(
        args_bytes=args,
        output_bytes=out_bytes,
        aliased_bytes=aliased,
        temp_peak_bytes=temp_peak,
        peak_bytes=args + out_bytes - aliased + temp_peak,
        largest_temp_bytes=largest[0],
        largest_temp_name=largest[1],
        largest_temp_op=largest[2],
        peak_at=peak_at,
        categories={
            "scratch": aliased,
            "temp": temp_peak,
            "exchange": exchange,
        },
    )


# ---------------------------------------------------------------------------
# budget derivation (the R7 gate) + the PJRT cross-check


# Temp-peak slack over the cell's per-buffer working-set base (R2's tile
# budget / strict probed-bytes bound): the peak SUMS several live tile
# buffers (carry ‖ tile concatenations, sort scratch, loop double
# buffers), so the per-buffer base under-counts it by a small factor.
# Measured across the shipped matrix the worst cell needs ≈4.1×; 6×
# holds everywhere with margin while a corpus-sized temp (the bug class)
# overshoots it by an order of magnitude at real shapes.
R7_TEMP_SLACK = 6
# mirrors rules.R2_SLACK without importing rules (see header note)
_R2_SLACK = 4

# PJRT cross-check tolerance on the TOTAL peak — an ASYMMETRIC band.
# The analyzer is deliberately conservative: it cannot see XLA's
# elementwise-reuse trick (a fusion writing into its dying operand's
# buffer), so same-size transform chains each add a modeled buffer the
# real heap shares — overestimates up to ~1.72× on the worst shipped
# cell (cosine-normalized mixed ring bodies). UNDERestimating is the
# dangerous direction (a buffer the model lost), so that side is tight:
# measured across the matrix analyzer/PJRT ∈ [0.90, 1.72]; the band is
# [−15%, +80%]. Leaving it is a finding in either direction.
PJRT_TOL_UNDER = 0.15
PJRT_TOL_OVER = 0.80
PJRT_TOL_ABS = 4096

# ledger drift tolerance: peak numbers are deterministic for a fixed
# (jax, platform) pair, but tiny constant-folding jitter across point
# releases should not page anyone — 2% + 4 KiB is noise, more is a real
# change someone must bank or explain
LEDGER_TOL_REL = 0.02
LEDGER_TOL_ABS = 4096


def temp_budget_bytes(meta: dict) -> int:
    """The cell's temp-peak allowance, derived from the same declared
    facts R2 budgets single buffers with: the strict probed-bytes bound
    when one is declared (clustered cells), else the tile working set —
    NEVER the largest input (that floor is exactly what lets a
    corpus-sized temp hide; see the R2 audit in tests). Registered
    per-cell extras (``extra_elems``: the mixed rerank gather, the bidir
    second traveler; ``peak_extra_elems``: allowances only the liveness
    view needs, e.g. the bf16 store's one-time f32 upcast) ride on top."""
    tile = _R2_SLACK * meta["q_tile"] * meta["c_tile"]
    base = max(
        meta.get("budget_elems") or 0,
        tile,
        meta.get("extra_elems", 0),
    )
    return (
        R7_TEMP_SLACK * base + meta.get("peak_extra_elems", 0)
    ) * meta["acc_bytes"]


def peak_budget_bytes(meta: dict, analysis: MemoryAnalysis) -> int:
    """The cell's peak-HBM budget: the program's own inputs at face
    value (they ARE the index — R2's input floor is fine for what is
    genuinely an input), plus the outputs the donation contract does
    NOT alias away (a donated cell promises every output aliased, so
    un-donated output bytes count against the budget — the un-donated-
    scratch-doubles-residency bug class), plus the derived temp
    allowance."""
    if meta.get("donated_params"):
        # the donation contract says outputs alias donated inputs: any
        # unaliased output bytes are unplanned allocations and must fit
        # inside the temp allowance instead of being budgeted away
        out_allow = 0
    else:
        out_allow = analysis.output_bytes
    return analysis.args_bytes + out_allow + temp_budget_bytes(meta)


def crosscheck_pjrt(analysis: MemoryAnalysis, pjrt: dict) -> list[str]:
    """Why the analyzer and PJRT disagree (empty = they agree). The
    structural components must match EXACTLY — both sides read the same
    declared shapes, so any gap is a parser/model bug. The total peak is
    held to the declared tolerance band."""
    out = []
    for mine, theirs, what in (
        (analysis.args_bytes, pjrt["argument_bytes"], "argument"),
        (analysis.output_bytes, pjrt["output_bytes"], "output"),
        (analysis.aliased_bytes, pjrt["alias_bytes"], "aliased"),
    ):
        if mine != theirs:
            out.append(
                f"{what} bytes disagree: analyzer {mine} vs PJRT "
                f"{theirs} — structural components are declared shapes "
                "and must match exactly (parser or model bug)"
            )
    lo = pjrt["peak_bytes"] * (1 - PJRT_TOL_UNDER) - PJRT_TOL_ABS
    hi = pjrt["peak_bytes"] * (1 + PJRT_TOL_OVER) + PJRT_TOL_ABS
    if not (lo <= analysis.peak_bytes <= hi):
        out.append(
            f"peak bytes disagree beyond tolerance: analyzer "
            f"{analysis.peak_bytes} vs PJRT {pjrt['peak_bytes']} "
            f"(band [{int(lo)}, {int(hi)}] at −{PJRT_TOL_UNDER:.0%}/"
            f"+{PJRT_TOL_OVER:.0%} + {PJRT_TOL_ABS}B) — analyzer bug "
            "or XLA surprise, either way a human must look"
        )
    return out


# ---------------------------------------------------------------------------
# the ledger

LEDGER_SCHEMA_VERSION = 1
DEFAULT_LEDGER = pathlib.Path("artifacts/lint/memory_ledger.json")


def ledger_entry(analysis: MemoryAnalysis, budget: int,
                 pjrt: dict | None) -> dict:
    return {
        **analysis.to_json(),
        "budget_bytes": budget,
        "pjrt": pjrt,
    }


def _peak_culprit(cell: dict) -> str:
    culprit = cell.get("largest_temp", {})
    return (
        f"largest temp {culprit.get('bytes')}B {culprit.get('op')!r} "
        f"at {culprit.get('instruction')!r}"
    )


# The R7 ledger as a LedgerSpec — all lifecycle (schema gate, atomic
# merge-aware save, vanished-cell semantics, tolerance-banded drift) is
# shared with R8's cost ledger via analysis/ledger.py so the two drift
# gates cannot diverge. The public functions below keep their original
# signatures and message text (pinned by tests/test_memory_lint.py).
LEDGER_SPEC = _ledger.LedgerSpec(
    kind="memory",
    schema_version=LEDGER_SCHEMA_VERSION,
    source="mpi_knn_tpu.analysis.memory",
    regen_cmd="mpi-knn lint --memory",
    tol_rel=LEDGER_TOL_REL,
    tol_abs=LEDGER_TOL_ABS,
    metrics=(
        _ledger.MetricSpec(
            key="peak_bytes", noun="peak", unit="bytes",
            culprit=_peak_culprit,
        ),
    ),
)


def load_ledger(path) -> dict | None:
    return _ledger.load_ledger(path, LEDGER_SPEC)


def save_ledger(path, cells: dict, merge_into: dict | None = None):
    """Write the ledger (atomically — lint may run concurrently with a
    serve process reading it). ``merge_into``: an existing ledger doc
    whose cells this run did not re-lower are preserved, so a filtered
    ``--memory`` sweep refreshes only what it measured."""
    return _ledger.save_ledger(path, cells, LEDGER_SPEC,
                               merge_into=merge_into)


def merge_base_for(
    committed: dict | None, *, full_matrix: bool,
    skipped_labels: frozenset | set = frozenset(),
) -> dict | None:
    """What a ``--memory`` WRITE should merge the fresh cells into (see
    :func:`mpi_knn_tpu.analysis.ledger.merge_base_for` — shared with the
    R8 cost ledger)."""
    return _ledger.merge_base_for(
        committed, full_matrix=full_matrix, skipped_labels=skipped_labels
    )


def ledger_drift(
    committed: dict, current: dict, *, full_matrix: bool,
    skipped_labels: frozenset | set = frozenset(),
) -> list[str]:
    """Why the current per-cell peaks fail the committed ledger (empty =
    green; see :func:`mpi_knn_tpu.analysis.ledger.ledger_drift` — shared
    with the R8 cost ledger)."""
    return _ledger.ledger_drift(
        committed, current, LEDGER_SPEC,
        full_matrix=full_matrix, skipped_labels=skipped_labels,
    )


# ---------------------------------------------------------------------------
# R7 as a lint rule — registered into the shared registry. Imported from
# rules.py at the END of its module body (rules → memory is the only
# import direction; memory defines its own shape readers above).


def r7_check(ctx, stage: str, module: HloModule, finding_cls) -> list:
    """The R7-peak-memory check body (rules.py wraps it in the Rule
    class): after-opt only — liveness over the program XLA will RUN;
    the before-opt module's buffers are pre-fusion fiction."""
    if stage != "after_opt":
        return []
    analysis = analyze_module(module)
    budget = peak_budget_bytes(ctx.meta, analysis)
    # stash for the engine's ledger collection (meta is a per-run copy)
    pjrt = ctx.meta.get("pjrt_memory")
    ctx.meta["r7_analysis"] = ledger_entry(analysis, budget, pjrt)
    out = []
    if analysis.peak_bytes > budget:
        out.append(
            finding_cls(
                "R7-peak-memory",
                ctx.target.label,
                stage,
                f"peak live bytes {analysis.peak_bytes} > budget "
                f"{budget} (args {analysis.args_bytes} + unaliased "
                f"outputs + {R7_TEMP_SLACK}× working-set temp "
                f"allowance) — largest temp "
                f"{analysis.largest_temp_bytes}B "
                f"{analysis.largest_temp_op!r} at "
                f"{analysis.largest_temp_name!r}, peak at "
                f"{analysis.peak_at!r}",
                {
                    "peak_bytes": analysis.peak_bytes,
                    "budget_bytes": budget,
                    "largest_temp": {
                        "bytes": analysis.largest_temp_bytes,
                        "op": analysis.largest_temp_op,
                        "instruction": analysis.largest_temp_name,
                    },
                },
            )
        )
    if pjrt is not None:
        for why in crosscheck_pjrt(analysis, pjrt):
            out.append(
                finding_cls(
                    "R7-peak-memory",
                    ctx.target.label,
                    stage,
                    why,
                    {
                        "analyzer": analysis.to_json(),
                        "pjrt": pjrt,
                    },
                )
            )
    return out
