"""Static rules over compiled-program structure (the lint engine's R1–R4).

Each rule asserts one property of the HLO a backend configuration actually
lowers/compiles to — the class of bug a timing run cannot surface (the
reference's non-blocking variant "worked" for its whole life while
serializing on MPI_Wait). The parsing core lives in
``mpi_knn_tpu.utils.hlo_graph``; this module interprets the parsed graph.

Shipped rules:

- **R1-overlap** — comm/compute sequencing. The overlap schedule's
  ``collective-permute`` must have no dependence path from the step's
  distance compute (both before and after XLA optimization); the blocking
  schedule's permutes must be sequenced after the compute via the
  ``opt-barrier`` (before-opt only: XLA legitimately expands the barrier
  mid-pipeline once it has constrained the passes it exists to constrain).
- **R2-memory** — footprint bound. No instruction may define a buffer
  larger than the tile budget implied by ``query_tile``/``corpus_tile``
  (with slack for concatenated carries) or the largest input, whichever is
  greater — statically forbidding accidental materialization of the full
  m×m distance matrix.
- **R3-dtype** — dtype integrity. In float64 debug mode no value may be
  silently downcast (f64→f32/bf16/f16 ``convert``); in any mode a ``dot``
  with bf16 operands must accumulate wider (bf16→bf16 dots lose the MXU's
  f32 accumulator). Quantized cells (int8 transfer / int8-int4 at-rest
  stores, ``meta["quantized"]``) add the quant/dequant contract: no dot
  may consume raw int8 codes (scoring codes without their scale is
  numerically meaningless, not merely imprecise), the module must
  contain at least one dequant (an s8→float ``convert`` — zero means the
  quantized payload never reaches compute and every check here is
  vacuous), and in mixed-policy cells each DEFAULT compress dot must be
  fed by EXACTLY ONE dequant convert plus a scale ``multiply`` in its
  backward slice.
- **R4-collective** — collective accounting. Ring backends must contain
  exactly the expected corpus-rotation ``collective-permute``s with
  ring-shaped ``source_target_pairs`` and nothing else (uni: one block+ids
  pair, forward; bidir: two counter-directed pairs, 2 permutes per torus
  direction — wrong-direction or missing permutes are findings);
  single-device backends must contain no collectives at all (a stray
  ``all-gather`` / ``all-reduce`` is a sharding leak); sharded-IVF
  programs contain exactly the candidate exchange's ``all-to-all``s
  (count, full-ring replica groups, payload bytes ≤ the declared
  per-tile exchange budget) and nothing else — an unrouted full-bucket
  broadcast or an over-budget per-shard gather is a finding.
- **R6-ivf-probe** — clustered-index probe discipline. In an IVF cell the
  only way corpus payload may reach a dot is the per-query probe gather:
  every batched candidate dot must carry a ``gather`` in its backward
  slice (and at least one must exist — zero is a vacuous contract), and
  no un-batched dot may be wider than the centroid score. Combined with
  R2's strict probed-bytes budget (``budget_elems``: the gather bound
  nprobe·bucket_cap·d per query row REPLACES the largest-input floor),
  "sublinear per query" is a compiled-program fact, not a Python-side
  counter.
- **R5-donation** — donation/aliasing of the serving batch program. The
  per-batch executable the serving engine compiles (``mpi_knn_tpu.serve``)
  must declare its scratch donation in the module header (``buffer_donor``
  before optimization / ``input_output_alias`` after — the compiled
  program's proof that steady-state serving reuses the carry in place
  rather than allocating per batch), and may not contain a
  ``copy``/``copy-start`` of resident-corpus size in either stage — a
  full-corpus copy inside the batch program would silently re-pay the
  corpus upload the resident index exists to amortize.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

from mpi_knn_tpu.utils.hlo_graph import (
    HloModule,
    backward_slice,
    parse_hlo,
    slice_opcodes,
)

# ---------------------------------------------------------------------------
# Findings and rule protocol


@dataclass
class Finding:
    """One rule violation, attributable to an instruction in one stage of
    one lowered configuration."""

    rule: str
    target: str  # "backend/metric/dtype"
    stage: str  # before_opt | after_opt
    message: str
    details: dict = field(default_factory=dict)

    def to_json(self) -> dict:
        return {
            "rule": self.rule,
            "target": self.target,
            "stage": self.stage,
            "message": self.message,
            "details": self.details,
        }


class Rule:
    """A static check over one parsed HLO module.

    ``applies`` gates on the configuration (a collective rule has nothing
    to say about code it knows nothing about — it still runs on serial
    programs, where "no collectives" IS the property); ``check`` returns
    findings for one (stage, module) of a configuration that does apply.
    """

    name: str = ""
    description: str = ""

    def applies(self, ctx) -> bool:
        return True

    def check(self, ctx, stage: str, module: HloModule) -> list[Finding]:
        raise NotImplementedError


RULES: list[Rule] = []


def register(cls):
    RULES.append(cls())
    return cls


def rules_by_name(names=None) -> list[Rule]:
    if names is None:
        return list(RULES)
    known = {r.name: r for r in RULES}
    missing = [n for n in names if n not in known]
    if missing:
        raise KeyError(f"unknown rule(s) {missing}; have {sorted(known)}")
    return [known[n] for n in names]


# ---------------------------------------------------------------------------
# R1: overlap/sequencing (the original ring-overlap artifact, generalized)

# Opcodes that witness the ring step's distance/top-k compute. ``dot`` is
# the MXU distance matmul; TopK/sort are the selection; reduce covers the
# sq_norms/row-sum forms XLA sometimes prefers over dot pre-optimization.
# Matched EXACTLY: prefix matching would classify the collective
# ``reduce-scatter`` / data-movement ``reduce-window`` as compute and
# falsely fail the overlap property on dumps with a second collective in
# the permute's slice.
COMPUTE_WITNESS = ("dot", "sort", "custom-call:TopK", "top-k", "topk",
                   "reduce")


def permute_dependence_report(text: str) -> dict:
    """For each collective-permute in the module: which compute-witness
    opcodes and how many opt-barriers its backward slice contains."""
    return permute_report_from_module(parse_hlo(text))


def permute_report_from_module(module: HloModule) -> dict:
    permutes = module.find("collective-permute")
    report = {
        "n_collective_permute": len(permutes),
        "n_opt_barrier_in_module": len(module.find("opt-barrier")),
        "n_dot_in_module": len(module.find("dot")),
        "permutes": [],
    }
    for comp, name in permutes:
        sl = backward_slice(module, comp, name)
        ops = slice_opcodes(module, sl)
        report["permutes"].append(
            {
                "instruction": f"{comp}::{name}",
                "slice_size": len(sl),
                "depends_on_opt_barrier": "opt-barrier" in ops,
                "compute_witnesses_in_slice": sorted(
                    o for o in ops if o in COMPUTE_WITNESS
                ),
                "depends_on_dot": "dot" in ops,
            }
        )
    return report


def overlap_violations(rep: dict) -> list[str]:
    """Why a permute-dependence report fails the OVERLAP schedule property
    (empty = holds). Zero permutes is itself a violation — it would make
    the dependence checks vacuous."""
    out = []
    if rep["n_collective_permute"] < 1:
        out.append("no collective-permute in module (vacuous overlap claim)")
    for p in rep["permutes"]:
        if p["compute_witnesses_in_slice"]:
            out.append(
                f"{p['instruction']} depends on compute "
                f"{p['compute_witnesses_in_slice']} — the transfer cannot "
                "overlap the work it waits on"
            )
        if p["depends_on_opt_barrier"]:
            out.append(
                f"{p['instruction']} is sequenced behind an opt-barrier"
            )
    return out


def blocking_violations(rep: dict) -> list[str]:
    """Why a (before-opt) report fails the BLOCKING schedule property:
    every permute must be sequenced after the compute via the barrier AND
    see the distance dot in its slice."""
    out = []
    if rep["n_collective_permute"] < 1:
        out.append("no collective-permute in module (vacuous blocking claim)")
    for p in rep["permutes"]:
        if not (p["depends_on_opt_barrier"] and p["depends_on_dot"]):
            out.append(
                f"{p['instruction']} is NOT sequenced after the compute "
                "(missing opt-barrier/dot dependence) — 'blocking' would "
                "silently be the overlap schedule"
            )
    return out


def property_holds(variant_reports: dict) -> bool:
    """THE ring-overlap artifact property, single definition shared by
    ``scripts/dump_ring_hlo.py`` (writes it into ``overlap_verdict.json``),
    ``tests/test_hlo_overlap.py`` (asserts it) and the engine's R1 rule —
    hand-maintained copies could drift and let the committed verdict
    disagree with the gate that is supposed to mirror it.

    Input: ``{variant: {stage: permute_dependence_report(...)}}`` with
    variants ``overlap``/``blocking`` and stages ``before_opt``/
    ``after_opt``. Holds iff the overlap reports pass
    :func:`overlap_violations` in BOTH stages and the blocking before-opt
    report passes :func:`blocking_violations` (after optimization the
    barrier is legitimately expanded — cpu: ``cse_barrier_expander`` — so
    after_opt makes no blocking claim).
    """
    ok = not overlap_violations(variant_reports["overlap"]["before_opt"])
    ok = ok and not overlap_violations(variant_reports["overlap"]["after_opt"])
    ok = ok and not blocking_violations(
        variant_reports["blocking"]["before_opt"]
    )
    return bool(ok)


@register
class R1Overlap(Rule):
    name = "R1-overlap"
    description = (
        "ring schedules keep their sequencing contract: overlap permutes "
        "are compute-independent, blocking permutes are barrier-sequenced"
    )

    def applies(self, ctx) -> bool:
        return ctx.target.backend in ("ring", "ring-overlap")

    def check(self, ctx, stage, module) -> list[Finding]:
        rep = permute_report_from_module(module)
        if ctx.target.backend == "ring-overlap":
            why = overlap_violations(rep)
        elif stage == "before_opt":
            why = blocking_violations(rep)
        else:  # blocking after-opt: barrier already expanded, no claim
            return []
        return [
            Finding(self.name, ctx.target.label, stage, w,
                    {"report": rep["permutes"]})
            for w in why
        ]


# ---------------------------------------------------------------------------
# R2: memory-footprint bound

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# Headroom over the (q_tile × c_tile) distance block for legitimate
# intermediates: the (carry ‖ tile) concatenation before the merge top-k,
# sort temporaries, and the twolevel survivor stack are all small multiples
# of the tile. 4× holds across the whole shipped matrix with margin; a full
# m×m materialization overshoots it by orders of magnitude.
R2_SLACK = 4


def max_buffer_bytes(type_str: str) -> int:
    """Largest single buffer in an HLO result type. Tuples are per-element
    buffers in XLA, so the max element — not the sum — is what an
    instruction materializes at once."""
    best = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        sz = _DTYPE_BYTES.get(dt)
        if sz is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        best = max(best, n * sz)
    return best


def max_buffer_elems(type_str: str) -> int:
    """Largest single buffer in an HLO result type, in ELEMENTS. The R2
    budget is element-denominated: a bf16 input legitimately widens to the
    f32 accumulation dtype (2× the input bytes), so byte-for-byte against
    the inputs would flag every upcast."""
    best = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        best = max(best, n)
    return best


@register
class R2Memory(Rule):
    name = "R2-memory"
    description = (
        "no instruction defines a buffer beyond the query_tile×corpus_tile "
        "budget (or the largest input) — no accidental m×m materialization"
    )

    def applies(self, ctx) -> bool:
        return True

    # strict-budget exemptions: opcodes that forward existing buffers
    # rather than materialize new payload — loop/tuple plumbing (XLA
    # aliases while state in place; tuple/gte are pointer shuffles). The
    # resident corpus legitimately rides through the query-tile loop's
    # state inside them; anything that COMPUTES corpus-sized bytes
    # (gather, dot, broadcast, fusion, …) stays on the hook.
    STRICT_EXEMPT = (
        "parameter", "tuple", "get-tuple-element", "while", "opt-barrier",
        "conditional", "call",
    )
    # sharded (SPMD) programs additionally pass the resident slice through
    # the partitioner's annotation custom-calls (@Sharding and the
    # full↔shard shape casts) — directives, not payload; every other
    # custom-call (TopK, …) stays on the hook
    _SPMD_ANNOTATIONS = (
        'custom_call_target="Sharding"',
        'custom_call_target="SPMDFullToShardShape"',
        'custom_call_target="SPMDShardToFullShape"',
    )

    @classmethod
    def _is_spmd_annotation(cls, instr) -> bool:
        return instr.opcode == "custom-call" and any(
            t in instr.attrs for t in cls._SPMD_ANNOTATIONS
        )

    def check(self, ctx, stage, module) -> list[Finding]:
        entry_params = [
            i
            for c in module.computations.values()
            if c.is_entry
            for i in c.instructions.values()
            if i.opcode == "parameter"
        ]
        max_param = max(
            (max_buffer_elems(i.type_str) for i in entry_params), default=0
        )
        tile_elems = ctx.meta["q_tile"] * ctx.meta["c_tile"]
        acc_bytes = ctx.meta["acc_bytes"]
        # element-denominated, then priced at the accumulation width: an
        # input-sized buffer may widen to the accumulator dtype (bf16
        # corpus → f32 norms path) but must not GROW in element count.
        # "extra_elems" is the lowering's registered legitimate intermediate
        # beyond the tile (today: the mixed policy's (q_tile, 4k, d) rerank
        # gather) — declared per configuration, never a blanket slack bump.
        #
        # "budget_elems" switches R2 to the STRICT mode the clustered (IVF)
        # cells use: the declared bound REPLACES the largest-input floor,
        # so the budget is the probe gather (q_tile·nprobe·bucket_cap·d)
        # and NOT the resident corpus — the lowering must prove it scans
        # only probed partitions, with only non-materializing loop/tuple
        # plumbing exempt.
        strict = ctx.meta.get("budget_elems")
        if strict is not None:
            budget = max(strict, R2_SLACK * tile_elems) * acc_bytes
        else:
            budget = max(
                max_param,
                R2_SLACK * tile_elems,
                ctx.meta.get("extra_elems", 0),
            ) * acc_bytes
        # "strict_exempt_ops": configuration-registered buffer-forwarding
        # opcodes beyond the structural plumbing — today the mutation
        # cells' in-place scatter/dynamic-update-slice forms, which XLA
        # aliases onto the donated store (the aliasing itself is R5's
        # claim; here they would read as store-sized materializations)
        exempt = (
            self.STRICT_EXEMPT + tuple(ctx.meta.get("strict_exempt_ops", ()))
            if strict is not None else ("parameter",)
        )
        # quantized stores additionally bound the GATHERS at the wire
        # width: the probe/exchange gathers must move code lanes (+ the
        # small scale/id/norm tables), never float-widened rows — an
        # f32-sized bucket gather under a quantized config means the
        # store was dequantized BEFORE the gather, silently re-paying the
        # bytes the quantization exists to cut (recall cost with no byte
        # win). The element-denominated budget above cannot see this: the
        # element counts are identical, only the itemsize differs.
        quant_gather = ctx.meta.get("quant_gather_bytes")
        out = []
        for c in module.computations.values():
            for i in c.instructions.values():
                if i.opcode in exempt:
                    continue  # inputs/plumbing: the caller's bytes, not new
                if strict is not None and self._is_spmd_annotation(i):
                    continue  # partitioner directives, not materialization
                b = max_buffer_bytes(i.type_str)
                if (
                    quant_gather is not None
                    and i.opcode == "gather"
                    and b > quant_gather
                ):
                    out.append(
                        Finding(
                            self.name,
                            ctx.target.label,
                            stage,
                            f"{c.name}::{i.name} gathers {b} bytes > the "
                            f"quantized wire budget {quant_gather} — a "
                            "float-sized bucket gather under a quantized "
                            "config moves the bytes the store compressed "
                            "away (dequantize AFTER the gather, not "
                            "before)",
                            {"bytes": b, "budget": quant_gather,
                             "type": i.type_str},
                        )
                    )
                if b > budget:
                    why = (
                        f"(declared probed-bytes bound {strict} elems, "
                        "NOT the resident corpus"
                        if strict is not None
                        else f"(max(largest input {max_param} elems, "
                        f"{R2_SLACK}×{ctx.meta['q_tile']}×"
                        f"{ctx.meta['c_tile']} tile elems)"
                    )
                    out.append(
                        Finding(
                            self.name,
                            ctx.target.label,
                            stage,
                            f"{c.name}::{i.name} ({i.opcode}) materializes "
                            f"{b} bytes > budget {budget} "
                            f"{why} × {acc_bytes} acc bytes)",
                            {
                                "bytes": b,
                                "budget": budget,
                                "type": i.type_str,
                            },
                        )
                    )
        return out


# ---------------------------------------------------------------------------
# R3: dtype integrity


def _result_dtype(type_str: str) -> str | None:
    m = _SHAPE_RE.search(type_str)
    return m.group(1) if m else None


_PRECISION_RE = re.compile(r"operand_precision=\{([^}]*)\}")


def dot_precision_class(instr) -> str:
    """Canonical precision of a ``dot``: the ``operand_precision`` attr
    ("highest"/"high"), or "default" when absent (XLA prints nothing for
    DEFAULT). Mismatched per-operand settings are reported joined — the
    mixed contract treats anything but a uniform default/highest as a
    violation."""
    m = _PRECISION_RE.search(instr.attrs)
    if not m:
        return "default"
    vals = {v.strip() for v in m.group(1).split(",") if v.strip()}
    return vals.pop() if len(vals) == 1 else "/".join(sorted(vals))


@register
class R3Dtype(Rule):
    name = "R3-dtype"
    description = (
        "no silent f64 downcast in float64 debug mode; bf16 dots must "
        "accumulate in f32 or wider; mixed-policy programs declare exactly "
        "one DEFAULT compress dot per tile computation with the rerank dot "
        "at HIGHEST"
    )

    def applies(self, ctx) -> bool:
        return True

    def check(self, ctx, stage, module) -> list[Finding]:
        out = []
        check_f64 = ctx.target.dtype == "float64"
        for c in module.computations.values():
            for i in c.instructions.values():
                res = _result_dtype(i.type_str)
                if (
                    check_f64
                    and i.opcode == "convert"
                    and res in ("f32", "bf16", "f16")
                ):
                    src = c.instructions.get(i.operands[0]) if i.operands else None
                    if src is not None and _result_dtype(src.type_str) == "f64":
                        out.append(
                            Finding(
                                self.name,
                                ctx.target.label,
                                stage,
                                f"{c.name}::{i.name} silently converts f64 "
                                f"-> {res} on the float64 debug path",
                                {"type": i.type_str},
                            )
                        )
                if i.opcode == "dot" and res == "bf16":
                    op_dts = [
                        _result_dtype(c.instructions[o].type_str)
                        for o in i.operands
                        if o in c.instructions
                    ]
                    if "bf16" in op_dts:
                        out.append(
                            Finding(
                                self.name,
                                ctx.target.label,
                                stage,
                                f"{c.name}::{i.name} is a bf16 dot without "
                                "f32 accumulation (result bf16) — the MXU "
                                "accumulator precision is being thrown away",
                                {"type": i.type_str},
                            )
                        )
        if (
            stage == "before_opt"
            and getattr(ctx.cfg, "precision_policy", "exact") == "mixed"
        ):
            out.extend(self._check_mixed_contract(ctx, stage, module))
        if stage == "before_opt" and ctx.meta.get("quantized"):
            out.extend(self._check_quant_contract(ctx, stage, module))
        return out

    @staticmethod
    def _is_dequant_convert(comp, instr) -> bool:
        """A ``convert`` whose source is int8 codes and whose result is a
        float — the first half of the dequant pair."""
        if instr.opcode != "convert":
            return False
        if _result_dtype(instr.type_str) not in ("f32", "bf16", "f16",
                                                 "f64"):
            return False
        for o in instr.operands:
            src = comp.instructions.get(o)
            if src is not None and _result_dtype(src.type_str) == "s8":
                return True
        return False

    def _check_quant_contract(self, ctx, stage, module) -> list[Finding]:
        """The quantized dtype contract (before-opt — fusion may legally
        rewrite the dequant afterwards; the declared dataflow is pinned on
        the module XLA receives): quantized payload reaches compute ONLY
        through the dequant (convert out of int8 + multiply by the scale).
        A dot consuming raw s8 operands is scoring codes without their
        scale; a quantized program with no s8→float convert at all never
        dequantized (the codes are dead or — worse — reinterpreted), which
        would make every other check here vacuous."""
        out = []
        n_dequant = 0
        for c in module.computations.values():
            for i in c.instructions.values():
                if self._is_dequant_convert(c, i):
                    n_dequant += 1
                if i.opcode != "dot":
                    continue
                op_dts = [
                    _result_dtype(c.instructions[o].type_str)
                    for o in i.operands
                    if o in c.instructions
                ]
                if any(dt in ("s8", "s4", "u8", "u4") for dt in op_dts):
                    out.append(
                        Finding(
                            self.name,
                            ctx.target.label,
                            stage,
                            f"{c.name}::{i.name} is a dot consuming raw "
                            f"int8/int4 codes ({op_dts}) — quantized "
                            "payload must be dequantized (convert + scale "
                            "multiply) before any distance dot; scoring "
                            "codes without their block scale is not a "
                            "precision loss, it is a different function",
                            {"operand_dtypes": op_dts,
                             "type": i.type_str},
                        )
                    )
        if n_dequant == 0:
            out.append(
                Finding(
                    self.name,
                    ctx.target.label,
                    stage,
                    "quantized cell lowered NO s8→float dequant convert — "
                    "the quantized payload never reaches compute through "
                    "the dequant path (the quant contract is vacuous)",
                    {},
                )
            )
        if getattr(ctx.cfg, "precision_policy", "exact") != "mixed":
            return out
        # mixed quantized cells: the compress dot is where the quantized
        # rows enter the pipeline — each DEFAULT dot must see exactly one
        # dequant convert and the scale multiply in its backward slice (a
        # second convert would mean two quantized sources merged into one
        # compress pass the budgets do not model; zero means the compress
        # pass is scoring something other than the dequantized store)
        for c in module.computations.values():
            for i in c.instructions.values():
                if i.opcode != "dot" or dot_precision_class(i) != "default":
                    continue
                sl = backward_slice(module, c.name, i.name)
                convs = 0
                has_mul = False
                for sc, sn in sl:
                    si = module.instr(sc, sn)
                    if si.opcode == "multiply":
                        has_mul = True
                    if self._is_dequant_convert(
                        module.computations[sc], si
                    ):
                        convs += 1
                if convs != 1:
                    out.append(
                        Finding(
                            self.name,
                            ctx.target.label,
                            stage,
                            f"{c.name}::{i.name} (DEFAULT compress dot) "
                            f"has {convs} dequant converts in its "
                            "backward slice — the quantized contract is "
                            "exactly one dequant feeding each compress "
                            "dot",
                            {"dequant_converts": convs},
                        )
                    )
                elif not has_mul:
                    out.append(
                        Finding(
                            self.name,
                            ctx.target.label,
                            stage,
                            f"{c.name}::{i.name} (DEFAULT compress dot) "
                            "sees the dequant convert but NO scale "
                            "multiply in its backward slice — the codes "
                            "are being scored unscaled",
                            {},
                        )
                    )
        return out

    def _check_mixed_contract(self, ctx, stage, module) -> list[Finding]:
        """The DECLARED mixed-precision contract, machine-checked on the
        module XLA receives (before-opt: optimization may legally fuse or
        rewrite dots afterwards, but the declared precisions are fixed
        here): every dot is either the compress (DEFAULT — single-pass
        bf16 MXU) or the rerank (HIGHEST — multi-pass exact); each tile
        computation contains at most ONE compress dot; and both passes
        must actually exist — a mixed program with no DEFAULT dot never
        compressed (it silently pays exact FLOPs), one with no HIGHEST
        dot never reranks (it silently ships compressed distances)."""
        out = []
        n_default = n_highest = 0
        for c in module.computations.values():
            defaults_here = []
            for i in c.instructions.values():
                if i.opcode != "dot":
                    continue
                cls = dot_precision_class(i)
                if cls == "default":
                    defaults_here.append(i.name)
                    n_default += 1
                elif cls == "highest":
                    n_highest += 1
                else:
                    out.append(
                        Finding(
                            self.name,
                            ctx.target.label,
                            stage,
                            f"{c.name}::{i.name} is a dot at precision "
                            f"{cls!r} — the mixed contract allows only the "
                            "DEFAULT compress dot and the HIGHEST rerank "
                            "dot",
                            {"precision": cls, "type": i.type_str},
                        )
                    )
            if len(defaults_here) > 1:
                out.append(
                    Finding(
                        self.name,
                        ctx.target.label,
                        stage,
                        f"{c.name} contains {len(defaults_here)} "
                        "DEFAULT-precision dots "
                        f"({', '.join(defaults_here)}) — the compress pass "
                        "is exactly one single-pass dot per tile; a second "
                        "one is a silent downcast of work the contract "
                        "promises at HIGHEST",
                        {"dots": defaults_here},
                    )
                )
        if n_default == 0:
            out.append(
                Finding(
                    self.name,
                    ctx.target.label,
                    stage,
                    "mixed policy lowered NO DEFAULT-precision compress "
                    "dot — the program pays exact multi-pass FLOPs on the "
                    "full tile (the policy silently degenerated to exact)",
                    {},
                )
            )
        if n_highest == 0:
            out.append(
                Finding(
                    self.name,
                    ctx.target.label,
                    stage,
                    "mixed policy lowered NO HIGHEST-precision rerank dot "
                    "— compressed distances would reach the final top-k "
                    "unreranked",
                    {},
                )
            )
        return out


# ---------------------------------------------------------------------------
# R4: collective accounting

RING_COLLECTIVE = "collective-permute"
STRAY_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-broadcast",
)
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")


def count_collectives(module: HloModule) -> dict[str, list[tuple[str, str]]]:
    """Collective instructions by canonical opcode. Async ``-start``/
    ``-done`` pairs count once (the ``-start`` carries the semantics)."""
    out: dict[str, list[tuple[str, str]]] = {}
    for op in (RING_COLLECTIVE,) + STRAY_COLLECTIVES:
        hits = [
            (c, n)
            for (c, n) in module.find(op)
            if not module.instr(c, n).opcode.endswith("-done")
        ]
        if hits:
            out[op] = hits
    return out


def _permute_pairs(module: HloModule, comp: str, name: str):
    m = re.search(
        r"source_target_pairs=\{(.*?)\}\}", module.instr(comp, name).attrs
    )
    if not m:
        return None
    return sorted(
        (int(a), int(b)) for a, b in _PAIR_RE.findall(m.group(1) + "}")
    )


def ring_rotation_pairs(ring_n: int) -> tuple[list, list]:
    """The two legal rotation shapes on an n-ring: forward (i → i+1, the
    reference's direction) and backward (i → i−1, the bidir schedule's
    counter-rotation), as sorted source_target_pairs."""
    fwd = sorted((i, (i + 1) % ring_n) for i in range(ring_n))
    bwd = sorted((i, (i - 1) % ring_n) for i in range(ring_n))
    return fwd, bwd


def permute_direction_census(module: HloModule, ring_n: int) -> dict:
    """Classify every collective-permute by rotation direction:
    ``{"fwd": n, "bwd": n, "other": [instruction, ...]}``. The bidir
    schedule must show an equal fwd/bwd split (one block + one ids permute
    per direction) and nothing in ``other`` — a wrong-direction permute
    would merge blocks in an order the round plan does not account for."""
    fwd, bwd = ring_rotation_pairs(ring_n)
    out: dict = {"fwd": 0, "bwd": 0, "other": []}
    for comp, name in module.find(RING_COLLECTIVE):
        if module.instr(comp, name).opcode.endswith("-done"):
            continue
        pairs = _permute_pairs(module, comp, name)
        if pairs == fwd:
            out["fwd"] += 1
        elif pairs == bwd and ring_n > 2:
            # n<=2: fwd and bwd coincide; classify as fwd above
            out["bwd"] += 1
        else:
            out["other"].append(f"{comp}::{name}")
    return out


_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")


def alltoall_census(module: HloModule, ring_n: int) -> dict:
    """Account every ``all-to-all`` (the sharded-IVF candidate exchange's
    collective): instruction count, total payload bytes (result buffer of
    the tiled form — what one scan step moves per shard), and any
    instruction whose replica_groups is NOT the single full-ring group —
    a partial-group exchange would route candidates to a subset of the
    owners the routing table named."""
    full = "{" + ",".join(str(i) for i in range(ring_n)) + "}"
    out: dict = {"count": 0, "bytes": 0, "bad_groups": []}
    for comp, name in module.find("all-to-all"):
        instr = module.instr(comp, name)
        if instr.opcode.endswith("-done"):
            continue
        out["count"] += 1
        out["bytes"] += max_buffer_bytes(instr.type_str)
        m = _REPLICA_GROUPS_RE.search(instr.attrs)
        groups = m.group(1).replace(" ", "") if m else ""
        if groups != full:
            out["bad_groups"].append(f"{comp}::{name} ({groups or 'none'})")
    return out


_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_WHILE_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_INT_CONST_RE = re.compile(r"^\s*(-?\d+)\s*$")


def _computation_closure(module: HloModule, root: str) -> set[str]:
    """``root`` plus every computation transitively called from it."""
    seen: set[str] = set()
    work = [root]
    while work:
        c = work.pop()
        if c in seen or c not in module.computations:
            continue
        seen.add(c)
        for i in module.computations[c].instructions.values():
            work.extend(i.called)
    return seen


def ring_scan_trip_counts(module: HloModule) -> list[int]:
    """Trip counts of the rotation scan(s): every ``while`` whose body
    (transitively) contains a ``collective-permute``, with the bound read
    from the compare-against-constant in its condition computation. This is
    how the bidir round-count claim (⌊P/2⌋+1 scan steps instead of P) is
    machine-checked from the lowered HLO instead of trusted from the Python
    that emitted it (tests/test_hlo_overlap.py; the dump artifact records
    it in overlap_verdict.json). Inner tile loops (``lax.map`` over query
    tiles, the corpus-tile scan) contain no collectives and are excluded by
    construction."""
    out = []
    for c in module.computations.values():
        for i in c.instructions.values():
            if i.opcode != "while":
                continue
            mb = _WHILE_BODY_RE.search(i.attrs)
            mc = _WHILE_COND_RE.search(i.attrs)
            if not mb or not mc:
                continue
            has_permute = any(
                instr.opcode.startswith(RING_COLLECTIVE)
                for comp in _computation_closure(module, mb.group(1))
                for instr in module.computations[comp].instructions.values()
            )
            if not has_permute:
                continue
            cond = module.computations.get(mc.group(1))
            if cond is None:
                continue
            for ci in cond.instructions.values():
                if ci.opcode != "compare" or "direction=LT" not in ci.attrs:
                    continue
                for op in ci.operands:
                    src = cond.instructions.get(op)
                    if src is None or src.opcode != "constant":
                        continue
                    m = _INT_CONST_RE.match(src.operand_text)
                    if m:
                        out.append(int(m.group(1)))
    return out


# ---------------------------------------------------------------------------
# R5: donation/aliasing of the serving batch program

# module-header alias entry: `{output_index}: (param, {param_index}, kind)`
_ALIAS_ENTRY_RE = re.compile(
    r"\{\s*(\d*)\s*\}\s*:\s*\(\s*(\d+)\s*,\s*\{[^}]*\}\s*,"
    r"\s*(?:may|must)-alias\s*\)"
)
# buffer_donor entry (pre-optimization form on sharded programs, where the
# concrete aliasing is resolved at compile time): `(param, {param_index})`
_DONOR_ENTRY_RE = re.compile(r"\(\s*(\d+)\s*,\s*\{[^}]*\}\s*\)")


def _header_group(header: str, attr: str) -> str | None:
    """The balanced ``{...}`` payload of a module-header attribute."""
    start = header.find(attr + "={")
    if start < 0:
        return None
    i = start + len(attr) + 1
    depth = 0
    for j in range(i, len(header)):
        if header[j] == "{":
            depth += 1
        elif header[j] == "}":
            depth -= 1
            if depth == 0:
                return header[i: j + 1]
    return header[i:]


def output_aliases(module: HloModule) -> dict[int, int]:
    """``{output_index: param_number}`` from the module header's
    ``input_output_alias`` (a single non-tuple output is index 0). Output
    indices — not python argnums — are the stable coordinate: jax elides
    unused arguments from the lowered program, renumbering parameters."""
    grp = _header_group(module.header, "input_output_alias")
    if not grp:
        return {}
    return {
        int(out or 0): int(param)
        for out, param in _ALIAS_ENTRY_RE.findall(grp)
    }


def donor_params(module: HloModule) -> set[int]:
    """Parameter numbers declared in ``buffer_donor`` (the not-yet-resolved
    donation form jax emits for sharded programs before optimization)."""
    grp = _header_group(module.header, "buffer_donor")
    if not grp:
        return set()
    return {int(p) for p in _DONOR_ENTRY_RE.findall(grp)}


def entry_output_count(module: HloModule) -> int:
    """Top-level output arity of the entry computation, read from the
    header's ``entry_computation_layout`` ``->(...)`` group (1 for a
    non-tuple output)."""
    m = re.search(r"->", module.header)
    if not m:
        return 0
    rest = module.header[m.end():].lstrip()
    if not rest.startswith("("):
        return 1
    depth = 0
    count = 1
    for ch in rest:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
            if depth == 0:
                break
        elif ch == "," and depth == 1:
            count += 1
    return count


def oversized_copies(module: HloModule, threshold_bytes: int):
    """``copy``/``copy-start`` instructions materializing a buffer of at
    least ``threshold_bytes`` (async pairs: the ``-start`` carries the
    semantics; ``copy-done`` returns the same buffer and is skipped)."""
    out = []
    for c in module.computations.values():
        for i in c.instructions.values():
            if i.opcode not in ("copy", "copy-start"):
                continue
            b = max_buffer_bytes(i.type_str)
            if b >= threshold_bytes:
                out.append((c.name, i.name, b))
    return out


@register
class R5Donation(Rule):
    name = "R5-donation"
    description = (
        "serving batch programs declare the per-batch scratch donation in "
        "the module header (buffer_donor before opt, input_output_alias "
        "after) and contain no resident-corpus-sized copy — steady-state "
        "serving must reuse memory in place, not re-pay the corpus"
    )

    def applies(self, ctx) -> bool:
        # serve batch programs AND the live-mutation programs (ISSUE 14:
        # the donation contract extends to upsert/delete/compact — an
        # un-donated store update would re-pay the corpus per chunk)
        return bool(
            getattr(ctx.target, "serve", False)
            or getattr(ctx.target, "mutate", "")
        )

    def check(self, ctx, stage, module) -> list[Finding]:
        out = []
        if ctx.meta.get("donated_params"):
            aliases = output_aliases(module)
            n_out = entry_output_count(module)
            unaliased = sorted(set(range(n_out)) - set(aliases))
            if unaliased and stage == "after_opt":
                # the compiled program is the ground truth: every output
                # buffer must alias a donated input or each batch
                # allocates fresh result+scratch memory — the in-place
                # steady state the engine promises did not materialize
                # (declared-but-dropped donation lands here too)
                out.append(
                    Finding(
                        self.name,
                        ctx.target.label,
                        stage,
                        f"output buffer(s) {unaliased} of {n_out} carry "
                        "no input_output_alias in the compiled program — "
                        "the donated scratch is not reused in place; "
                        "every batch allocates fresh result memory",
                        {"aliases": {str(k): v
                                     for k, v in aliases.items()},
                         "outputs": n_out},
                    )
                )
            elif not aliases and not donor_params(module):
                # before optimization the donation may still be the
                # unresolved buffer_donor form (sharded programs); what is
                # NOT acceptable is a serve program with no donation
                # declaration at all
                out.append(
                    Finding(
                        self.name,
                        ctx.target.label,
                        stage,
                        "serve program declares no donation at all (no "
                        "input_output_alias, no buffer_donor) — every "
                        "batch allocates a fresh carry instead of "
                        "reusing the donated one in place",
                        {"outputs": n_out},
                    )
                )
        resident = ctx.meta.get("resident_bytes", 0)
        if resident:
            # Deliberate blind spot, not an oversight: on ring cells the
            # compiled SPMD module is per-shard, and a shard-sized copy is
            # the ROTATION ITSELF (each round copies the traveling block —
            # exactly c_pad/ring_n rows — through the loop state), so a
            # per-shard threshold flags every correct ring program. A
            # redundant local-shard copy is size-indistinguishable from
            # that legitimate traffic; the census therefore keeps the
            # GLOBAL corpus bound everywhere (it still catches full-corpus
            # materializations, and R4's collective accounting covers the
            # regather class on rings).
            for comp, name, b in oversized_copies(module, resident):
                out.append(
                    Finding(
                        self.name,
                        ctx.target.label,
                        stage,
                        f"{comp}::{name} copies {b} bytes >= the resident "
                        f"corpus ({resident} bytes) inside the per-batch "
                        "program — the corpus the index amortized is being "
                        "re-copied every batch",
                        {"bytes": b, "resident_bytes": resident},
                    )
                )
        return out


@register
class R4Collectives(Rule):
    name = "R4-collective"
    description = (
        "ring programs contain exactly the corpus-rotation permutes "
        "(uni: one forward pair; bidir: two counter-directed pairs) with "
        "ring-shaped source_target_pairs; sharded-IVF programs exactly "
        "the candidate-exchange all-to-alls (full-ring groups, payload "
        "inside the declared budget); single-device programs contain no "
        "collectives — anything else is a sharding leak"
    )

    def applies(self, ctx) -> bool:
        # sharded-store MUTATION programs are GSPMD-partitioned scatters:
        # they have no candidate exchange to account (the partitioner
        # owns whatever plumbing it emits), so the sharded-exchange
        # checker has no claim there; single-device mutation cells keep
        # the no-collectives check like every other single-device program
        if (
            getattr(ctx.target, "mutate", "")
            and ctx.target.backend == "ivf-sharded"
        ):
            return False
        return True

    def _check_sharded_exchange(self, ctx, stage, module, found):
        """The sharded-IVF accounting: the candidate exchange is EXACTLY
        ``expected_alltoalls`` all-to-alls per tile (request table + the
        rows/ids/norms returns), each over the single full-ring replica
        group, with total payload bytes inside the declared per-tile
        exchange budget. Anything else — a collective-permute (this
        search has no rotation), an all-gather/broadcast (an unrouted
        full-bucket exchange would re-centralize the corpus the sharding
        exists to distribute), a partial replica group, or an over-budget
        payload — is a finding."""
        t = ctx.target
        out = []
        for op, hits in found.items():
            if op == "all-to-all":
                continue
            out.append(
                Finding(
                    self.name,
                    t.label,
                    stage,
                    f"sharded-clustered program contains a stray {op} "
                    f"({len(hits)}×, e.g. {hits[0][1]}) — the only legal "
                    "collective is the routed candidate exchange's "
                    "all-to-all; an unrouted broadcast/gather would move "
                    "whole bucket stores instead of routed candidates",
                    {"op": op, "count": len(hits)},
                )
            )
        census = alltoall_census(module, ctx.meta.get("shards", 0))
        if stage == "before_opt":
            expected = ctx.meta.get("expected_alltoalls")
            if expected is not None and census["count"] != expected:
                out.append(
                    Finding(
                        self.name,
                        t.label,
                        stage,
                        f"expected exactly {expected} all-to-alls per tile "
                        "(request table + rows/ids/norms candidate "
                        f"returns), found {census['count']}",
                        {"count": census["count"]},
                    )
                )
            for bad in census["bad_groups"]:
                out.append(
                    Finding(
                        self.name,
                        t.label,
                        stage,
                        f"{bad} replica_groups is not the single full-"
                        f"ring group over {ctx.meta.get('shards')} shards "
                        "— a partial-group exchange cannot reach every "
                        "owner the routing table names",
                        {"shards": ctx.meta.get("shards")},
                    )
                )
            budget = ctx.meta.get("exchange_bytes_tile")
            if budget is not None and census["bytes"] > budget:
                out.append(
                    Finding(
                        self.name,
                        t.label,
                        stage,
                        f"candidate exchange moves {census['bytes']} bytes "
                        f"per tile > the declared budget {budget} "
                        "(shards·route_cap·(request + bucket payload)) — "
                        "an over-budget per-shard gather is scanning more "
                        "than it routed",
                        {"bytes": census["bytes"], "budget": budget},
                    )
                )
        elif census["count"] == 0:
            out.append(
                Finding(
                    self.name,
                    t.label,
                    stage,
                    "sharded-clustered program compiled to zero "
                    "all-to-alls — the candidate exchange was optimized "
                    "away (results can only be correct if no query ever "
                    "probes a remote shard, i.e. they are not)",
                    {},
                )
            )
        return out

    def check(self, ctx, stage, module) -> list[Finding]:
        found = count_collectives(module)
        t = ctx.target
        out = []
        if t.backend == "ivf-sharded":
            return self._check_sharded_exchange(ctx, stage, module, found)
        if t.backend not in ("ring", "ring-overlap"):
            for op, hits in found.items():
                out.append(
                    Finding(
                        self.name,
                        t.label,
                        stage,
                        f"single-device backend lowered a collective: "
                        f"{len(hits)}× {op} ({hits[0][1]}, …) — sharding "
                        "leak",
                        {"op": op, "count": len(hits)},
                    )
                )
            return out

        for op in STRAY_COLLECTIVES:
            if op in found:
                hits = found[op]
                out.append(
                    Finding(
                        self.name,
                        t.label,
                        stage,
                        f"ring program contains a stray {op} "
                        f"({len(hits)}×, e.g. {hits[0][1]}) — a sharding "
                        "leak would regather the corpus every round",
                        {"op": op, "count": len(hits)},
                    )
                )
        permutes = found.get(RING_COLLECTIVE, [])
        expected = ctx.meta.get("expected_permutes")
        # wire-dtype pricing (quantized transfer cells): every rotation
        # permute's payload must fit the block's WIRE bytes — int8 codes
        # for the block, s32/f32 for the small id/scale rows. A permute
        # moving 4× the budget is rotating float rows under an int8
        # label: the recall cost of quantization with none of the byte
        # win. Before-opt only (the combiner may later legally fuse the
        # three permutes into one tuple-typed collective).
        pbudget = ctx.meta.get("permute_bytes_budget")
        if stage == "before_opt" and pbudget is not None:
            for comp, name in permutes:
                b = max_buffer_bytes(module.instr(comp, name).type_str)
                if b > pbudget:
                    out.append(
                        Finding(
                            self.name,
                            t.label,
                            stage,
                            f"{comp}::{name} moves {b} bytes > the "
                            f"wire-dtype budget {pbudget} (the int8 code "
                            "block) — the rotation is shipping wider "
                            "payload than the declared transfer dtype",
                            {"bytes": b, "budget": pbudget},
                        )
                    )
        if stage == "before_opt" and expected is not None:
            sched = ctx.meta.get("ring_schedule", "uni")
            if len(permutes) != expected:
                out.append(
                    Finding(
                        self.name,
                        t.label,
                        stage,
                        f"expected exactly {expected} collective-permutes "
                        + (
                            "(corpus block + ids rotation, one pair per "
                            "torus direction)"
                            if sched == "bidir"
                            else "(corpus block + ids rotation)"
                        )
                        + f", found {len(permutes)}",
                        {"count": len(permutes)},
                    )
                )
            ring_n = ctx.meta.get("ring_n")
            if ring_n and sched == "bidir":
                # bidir accounting: 2 permutes per round per DIRECTION
                # (block + ids), counter-directed source_target_pairs.
                # A wrong-direction permute merges blocks in an order the
                # ⌊P/2⌋+1-round plan does not account for (results wrong);
                # a missing one means a traveler stopped moving (a silent
                # fallback to half-duplex) — both are findings.
                census = permute_direction_census(module, ring_n)
                for instr_label in census["other"]:
                    out.append(
                        Finding(
                            self.name,
                            t.label,
                            stage,
                            f"{instr_label} source_target_pairs is neither "
                            f"the forward nor the backward {ring_n}-ring "
                            "rotation — a wrong-direction permute breaks "
                            "the bidir round plan",
                            {"census": {k: census[k] for k in ("fwd", "bwd")}},
                        )
                    )
                if ring_n <= 2:
                    # the two rotations coincide on a <=2-ring (the census
                    # files everything under "fwd"), so only the combined
                    # count is checkable — a per-direction split here would
                    # fail every correct program
                    if census["fwd"] + census["bwd"] != expected:
                        out.append(
                            Finding(
                                self.name,
                                t.label,
                                stage,
                                f"bidir schedule must issue {expected} "
                                f"ring-rotation permutes on the {ring_n}-"
                                "ring (directions coincide there), found "
                                f"{census['fwd'] + census['bwd']}",
                                {"census": {k: census[k]
                                            for k in ("fwd", "bwd")}},
                            )
                        )
                else:
                    want_each = expected // 2
                    for direction in ("fwd", "bwd"):
                        if census[direction] != want_each:
                            out.append(
                                Finding(
                                    self.name,
                                    t.label,
                                    stage,
                                    "bidir schedule must rotate block + "
                                    f"ids in the {direction} direction "
                                    f"({want_each} permutes), found "
                                    f"{census[direction]} — a missing "
                                    "counter-directed permute is a silent "
                                    "fallback to half-duplex",
                                    {"census": {k: census[k]
                                                for k in ("fwd", "bwd")}},
                                )
                            )
            elif ring_n:
                want, _ = ring_rotation_pairs(ring_n)
                for comp, name in permutes:
                    pairs = _permute_pairs(module, comp, name)
                    if pairs is not None and pairs != want:
                        out.append(
                            Finding(
                                self.name,
                                t.label,
                                stage,
                                f"{comp}::{name} source_target_pairs "
                                f"{pairs} is not the {ring_n}-ring rotation",
                                {"pairs": pairs},
                            )
                        )
        elif stage == "after_opt" and not permutes:
            out.append(
                Finding(
                    self.name,
                    t.label,
                    stage,
                    "ring program compiled to zero collective-permutes "
                    "— the rotation was optimized away (results can "
                    "only be correct if the corpus never moved, i.e. "
                    "they are not)",
                    {},
                )
            )
        return out


# ---------------------------------------------------------------------------
# R6: clustered-index probe discipline

# a dot with a non-empty batch dimension list — the per-query candidate
# form (q, d) × (q, v, d): the only legal way corpus payload reaches a dot
# in an IVF program, because the batched candidate operand can only come
# from the per-query probe gather
_BATCH_DIMS_RE = re.compile(r"(?:lhs|rhs)_batch_dims=\{\s*\d")


@register
class R6IvfProbe(Rule):
    name = "R6-ivf-probe"
    description = (
        "clustered (IVF) programs score corpus payload ONLY through the "
        "probe gather: every batched candidate dot is fed by a gather, at "
        "least one exists, and no un-batched dot is wider than the "
        "centroid score — a full-corpus dot would bypass the partition "
        "pruning the index exists for"
    )

    def applies(self, ctx) -> bool:
        # the sharded form keeps the same probe discipline: the routed
        # exchange only ever moves gathered buckets, so every batched
        # candidate dot still carries a gather in its backward slice.
        # Mutation programs have no candidate dots at all (a scatter and
        # at most the centroid-score assignment) — the ≥1-probe-dot
        # vacuity guard would misfire there, so they are out of scope.
        if getattr(ctx.target, "mutate", ""):
            return False
        return getattr(ctx.target, "backend", None) in ("ivf", "ivf-sharded")

    def check(self, ctx, stage, module) -> list[Finding]:
        if stage != "before_opt":
            # after optimization fusion legitimately rewrites dots and
            # gathers into fusion computations; the declared dataflow is
            # pinned on the module XLA receives (the R3-contract stance)
            return []
        out = []
        n_batched = 0
        # un-batched dots may only be the centroid score: operands are the
        # (q_tile, d) query tile and the (partitions, d) routing table
        allowed = (
            max(ctx.meta.get("q_tile", 0), ctx.meta.get("partitions", 0))
            * ctx.meta.get("dim", 0)
        )
        for c in module.computations.values():
            for i in c.instructions.values():
                if i.opcode != "dot":
                    continue
                if _BATCH_DIMS_RE.search(i.attrs):
                    n_batched += 1
                    sl = backward_slice(module, c.name, i.name)
                    if "gather" not in slice_opcodes(module, sl):
                        out.append(
                            Finding(
                                self.name,
                                ctx.target.label,
                                stage,
                                f"{c.name}::{i.name} is a batched "
                                "candidate dot with NO gather in its "
                                "backward slice — it scores rows the "
                                "probe never selected (the partition "
                                "pruning is bypassed)",
                                {"type": i.type_str},
                            )
                        )
                elif allowed:
                    op_elems = max(
                        (
                            max_buffer_elems(c.instructions[o].type_str)
                            for o in i.operands
                            if o in c.instructions
                        ),
                        default=0,
                    )
                    if op_elems > allowed:
                        out.append(
                            Finding(
                                self.name,
                                ctx.target.label,
                                stage,
                                f"{c.name}::{i.name} is an un-batched dot "
                                f"over {op_elems} elems > the centroid "
                                f"score bound {allowed} (max(q_tile, "
                                "partitions)·d) — a full-corpus dot "
                                "bypasses the partition pruning",
                                {"elems": op_elems, "bound": allowed},
                            )
                        )
        if n_batched == 0:
            out.append(
                Finding(
                    self.name,
                    ctx.target.label,
                    stage,
                    "IVF program lowered NO batched candidate dot — the "
                    "probe-gather contract is vacuous (nothing scores the "
                    "gathered candidates exactly)",
                    {},
                )
            )
        return out


# ---------------------------------------------------------------------------
# R7: peak-HBM certification (ISSUE 15). The analyzer lives in
# analysis/memory.py (liveness model, aliasing, budget derivation, the
# PJRT cross-check, the ledger); this class is the registry adapter —
# the import direction is rules → memory ONLY, so memory.py keeps its
# own shape readers and can be unit-tested without the rule registry.

from mpi_knn_tpu.analysis import memory as _memory  # noqa: E402


@register
class R7PeakMemory(Rule):
    name = "R7-peak-memory"
    description = (
        "aliasing-aware liveness peak of the after-opt program: peak "
        "live bytes (def-use intervals, donated scratch counted once, "
        "while bodies loop-resident, fusions collapsed) must fit the "
        "budget derived from the cell's index facts, and must agree "
        "with PJRT's own memory_analysis() within the declared "
        "tolerance — disagreement is itself a finding"
    )

    def applies(self, ctx) -> bool:
        return True

    def check(self, ctx, stage, module) -> list[Finding]:
        return _memory.r7_check(ctx, stage, module, Finding)


# R8: the static cost certification. Everything substantive lives in
# analysis/cost.py (dot-FLOP counter with loop multiplicities, the
# closed-form exactness contract, the wire-priced collective census,
# the roofline, the cost ledger); this class is the registry adapter —
# the import direction is rules → cost ONLY, mirroring R7.

from mpi_knn_tpu.analysis import cost as _cost  # noqa: E402


@register
class R8Cost(Rule):
    name = "R8-cost"
    description = (
        "static cost model of the after-opt program: MXU FLOPs from dot "
        "shapes × statically-read loop trip counts must EXACTLY equal "
        "the closed-form count from the cell's declared configuration "
        "facts (disagreement in either direction is a finding), every "
        "collective-family opcode must be in the wire-price registry, "
        "and the FLOP/HBM/ICI totals land in the committed cost ledger "
        "with a roofline q/s bound under the declared device profile"
    )

    def applies(self, ctx) -> bool:
        return True

    def check(self, ctx, stage, module) -> list[Finding]:
        return _cost.r8_check(ctx, stage, module, Finding)


# registration order follows source position; the registry is presented in
# rule-number order regardless (R5's helpers sit above R4 in the file so
# they can share the R2 shape readers)
RULES.sort(key=lambda r: r.name)
