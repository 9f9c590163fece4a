"""Rule engine: lower each configuration, parse both stages, run every
applicable rule, aggregate a machine-readable report.

The report is the artifact: ``mpi-knn lint`` writes it to
``artifacts/lint/report.json`` and exits non-zero on any violation, so a
CI step (scripts/check.sh) — or a human before a TPU reservation — gets a
single yes/no with the full evidence attached.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from dataclasses import field

import jax

from mpi_knn_tpu.analysis import rules as rules_mod
from mpi_knn_tpu.analysis.lowering import (
    LintTarget,
    UnsupportedTarget,
    default_targets,
    lower_target,
)
from mpi_knn_tpu.analysis.rules import Finding, rules_by_name
from mpi_knn_tpu.utils.hlo_graph import parse_hlo

SCHEMA_VERSION = 1


@dataclasses.dataclass
class LintContext:
    """What a rule may know about the program under inspection: the matrix
    cell, the config it was lowered with, and lowering metadata (tile
    sizes, accumulation width, ring topology)."""

    target: LintTarget
    cfg: object
    meta: dict


@dataclasses.dataclass
class TargetResult:
    target: LintTarget
    findings: list[Finding] = field(default_factory=list)
    rules_run: list[str] = field(default_factory=list)
    stages: list[str] = field(default_factory=list)
    skipped: str | None = None  # UnsupportedTarget reason
    # R7's per-cell memory ledger entry (peak live bytes, attribution,
    # budget, PJRT cross-check numbers) — populated whenever the
    # peak-memory rule ran on the after-opt stage (analysis.memory)
    memory: dict | None = None
    # R8's per-cell cost ledger entry (MXU FLOPs + the analytical
    # cross-check, modeled HBM traffic, wire-priced ICI bytes, roofline
    # under the default profile) — populated whenever the cost rule ran
    # on the after-opt stage (analysis.cost)
    cost: dict | None = None

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> dict:
        return {
            "label": self.target.label,
            "backend": self.target.backend,
            "metric": self.target.metric,
            "dtype": self.target.dtype,
            "policy": self.target.policy,
            "schedule": self.target.schedule,
            "quant": self.target.quant,
            "serve": self.target.serve,
            "ladder": self.target.ladder,
            "frontend": self.target.frontend,
            "mutate": self.target.mutate,
            "ok": self.ok,
            "skipped": self.skipped,
            "rules_run": self.rules_run,
            "stages": self.stages,
            "findings": [f.to_json() for f in self.findings],
            "memory": self.memory,
            "cost": self.cost,
        }


@dataclasses.dataclass
class LintReport:
    results: list[TargetResult]

    @property
    def ok(self) -> bool:
        return all(r.ok for r in self.results)

    @property
    def findings(self) -> list[Finding]:
        return [f for r in self.results for f in r.findings]

    def to_json(self) -> dict:
        checked = [r for r in self.results if r.skipped is None]
        return {
            "schema_version": SCHEMA_VERSION,
            "source": "mpi_knn_tpu.analysis",
            "jax_version": jax.__version__,
            "platform": jax.default_backend(),
            "device_count": jax.device_count(),
            "ok": self.ok,
            "summary": {
                "targets_checked": len(checked),
                "targets_skipped": len(self.results) - len(checked),
                "findings": len(self.findings),
            },
            "targets": [r.to_json() for r in self.results],
        }

    def save(self, out_dir) -> pathlib.Path:
        out_dir = pathlib.Path(out_dir)
        out_dir.mkdir(parents=True, exist_ok=True)
        path = out_dir / "report.json"
        path.write_text(json.dumps(self.to_json(), indent=1) + "\n")
        return path


def run_rules(
    texts: dict[str, str],
    ctx: LintContext,
    rules: list | None = None,
) -> tuple[list[Finding], list[str]]:
    """Run rules over already-lowered stage texts. Split out from
    :func:`lint_target` so tests can feed deliberately broken lowerings
    (a de-tiled distance matrix, an injected sharding leak) through the
    exact production rule path."""
    rules = rules_mod.RULES if rules is None else rules
    findings: list[Finding] = []
    ran: list[str] = []
    applicable = [r for r in rules if r.applies(ctx)]
    for rule in applicable:
        ran.append(rule.name)
    for stage, text in texts.items():
        module = parse_hlo(text)
        for rule in applicable:
            findings.extend(rule.check(ctx, stage, module))
    return findings, ran


def lint_target(
    target: LintTarget, rule_names: list[str] | None = None
) -> TargetResult:
    """Lower one matrix cell and run every applicable rule on both stages."""
    rules = rules_by_name(rule_names)
    res = TargetResult(target=target)
    try:
        texts, cfg, meta = lower_target(target)
    except UnsupportedTarget as e:
        res.skipped = str(e)
        return res
    res.stages = list(texts)
    # a per-run copy: lower_target's meta is lru_cached and shared across
    # runs, and R7 stashes its ledger entry into the context's meta
    ctx = LintContext(target=target, cfg=cfg, meta=dict(meta))
    res.findings, res.rules_run = run_rules(texts, ctx, rules)
    res.memory = ctx.meta.get("r7_analysis")
    res.cost = ctx.meta.get("r8_analysis")
    return res


def run_matrix(
    targets: list[LintTarget] | None = None,
    rule_names: list[str] | None = None,
    progress=None,
) -> LintReport:
    """The full backend × metric × dtype sweep (or a filtered subset)."""
    targets = default_targets() if targets is None else targets
    results = []
    for t in targets:
        r = lint_target(t, rule_names)
        if progress is not None:
            progress(r)
        results.append(r)
    return LintReport(results=results)
