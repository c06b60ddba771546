"""The shardlint driver: collect artifacts → run rules → apply baseline.

:func:`lint` is the one entry point every consumer calls — the dryrun
gate, the tier-1 ``analysis`` suite, and ad-hoc standalone use::

    from paddle_tpu.analysis import lint
    report = lint(step, args=(ids, labels))     # a (Distributed)TrainStep
    report = lint(jax.jit(fn), args=(x,))       # any jitted callable
    print(report.format())
    assert report.ok

Findings check against the committed baseline
(:mod:`paddle_tpu.analysis.baseline`); a finding a baseline entry matches
is EXEMPTED (reported, never gating), everything else is NEW.  The
report's ``ok``/``failures()`` implement the gate.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

from .baseline import Baseline, load_baseline, strict_baseline_enabled
from .findings import Finding, LintReport, Severity
from .program import ProgramArtifacts, collect
from .rules import run_rules

__all__ = ["lint"]


def _resolve_baseline(baseline) -> Optional[Baseline]:
    if baseline is True:
        return load_baseline()
    if baseline in (None, False):
        return None
    if isinstance(baseline, Baseline):
        return baseline
    if isinstance(baseline, str):
        return load_baseline(baseline)
    raise TypeError(f"baseline must be bool/str/Baseline, "
                    f"got {type(baseline).__name__}")


def lint(target, args: Sequence[Any] = (), rules: Optional[List[str]] = None,
         baseline=True, config: Optional[dict] = None,
         name: Optional[str] = None, compile: bool = True,
         extra_source_fns: Sequence[Callable] = ()) -> LintReport:
    """Lint one program.  ``target`` is anything :func:`collect` can
    lower (TrainStep/DistributedTrainStep + example batch, AOTFunction,
    jitted or plain callable + example args, lowered/compiled object, or
    pre-built artifacts).  ``rules`` selects a rule-id subset (default
    all); ``baseline`` is True (committed default), a path, a
    :class:`Baseline`, or False for none."""
    artifacts = collect(target, args=args, name=name, compile=compile,
                        extra_source_fns=extra_source_fns)
    findings = run_rules(artifacts, rules=rules, config=config)
    bl = _resolve_baseline(baseline)
    if bl is not None:
        new, exempted = bl.apply(findings)
        unused = bl.unused()
    else:
        new, exempted, unused = findings, [], []
    if unused and strict_baseline_enabled():
        # strict mode (dryrun gate): a stale exemption is debt the table
        # still claims but the program no longer has — delete the entry
        for e in unused:
            new.append(Finding(
                rule="stale-baseline-exemption",
                severity=Severity.ERROR,
                subject=f"{e.get('rule', '*')}: {e.get('match', '')!r}",
                message="baseline exemption matched no finding in this "
                        "program; delete the entry from "
                        f"{getattr(bl, 'path', 'baseline.json')} "
                        f"(reason was: {e.get('reason', '?')})",
                fix="remove the exemption, or fix its regex if the defect "
                    "still exists under a different signature",
                source=getattr(bl, "path", None)))
    report = LintReport(
        name=artifacts.name, findings=new, exempted=exempted,
        unused_exemptions=unused,
        meta={"n_devices": artifacts.n_devices,
              "mesh": artifacts.mesh_shape,
              "rules": rules or "all",
              "baseline": getattr(bl, "path", None)})
    _record_telemetry(report)
    return report


def _record_telemetry(report: LintReport) -> None:
    """Flight-recorder event + counters per lint run; never raises."""
    try:
        from .. import telemetry

        telemetry.record_event(
            "lint", report.name, findings=sum(report.counts.values()),
            exempted=len(report.exempted), counts=report.counts,
            ok=report.ok)
        telemetry.bump("lint_runs_total")
        n = sum(report.counts.values())
        if n:
            telemetry.bump("lint_findings_total", n)
    except Exception:
        pass
