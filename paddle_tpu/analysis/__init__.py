"""paddle_tpu.analysis — **shardlint**, the SPMD/HLO static linter.

The repo inspected optimized HLO in ad-hoc places (a wire-byte walk, the
hand-written ``ParallelCrossEntropy`` no-``[B,V]``-all-gather assert);
this subsystem promotes that pattern into a first-class tool: anything
the ``compile/`` subsystem can lower — an
:class:`~paddle_tpu.jit.TrainStep` /
:class:`~paddle_tpu.distributed.engine.DistributedTrainStep`, an
:class:`~paddle_tpu.compile.AOTFunction`, a jitted callable, a raw
lowered/compiled object — runs through a rule set over the optimized HLO
text, the jaxpr, the compiled memory analysis and the captured
partitioner diagnostics, emitting structured findings (rule id,
severity, op/tensor, priced byte cost, suggested fix).

Layers:

- :mod:`.findings`     — :class:`Finding` / :class:`LintReport`;
- :mod:`.program`      — artifact collection incl. fd-level capture of
  the XLA compile diagnostics (:func:`capture_compile_diagnostics`);
- :mod:`.rules`        — the rule registry (see its docstring for the
  rule table);
- :mod:`.baseline`     — the committed exemption table
  (``baseline.json``): known debt pinned with justifications, new
  findings fail, fixes shrink the file;
- :mod:`.source_check` — the repo-source AST check enforcing the
  ``framework/jax_compat`` shard_map/pcast seam;
- :mod:`.linter`       — :func:`lint`, the one entry point.

Gates wired on top: ``__graft_entry__.dryrun_multichip`` fails loudly on
unexempted involuntary-remat findings in every factorization, and the
tier-1 ``analysis`` pytest marker runs the fixture + clean-program suites.
"""

from .annotations import host_sync_ok, is_host_sync_ok  # noqa: F401

# everything else resolves lazily (PEP 562): runtime code that only wants
# the import-light annotations (the snapshot capture path marks itself
# @host_sync_ok) must not drag the linter's jax-lowering machinery into
# every `import paddle_tpu`
_LAZY = {
    "lint": ".linter",
    "ProgramArtifacts": ".program", "collect": ".program",
    "capture_compile_diagnostics": ".program",
    "jaxpr_primitives": ".program",
    "RULES": ".rules", "run_rules": ".rules",
    "Finding": ".findings", "LintReport": ".findings",
    "Severity": ".findings",
    "Baseline": ".baseline", "load_baseline": ".baseline",
    "strict_baseline_enabled": ".baseline",
    "DEFAULT_BASELINE_PATH": ".baseline",
    "parse_partitioner_diagnostics": ".rules.remat",
    "analyze_perm": ".rules.ring", "check_overlap_rings": ".rules.ring",
    "check_jax_compat_seam": ".source_check",
    "check_source_text": ".source_check",
}


def __getattr__(name: str):
    try:
        target = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    from importlib import import_module

    return getattr(import_module(target, __name__), name)


__all__ = [
    "lint", "collect", "run_rules", "RULES",
    "Finding", "LintReport", "Severity", "ProgramArtifacts",
    "Baseline", "load_baseline", "strict_baseline_enabled",
    "DEFAULT_BASELINE_PATH",
    "capture_compile_diagnostics", "jaxpr_primitives",
    "parse_partitioner_diagnostics", "analyze_perm", "check_overlap_rings",
    "check_jax_compat_seam", "check_source_text",
    "host_sync_ok", "is_host_sync_ok",
]
