"""shardlint (paddle_tpu.analysis) suite — tier-1 ``analysis`` marker.

Structure per the PR-7 contract:

- one deliberately-BAD fixture program per rule, proving each rule fires
  (inconsistent stage-boundary specs → involuntary-remat; replicated
  logits → replication-blowup; undonated opt-state → donation; host sync
  in a step fn → host-sync; broken ppermute cycle → ring-consistency);
- a CLEAN-program suite proving zero false positives on the shipped
  GPT/Llama train steps;
- the baseline/exemption machinery, the partitioner-diagnostic parser
  (BOTH xla message dialects), and the repo-source jax_compat seam check.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.analysis import (Baseline, Finding, Severity, analyze_perm,
                                 check_jax_compat_seam, check_overlap_rings,
                                 check_source_text, lint, load_baseline,
                                 parse_partitioner_diagnostics)

pytestmark = pytest.mark.analysis


@pytest.fixture(scope="class")
def interpreted_kernels():
    """Pallas kernels through the interpreter while a class's programs are
    traced (class scope: ``hybrid_step`` is traced lazily by later tests);
    the flag goes back afterwards, so no later test of the worker
    (``test_tpu_lowering.py`` lowers the real kernels) inherits it."""
    prior = paddle.get_flags(["pallas_interpret"])
    paddle.set_flags({"pallas_interpret": True})
    yield
    paddle.set_flags(prior)


def _mesh(axes):
    names = tuple(axes)
    sizes = tuple(axes[a] for a in names)
    n = int(np.prod(sizes))
    return Mesh(np.array(jax.devices()[:n]).reshape(sizes), names)


# ---------------------------------------------------------------------------
# partitioner-diagnostic parser: both xla message dialects

_DIALECT_NEW = (
    'E0804 11:48:25.489329 1 spmd_partitioner.cc:613] [spmd] Involuntary '
    'full rematerialization. The compiler was not able to go from sharding '
    '{devices=[4,1,2]<=[2,2,2]T(0,2,1) last_tile_dim_replicate} to '
    '{devices=[1,2,4]<=[4,2]T(1,0) last_tile_dim_replicate} without doing '
    'a full rematerialization of the tensor for HLO operation: '
    '%reshape.3473 = f32[64,64]{1,0} reshape(f32[4096]{0} %copy), '
    'sharding={devices=[4,1,2]<=[2,2,2]T(0,2,1) last_tile_dim_replicate}, '
    'metadata={op_name="jit(_step)/jit(main)/reshape" '
    'source_file="/root/repo/paddle_tpu/distributed/overlap/bucketer.py" '
    'source_line=127}. You probably want to enrich the sharding '
    'annotations to prevent this from happening.')

_DIALECT_OLD = (
    'W0731 07:16:07.363084 26465 spmd_partitioner.cc:652] [SPMD] '
    'Involuntary full rematerialization. The compiler cannot go from '
    'sharding {devices=[4,1,1,2]<=[2,2,2]T(0,2,1) last_tile_dim_replicate} '
    'to {devices=[1,1,2,4]<=[4,2]T(1,0) last_tile_dim_replicate} '
    'efficiently for HLO operation %fake_parameter.2 = f32[1,16,64]{2,1,0} '
    'parameter(2), sharding={devices=[4,1,1,2]<=[2,2,2]T(0,2,1) '
    'last_tile_dim_replicate}. As the last resort, SPMD will replicate '
    'the tensor and then partition it to obtain the target sharding, '
    'which is inefficient.')


class TestDiagnosticParser:
    def test_new_dialect(self):
        (r,) = parse_partitioner_diagnostics(_DIALECT_NEW, n_devices=8)
        assert r["op_kind"] == "reshape"
        assert r["dtype"] == "f32" and r["dims"] == "64,64"
        assert r["source"].endswith("overlap/bucketer.py:127")
        # devices=[4,1,2] + last_tile_dim_replicate: 4 SHARDS x2 replicas
        # — the gather ring runs over the shards, not all 8 devices
        assert r["participants"] == 4
        assert r["full_bytes"] == 64 * 64 * 4
        assert r["wire_bytes"] == int(64 * 64 * 4 * 3 / 4)

    def test_participants_without_replicate_dim(self):
        line = _DIALECT_NEW.replace(" last_tile_dim_replicate", "")
        (r,) = parse_partitioner_diagnostics(line, n_devices=8)
        assert r["participants"] == 8
        assert r["wire_bytes"] == int(64 * 64 * 4 * 7 / 8)

    def test_old_dialect(self):
        (r,) = parse_partitioner_diagnostics(_DIALECT_OLD, n_devices=8)
        assert r["op_kind"] == "fake_parameter"
        assert r["dims"] == "1,16,64"
        assert r["source"] is None
        assert r["wire_bytes"] > 0

    def test_mixed_and_noise(self):
        noise = "I0000 something harmless\nW0000 another log line\n"
        recs = parse_partitioner_diagnostics(
            noise + _DIALECT_NEW + "\n" + _DIALECT_OLD, 8)
        assert len(recs) == 2


# ---------------------------------------------------------------------------
# rule fixtures: one deliberately-bad program per rule


class TestInvoluntaryRematFixture:
    """The ZeRO-3 × pipe-stacked mini hybrid step (the north-star
    sharding2×pp2×dp2 layout mix) used to trip the partitioner's
    involuntary-remat warnings at every stage boundary.  The engine now
    single-homes param/activation specs across both layouts, so the SAME
    program must lint clean with no baseline at all — the debt is paid,
    not exempted.  (The rule machinery itself stays covered by TestParse
    and TestBaseline on synthetic diagnostics.)"""

    @pytest.fixture(scope="class")
    def hybrid_step(self, interpreted_kernels):
        strategy = dist.fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 2, "mp_degree": 1, "pp_degree": 2,
            "sharding_degree": 2, "sep_degree": 1}
        dist.fleet.init(is_collective=True, strategy=strategy)
        hcg = dist.get_hybrid_communicate_group()
        paddle.seed(0)
        from paddle_tpu.models.llama import llama_tiny
        from paddle_tpu.models.llama_parallel import LlamaForCausalLMHybrid

        cfg = llama_tiny(num_hidden_layers=4, num_attention_heads=4,
                         num_key_value_heads=2)
        model = LlamaForCausalLMHybrid(cfg, hcg)
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        step = dist.DistributedTrainStep(
            model, lambda m, x, y: m(x, labels=y)[0], opt, hcg,
            sharding_stage=3)
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (8, 16)).astype("int32"))
        lbl = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (8, 16)).astype("int32"))
        return step, (ids, lbl)

    @pytest.fixture(scope="class")
    def hybrid_report(self, hybrid_step):
        # ONE lint per process: a second compile of the identical program
        # hits jax's in-process compilation cache and emits no fresh
        # partitioner diagnostics — all assertions read this report
        step, batch = hybrid_step
        return lint(step, args=batch, baseline=False)

    def test_no_involuntary_remat_without_baseline(self, hybrid_report):
        remats = [f for f in hybrid_report.findings
                  if f.rule == "involuntary-remat"]
        assert remats == [], "\n".join(f.format() for f in remats)

    def test_committed_baseline_carries_no_debt(self, hybrid_report):
        from paddle_tpu.analysis import load_baseline as _lb

        bl = _lb()  # the committed baseline.json
        assert bl.exemptions == [], \
            "spec single-homing paid the remat debt; keep baseline.json empty"
        new, exempted = bl.apply(list(hybrid_report.findings))
        assert new == [] and exempted == []

    def test_donation_clean_on_hybrid_step(self, hybrid_step):
        """The pinned-sharding donated step must NOT trip the donation
        rule (alias bytes cover the state)."""
        step, batch = hybrid_step
        report = lint(step, args=batch, baseline=False, rules=["donation"])
        assert report.ok, report.format()


class TestReplicationBlowupFixture:
    def test_replicated_logits_fire(self):
        mesh = _mesh({"model": 2})
        B, V = 8, 64

        def loss(lg):
            lg = jax.lax.with_sharding_constraint(
                lg, NamedSharding(mesh, P(None, "model")))
            # the seeded bug: gather the full [B, V] row on every device
            full = jax.lax.with_sharding_constraint(
                lg * 2.0, NamedSharding(mesh, P(None, None)))
            return jnp.sum(full)

        logits = jnp.zeros((B, V), jnp.float32)
        report = lint(jax.jit(loss), args=(logits,), baseline=False,
                      rules=["replication-blowup"],
                      config={"replication_threshold_bytes": B * V * 4})
        assert not report.ok, "replicated [B,V] logits not flagged"
        f = report.failures()[0]
        assert f.rule == "replication-blowup"
        assert f.cost_bytes >= B * V * 4

    def test_sharded_ce_is_clean(self):
        """The fixed ParallelCrossEntropy pattern (elementwise + psum)
        stays below threshold — zero false positives."""
        mesh = _mesh({"model": 2})
        B, V = 8, 64
        labels = jnp.zeros((B,), jnp.int32)

        def loss(lg):
            lg = jax.lax.with_sharding_constraint(
                lg, NamedSharding(mesh, P(None, "model")))
            onehot = jax.nn.one_hot(labels, V, dtype=lg.dtype)
            onehot = jax.lax.with_sharding_constraint(
                onehot, NamedSharding(mesh, P(None, "model")))
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            return jnp.sum(lse - jnp.sum(onehot * lg, axis=-1))

        logits = jnp.zeros((B, V), jnp.float32)
        report = lint(jax.jit(loss), args=(logits,), baseline=False,
                      rules=["replication-blowup"],
                      config={"replication_threshold_bytes": B * V * 4})
        assert report.ok, report.format()


class TestDonationFixture:
    def test_undonated_opt_state_fires(self):
        # 2 MB of "opt state" updated without donation: a full second
        # copy lives across the update
        state = jnp.zeros((512, 1024), jnp.float32)

        def update(s, g):
            return s * 0.9 + g

        report = lint(jax.jit(update), args=(state, state),
                      baseline=False, rules=["donation"])
        assert not report.ok, "undonated multi-MB state not flagged"
        f = report.failures()[0]
        assert f.rule == "donation"
        assert f.cost_bytes >= state.size * 4

    def test_donated_update_is_clean(self):
        state = jnp.zeros((512, 1024), jnp.float32)

        def update(s, g):
            return s * 0.9 + g

        report = lint(jax.jit(update, donate_argnums=(0,)),
                      args=(state, state), baseline=False,
                      rules=["donation"])
        assert report.ok, report.format()

    def test_donate_false_step_reports_cost(self):
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny

        paddle.seed(0)
        cfg = llama_tiny(num_hidden_layers=1)
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        step = paddle.jit.TrainStep(
            model, lambda m, x, y: m(x, labels=y)[0], opt, donate=False)
        ids = paddle.to_tensor(np.zeros((2, 8), dtype="int32"))
        report = lint(step, args=(ids, ids), baseline=False,
                      rules=["donation"],
                      config={"donation_threshold_bytes": 1024})
        warns = [f for f in report.findings if f.rule == "donation"]
        assert warns and warns[0].severity == Severity.WARNING
        assert warns[0].cost_bytes > 0


class TestHostSyncFixture:
    def test_host_sync_in_step_fn_fires(self):
        def bad_step(m, x, y):
            loss = m(x, labels=y)[0]
            logged = float(loss)  # noqa: F841  device->host sync
            arr = np.asarray(x)   # noqa: F841  another one
            return loss

        # scan source only (tracing the bad fn would raise on float())
        from paddle_tpu.analysis import ProgramArtifacts, run_rules

        art = ProgramArtifacts(name="bad_step", source_fns=[bad_step])
        findings = run_rules(art, rules=["host-sync"])
        subjects = " ".join(f.subject for f in findings)
        assert "float()" in subjects
        assert "np.asarray" in subjects

    def test_callback_in_jaxpr_fires(self):
        def noisy(x):
            jax.debug.print("x={x}", x=x.sum())
            return x * 2

        report = lint(noisy, args=(jnp.zeros((4,)),), baseline=False,
                      rules=["host-sync"], compile=False)
        assert any("callback" in f.subject for f in report.findings), \
            report.format()

    def test_clean_loss_fn(self):
        from paddle_tpu.analysis import ProgramArtifacts, run_rules

        art = ProgramArtifacts(
            name="clean", source_fns=[lambda m, x, y: m(x, labels=y)[0]])
        assert run_rules(art, rules=["host-sync"]) == []

    def test_host_sync_ok_exempts_decorated_fn(self):
        """The scoped exemption (PR-8 snapshot rider): a function marked
        @host_sync_ok — the snapshot capture path's deliberate device-get
        — is skipped whether the linter sees the object (attribute) or
        only its source (AST decorator), while an undecorated twin with
        the identical body keeps flagging."""
        from paddle_tpu.analysis import (ProgramArtifacts, host_sync_ok,
                                         run_rules)

        @host_sync_ok(reason="deliberate snapshot device-get")
        def capture_like(state):
            return np.asarray(state)  # the deliberate host sync

        def stray(state):
            return np.asarray(state)  # same body, no blessing

        art = ProgramArtifacts(name="mixed",
                               source_fns=[capture_like, stray])
        findings = run_rules(art, rules=["host-sync"])
        subjects = " ".join(f.subject for f in findings)
        assert "stray" in subjects
        assert "capture_like" not in subjects

    def test_host_sync_ok_exempts_inner_def_by_ast(self):
        """A decorated INNER def inside a linted function is skipped as a
        subtree; syncs outside it still fire."""
        from paddle_tpu.analysis import ProgramArtifacts, run_rules

        def step_fn(m, x):
            from paddle_tpu.analysis import host_sync_ok

            @host_sync_ok
            def snap(v):
                return np.asarray(v)  # blessed subtree

            logged = float(x)  # noqa: F841  stray: must still flag
            return snap(m(x))

        art = ProgramArtifacts(name="inner", source_fns=[step_fn])
        findings = run_rules(art, rules=["host-sync"])
        subjects = " ".join(f.subject for f in findings)
        assert "float()" in subjects
        assert "np.asarray" not in subjects

    def test_shipped_snapshot_capture_is_marked(self):
        """The real snapshot capture path carries the exemption — linting
        it directly produces no host-sync findings."""
        from paddle_tpu.analysis import (ProgramArtifacts, is_host_sync_ok,
                                         run_rules)
        from paddle_tpu.distributed.checkpoint.snapshot import _materialize

        assert is_host_sync_ok(_materialize)
        art = ProgramArtifacts(name="snap_capture",
                               source_fns=[_materialize])
        assert run_rules(art, rules=["host-sync"]) == []


class TestRingFixture:
    def test_analyze_perm_classes(self):
        # clean single ring
        assert analyze_perm([(0, 1), (1, 2), (2, 3), (3, 0)]) == []
        # clean pair of equal parallel rings (dp groups)
        assert analyze_perm([(0, 1), (1, 0), (2, 3), (3, 2)],
                            axis_size=2) == []
        # duplicate target: payload collision
        d = analyze_perm([(0, 1), (2, 1), (1, 0)])
        assert any("duplicate targets" in x for x in d)
        # open chain: ring never closes
        d = analyze_perm([(0, 1), (1, 2), (2, 3)])
        assert any("open chain" in x for x in d)
        # mixed cycle lengths
        d = analyze_perm([(0, 1), (1, 0), (2, 3), (3, 4), (4, 2)])
        assert any("mixed cycle lengths" in x for x in d)

    def test_broken_ppermute_cycle_fires(self):
        from paddle_tpu.framework.jax_compat import shard_map

        mesh = _mesh({"ring": 4})
        # seeded bug: the "ring" is an open chain — rank 3 never sends,
        # rank 0 never receives; on real chips the consumer deadlocks
        broken = [(0, 1), (1, 2), (2, 3)]

        def body(x):
            return jax.lax.ppermute(x, "ring", perm=broken)

        fn = shard_map(body, mesh, in_specs=P("ring"), out_specs=P("ring"),
                       check_vma=False)
        x = jnp.arange(8, dtype=jnp.float32)
        report = lint(jax.jit(fn), args=(x,), baseline=False,
                      rules=["ring-consistency"])
        assert not report.ok, report.format()
        assert any("chain" in f.message for f in report.failures())

    def test_hlo_layer_parses_multi_pair_tables(self):
        """The HLO layer alone (no jaxpr) must parse the FULL nested
        pair list — a truncating regex would verify nothing on any real
        >=2-hop table. GSPMD legitimately emits chains/self-loops for
        point-to-point resharding, so only DUPLICATE endpoints (invalid
        in any semantics) are defects at this layer."""
        from paddle_tpu.analysis import ProgramArtifacts, run_rules

        hlo_ok = ("%cp = f32[4]{0} collective-permute(f32[4]{0} %x), "
                  "channel_id=1, source_target_pairs="
                  "{{0,1},{1,2},{2,3},{3,0}}\n")
        art = ProgramArtifacts(name="t", hlo_text=hlo_ok, n_devices=4)
        assert run_rules(art, rules=["ring-consistency"]) == []

        # GSPMD-style open chain: legitimate at the HLO layer
        hlo_chain = hlo_ok.replace("{{0,1},{1,2},{2,3},{3,0}}",
                                   "{{1,0},{3,2},{5,4},{7,6}}")
        art = ProgramArtifacts(name="t", hlo_text=hlo_chain, n_devices=8)
        assert run_rules(art, rules=["ring-consistency"]) == []

        # duplicate target: a payload collision, defect in any semantics
        hlo_bad = hlo_ok.replace("{{0,1},{1,2},{2,3},{3,0}}",
                                 "{{0,1},{2,1},{1,3},{3,0}}")
        art = ProgramArtifacts(name="t", hlo_text=hlo_bad, n_devices=4)
        findings = run_rules(art, rules=["ring-consistency"])
        assert findings and "duplicate" in findings[0].message

    def test_shipped_rings_are_clean(self):
        mesh = _mesh({"ring": 4})
        perm = [(r, (r - 1) % 4) for r in range(4)]
        from paddle_tpu.framework.jax_compat import shard_map

        def body(x):
            return jax.lax.ppermute(x, "ring", perm=perm)

        fn = shard_map(body, mesh, in_specs=P("ring"), out_specs=P("ring"),
                       check_vma=False)
        report = lint(jax.jit(fn), args=(jnp.arange(8.0),),
                      baseline=False, rules=["ring-consistency"])
        assert report.ok, report.format()

    def test_overlap_rings_audit_clean(self):
        mesh = _mesh({"data": 2, "model": 4})
        findings = check_overlap_rings(mesh, axis="model")
        assert findings == [], [f.format() for f in findings]

    def test_overlap_rings_audit_catches_mismatch(self, monkeypatch):
        from paddle_tpu.distributed.overlap import collective_matmul as cm

        mesh = _mesh({"model": 4})
        # seeded bug: two half-rings instead of one rotation — exactly
        # the table corruption that deadlocks a 4-chip ring
        monkeypatch.setattr(
            cm, "_ring_perm",
            lambda p: [(0, 1), (1, 0), (2, 3), (3, 2)][:p] if p == 4
            else [(r, (r - 1) % p) for r in range(p)])
        cm._ag_mm_fn.cache_clear()
        cm._mm_rs_fn.cache_clear()
        try:
            findings = check_overlap_rings(mesh, axis="model")
            assert findings, "broken ring table not caught"
            assert any(f.severity == Severity.ERROR for f in findings)
        finally:
            monkeypatch.undo()
            cm._ag_mm_fn.cache_clear()
            cm._mm_rs_fn.cache_clear()


# ---------------------------------------------------------------------------
# clean-program suite: the shipped train steps lint clean


class TestCleanPrograms:
    @pytest.mark.parametrize("family", ["llama", "gpt"])
    def test_shipped_train_steps_lint_clean(self, family):
        paddle.seed(0)
        if family == "llama":
            from paddle_tpu.models import LlamaForCausalLM, llama_tiny

            cfg = llama_tiny(num_hidden_layers=2)
            model = LlamaForCausalLM(cfg)
        else:
            from paddle_tpu.models import GPTForCausalLM, gpt_tiny

            cfg = gpt_tiny()
            model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        step = paddle.jit.TrainStep(
            model, lambda m, x, y: m(x, labels=y)[0], opt)
        ids = paddle.to_tensor(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 16)).astype("int32"))
        report = lint(step, args=(ids, ids), baseline=False)
        assert report.findings == [], report.format()

    def test_tp_hybrid_step_lints_clean(self, interpreted_kernels):
        """mp2×pp2×dp2 (dryrun factorization 1): the TP slice — scanned
        pipe stack and GSPMD TP layers included — produces ZERO findings;
        the remat debt is specific to the ZeRO-3 × pipe layout mix."""
        strategy = dist.fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 2, "mp_degree": 2, "pp_degree": 2,
            "sharding_degree": 1, "sep_degree": 1}
        dist.fleet.init(is_collective=True, strategy=strategy)
        hcg = dist.get_hybrid_communicate_group()
        paddle.seed(0)
        from paddle_tpu.models.llama import llama_tiny
        from paddle_tpu.models.llama_parallel import LlamaForCausalLMHybrid

        cfg = llama_tiny(num_hidden_layers=4, num_attention_heads=4,
                         num_key_value_heads=2)
        model = LlamaForCausalLMHybrid(cfg, hcg)
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        step = dist.DistributedTrainStep(
            model, lambda m, x, y: m(x, labels=y)[0], opt, hcg,
            sharding_stage=3)
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (8, 16)).astype("int32"))
        report = lint(step, args=(ids, ids), baseline=False)
        assert report.findings == [], report.format()


# ---------------------------------------------------------------------------
# baseline machinery


class TestBaseline:
    def _finding(self, rule="involuntary-remat", subject="reshape f32[8,8]",
                 source="paddle_tpu/distributed/engine.py:400"):
        return Finding(rule=rule, severity=Severity.ERROR, subject=subject,
                       message="m", source=source)

    def test_exemption_matches_rule_and_regex(self):
        bl = Baseline([{"rule": "involuntary-remat",
                        "match": r"engine\.py", "reason": "known"}])
        new, exempted = bl.apply([self._finding()])
        assert new == [] and len(exempted) == 1
        assert exempted[0].context["exemption"]["reason"] == "known"

    def test_wrong_rule_never_matches(self):
        bl = Baseline([{"rule": "donation", "match": ".*", "reason": "x"}])
        new, exempted = bl.apply([self._finding()])
        assert len(new) == 1 and exempted == []

    def test_new_site_fails(self):
        bl = load_baseline()  # the committed file
        fresh = self._finding(
            subject="all-gather bf16[4096,50304]",
            source="paddle_tpu/ops/pallas/new_kernel.py:10")
        new, exempted = bl.apply([fresh])
        assert new == [fresh], \
            "a new remat in a new kernel must NOT be swallowed"

    def test_unused_exemptions_reported(self):
        bl = Baseline([{"rule": "donation", "match": "zzz", "reason": "r"}])
        bl.apply([self._finding()])
        assert len(bl.unused()) == 1

    def test_committed_baseline_loads(self):
        # the involuntary-remat debt was paid by engine spec single-homing,
        # and the dryrun gate runs with PADDLE_TPU_LINT_STRICT_BASELINE=1 —
        # a stale exemption is itself an error, so the file must stay empty
        bl = load_baseline()
        assert bl.exemptions == [], \
            "committed baseline must stay empty; fix the program instead"
        for e in bl.exemptions:
            assert e.get("reason"), "every exemption needs a justification"


# ---------------------------------------------------------------------------
# repo-source AST seam check (PR-1 invariant, now machine-enforced)


class TestJaxCompatSeam:
    def test_repo_sources_route_through_seam(self):
        findings = check_jax_compat_seam()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_direct_import_flagged(self):
        bad = "from jax.experimental.shard_map import shard_map\n"
        hits = check_source_text(bad, "pkg/mod.py")
        assert hits and hits[0].rule == "jax-compat-seam"
        assert "pkg/mod.py:1" == hits[0].source

    def test_direct_attribute_flagged(self):
        bad = ("import jax\n"
               "def f(b, m):\n"
               "    return jax.shard_map(b, mesh=m)\n"
               "def g(x):\n"
               "    return jax.lax.pcast(x, ('a',), to='varying')\n")
        hits = check_source_text(bad, "pkg/mod.py")
        assert {h.subject for h in hits} == {"jax.shard_map",
                                             "jax.lax.pcast"}

    def test_qualified_spelling_flagged(self):
        bad = ("import jax\n"
               "out = jax.experimental.shard_map.shard_map(f, mesh=m)\n")
        hits = check_source_text(bad, "pkg/mod.py")
        assert len(hits) == 1 and hits[0].rule == "jax-compat-seam"
        bad2 = ("from jax import experimental\n"
                "out = experimental.shard_map.shard_map(f)\n")
        assert len(check_source_text(bad2, "pkg/mod.py")) == 1

    def test_seam_module_itself_allowed(self):
        findings = check_jax_compat_seam()
        assert not any("jax_compat" in (f.source or "") for f in findings)

    def test_innocent_shard_map_name_ok(self):
        ok = ("from paddle_tpu.framework.jax_compat import shard_map\n"
              "out = shard_map(lambda x: x, None, None, None)\n")
        assert check_source_text(ok, "pkg/mod.py") == []


# ---------------------------------------------------------------------------
# report plumbing


class TestReport:
    def test_format_and_json_roundtrip(self):
        f = Finding(rule="donation", severity=Severity.ERROR,
                    subject="no donated buffers", message="m",
                    cost_bytes=1 << 20)
        from paddle_tpu.analysis import LintReport

        r = LintReport(name="t", findings=[f])
        assert "donation" in r.format()
        assert not r.ok
        import json as _json

        data = _json.loads(r.to_json())
        assert data["counts"] == {"donation": 1}

    def test_gate_rule_subset(self):
        from paddle_tpu.analysis import LintReport

        r = LintReport(name="t", findings=[
            Finding(rule="host-sync", severity=Severity.WARNING,
                    subject="s", message="m"),
            Finding(rule="donation", severity=Severity.ERROR,
                    subject="s", message="m")])
        assert r.failures(rules=["involuntary-remat"]) == []
        assert len(r.failures()) == 1
