"""Sequence parallelism (distributed/meta_parallel/sequence_parallel):
the SP residency is a LAYOUT choice, never a math change.

Covers: constraint-op round trips (Scatter/Gather/ReduceScatter), the
``sequence_parallel_enabled`` gate precedence, Column/Row SP linear fwd +
grad parity against the plain TP layers on a 4-way mesh, the ring path
(seq-variant collective matmuls) vs fused GSPMD bitwise at p=2 and its
DP composition, the replication-blowup guarantee (no full [b, s, h]
all-gather in the ring program's HLO), the marked-parameter (norm scale)
mp-axis grad sum verified against the analytic value at tp=2, the
register hooks' loud-failure contract, model-level SP resolution on
``LlamaForCausalLMHybrid``, and compile-fingerprint sensitivity to the
SP flag.

Tier-1 FAST lane (``-m sp``)."""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.meta_parallel import (
    ColumnSequenceParallelLinear, GatherOp, ReduceScatterOp,
    RowSequenceParallelLinear, ScatterOp, is_sequence_parallel_parameter,
    mark_as_sequence_parallel_parameter,
    register_sequence_parallel_allreduce_hooks, sequence_parallel_enabled,
    sp_fingerprint)
from paddle_tpu.distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear)
from paddle_tpu.distributed.overlap import (all_gather_matmul_seq,
                                            matmul_reduce_scatter_seq,
                                            should_decompose_seq)
from paddle_tpu.distributed.topology import build_mesh

from op_test import sum_order_atol

pytestmark = pytest.mark.sp


def _hcg(dp, mp, sharding=1):
    import paddle_tpu.distributed as dist

    strategy = dist.fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp,
                               "pp_degree": 1, "sharding_degree": sharding,
                               "sep_degree": 1}
    dist.fleet.init(is_collective=True, strategy=strategy)
    return dist.get_hybrid_communicate_group()


@pytest.fixture
def hcg_mp2():
    """dp2 x sharding2 x mp2 — the 4-way (8-device) hybrid mesh."""
    from paddle_tpu.distributed import topology

    saved = topology.get_hybrid_communicate_group()
    yield _hcg(dp=2, mp=2, sharding=2)
    topology._hcg = saved


@pytest.fixture
def hcg_tp2():
    """tp=2 with the rest of the 8-device platform on "data" — the
    analytic-grad and parity group (degrees must multiply to the device
    count)."""
    from paddle_tpu.distributed import topology

    saved = topology.get_hybrid_communicate_group()
    yield _hcg(dp=4, mp=2)
    topology._hcg = saved


@pytest.fixture
def mesh_mp2():
    """A bare 2-device mp mesh for raw seq-prim tests (no hybrid group)."""
    return build_mesh(mp=2, devices=jax.devices()[:2])


@pytest.fixture
def overlap_on(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TP_OVERLAP", "1")
    monkeypatch.setenv("PADDLE_TPU_TP_OVERLAP_MIN_ROWS", "1")


@pytest.fixture
def overlap_off(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TP_OVERLAP", "0")


# ---------------------------------------------------------------------------
# constraint ops + gate


class TestConstraintOps:
    def test_scatter_gather_round_trip(self, hcg_mp2):
        x = paddle.to_tensor(np.random.default_rng(0)
                             .standard_normal((2, 8, 16)).astype(np.float32))
        s = ScatterOp.apply(x)
        assert tuple(s.shape) == (2, 8, 16)  # global shape is unchanged
        g = GatherOp.apply(s)
        np.testing.assert_array_equal(g.numpy(), x.numpy())

    def test_reduce_scatter_is_value_identity(self, hcg_mp2):
        """On an already-reduced tensor the op is pure layout: the values
        survive the seq-shard constraint bit-for-bit."""
        x = paddle.to_tensor(np.random.default_rng(1)
                             .standard_normal((2, 8, 16)).astype(np.float32))
        np.testing.assert_array_equal(ReduceScatterOp.apply(x).numpy(),
                                      x.numpy())

    def test_gate_precedence(self, hcg_mp2, monkeypatch):
        # explicit flag wins over everything
        monkeypatch.setenv("PADDLE_TPU_SP", "0")
        assert sequence_parallel_enabled(True)
        monkeypatch.setenv("PADDLE_TPU_SP", "1")
        assert not sequence_parallel_enabled(False)
        # env wins over the mp>1 default
        monkeypatch.setenv("PADDLE_TPU_SP", "0")
        assert not sequence_parallel_enabled()
        monkeypatch.delenv("PADDLE_TPU_SP")
        # default: on exactly when the live group has model degree > 1
        assert sequence_parallel_enabled()

    def test_should_decompose_seq_gating(self, mesh_mp2, overlap_on):
        assert should_decompose_seq((2, 8, 16), mesh_mp2)
        assert not should_decompose_seq((8, 16), mesh_mp2)  # needs a seq dim
        assert not should_decompose_seq((2, 7, 16), mesh_mp2)  # 7 % 2 != 0
        mesh_dp = build_mesh(dp=2, devices=jax.devices()[:2])
        assert not should_decompose_seq((2, 8, 16), mesh_dp)  # mp degree 1
        # batch rows must divide over the data axes for the ring reshape
        mesh_dpmp = build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
        assert should_decompose_seq((2, 8, 16), mesh_dpmp)
        assert not should_decompose_seq((3, 8, 16), mesh_dpmp)


# ---------------------------------------------------------------------------
# Column/Row SP linears: parity vs the plain TP layers, ring vs fused


class TestSequenceParallelLinearParity:
    def _x(self, seed=0, shape=(2, 8, 16)):
        return np.random.default_rng(seed).standard_normal(shape) \
            .astype(np.float32)

    def _build(self, cls_col, cls_row, h=16, ffn=32, seed=0):
        paddle.seed(seed)
        col = cls_col(h, ffn, has_bias=False, gather_output=False)
        row = cls_row(ffn, h, has_bias=False, input_is_parallel=True)
        return col, row

    def test_fwd_matches_non_sp_tp(self, hcg_mp2, overlap_off):
        """Same weights, same input: the SP block (scatter → col → row →
        gather) must equal the plain TP block — SP only moves layouts."""
        col_sp, row_sp = self._build(ColumnSequenceParallelLinear,
                                     RowSequenceParallelLinear)
        col, row = self._build(ColumnParallelLinear, RowParallelLinear)
        np.testing.assert_array_equal(col_sp.weight.numpy(),
                                      col.weight.numpy())
        x = paddle.to_tensor(self._x())
        y_sp = GatherOp.apply(row_sp(col_sp(ScatterOp.apply(x)))).numpy()
        y_tp = row(col(x)).numpy()
        np.testing.assert_allclose(y_sp, y_tp, rtol=1e-6, atol=1e-6)

    def test_grads_match_non_sp_tp(self, hcg_mp2, overlap_off):
        """Eager-tape grads through the SP block vs the plain TP block:
        dW and dx must agree — the rs/ag transposes reproduce the
        all-reduce cotangents."""
        col_sp, row_sp = self._build(ColumnSequenceParallelLinear,
                                     RowSequenceParallelLinear, seed=1)
        col, row = self._build(ColumnParallelLinear, RowParallelLinear,
                               seed=1)
        xv = self._x(seed=1)

        def grads(c, r, sp):
            x = paddle.to_tensor(xv, stop_gradient=False)
            c.weight.clear_grad(), r.weight.clear_grad()
            h = c(ScatterOp.apply(x)) if sp else c(x)
            out = r(h)
            (GatherOp.apply(out) if sp else out).sum().backward()
            return (x.grad.numpy().copy(), c.weight.grad.numpy().copy(),
                    r.weight.grad.numpy().copy())

        dx_sp, dc_sp, dr_sp = grads(col_sp, row_sp, True)
        dx, dc, dr = grads(col, row, False)
        np.testing.assert_allclose(dx_sp, dx, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(dc_sp, dc, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(dr_sp, dr, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("part", ["row_ring", "block"])
    def test_ring_matches_fused_p2(self, hcg_tp2, overlap_on, monkeypatch,
                                   part):
        """At p=2 the seq-variant reduce-scatter ring sums the same two
        partials as the fused collective: given the same input the row
        layer is BIT-identical.  The whole block (column, then row) agrees
        to float32 rounding only: the column layer sums nothing across
        devices, but XLA:CPU picks a dot kernel by the local shape — the
        fused path's eager matmul runs at the full output width (32
        columns, sharded afterwards), the ring's at a shard's 16 — and the
        two kernels add the 16 products of a dot in different orders
        (measured 4.8e-7)."""
        col, row = self._build(ColumnSequenceParallelLinear,
                               RowSequenceParallelLinear, seed=2)
        x = paddle.to_tensor(self._x(seed=2, shape=(4, 8, 16)))

        def run(overlap, h=None):
            monkeypatch.setenv("PADDLE_TPU_TP_OVERLAP", overlap)
            return row(col(ScatterOp.apply(x)) if h is None else h)

        if part == "row_ring":
            monkeypatch.setenv("PADDLE_TPU_TP_OVERLAP", "0")
            h = col(ScatterOp.apply(x))
            np.testing.assert_array_equal(run("1", h).numpy(),
                                          run("0", h).numpy())
        else:
            y_fused = run("0").numpy()
            np.testing.assert_allclose(run("1").numpy(), y_fused, rtol=0,
                                       atol=sum_order_atol(y_fused, k=32))

    def test_ring_grads_match_fused(self, hcg_tp2, overlap_on, monkeypatch):
        col, row = self._build(ColumnSequenceParallelLinear,
                               RowSequenceParallelLinear, seed=3)
        xv = self._x(seed=3, shape=(4, 8, 16))

        def grads(overlap):
            monkeypatch.setenv("PADDLE_TPU_TP_OVERLAP", overlap)
            x = paddle.to_tensor(xv, stop_gradient=False)
            col.weight.clear_grad(), row.weight.clear_grad()
            row(col(ScatterOp.apply(x))).sum().backward()
            return (x.grad.numpy().copy(), col.weight.grad.numpy().copy(),
                    row.weight.grad.numpy().copy())

        ring, fused = grads("1"), grads("0")
        for a, b in zip(ring, fused):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_ring_composes_with_dp(self, hcg_mp2, overlap_on):
        """dp2 x sharding2 x mp2: batch rows stay sharded over the data
        axes inside the seq-ring's manual region — values still match the
        dense reference and nothing trips a nested-manual error."""
        col, row = self._build(ColumnSequenceParallelLinear,
                               RowSequenceParallelLinear, seed=4)
        x = paddle.to_tensor(self._x(seed=4, shape=(4, 8, 16)))
        y = GatherOp.apply(row(col(ScatterOp.apply(x)))).numpy()
        ref = self._x(seed=4, shape=(4, 8, 16)) @ col.weight.numpy() \
            @ row.weight.numpy()
        np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# replication blowup: the ring program must not materialize [b, s, h]


class TestNoFullSeqAllGather:
    def test_ring_hlo_has_no_all_gather(self, mesh_mp2, overlap_on):
        """The compiled fwd+grad of the seq-variant prims must run the
        seq all-gather/reduce-scatter as collective-permute hops — no
        all-gather op materializing the full [b, s, h] block at once."""
        mesh = mesh_mp2
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((2, 8, 16)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))

        def loss(xx, ww):
            return jnp.sum(all_gather_matmul_seq(xx, ww, mesh) ** 2)

        txt = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            x, w).compile().as_text()
        assert len(re.findall(r"collective-permute", txt)) > 0
        assert "all-gather(" not in txt and "all-gather-start(" not in txt

    def test_rs_ring_hlo_has_no_reduce_scatter(self, mesh_mp2, overlap_on):
        mesh = mesh_mp2
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.standard_normal((2, 8, 8)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32))

        def loss(xx, ww):
            return jnp.sum(matmul_reduce_scatter_seq(xx, ww, mesh) ** 2)

        txt = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            x, w).compile().as_text()
        assert len(re.findall(r"collective-permute", txt)) > 0
        assert "reduce-scatter(" not in txt

    def test_seq_prims_match_dense_reference(self, mesh_mp2, overlap_on):
        mesh = mesh_mp2
        rng = np.random.default_rng(7)
        x = rng.standard_normal((2, 8, 16)).astype(np.float32)
        w = rng.standard_normal((16, 8)).astype(np.float32)
        out = jax.jit(lambda a, b: all_gather_matmul_seq(a, b, mesh))(
            jnp.asarray(x), jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(out), x @ w,
                                   rtol=1e-5, atol=1e-5)
        x2 = rng.standard_normal((2, 8, 8)).astype(np.float32)
        w2 = rng.standard_normal((8, 16)).astype(np.float32)
        out2 = jax.jit(lambda a, b: matmul_reduce_scatter_seq(a, b, mesh))(
            jnp.asarray(x2), jnp.asarray(w2))
        np.testing.assert_allclose(np.asarray(out2), x2 @ w2,
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# marked parameters: the mp-axis grad sum


class TestMarkedParameterGrads:
    def test_analytic_grad_sum_at_tp2(self, mesh_mp2):
        """A replicated param consumed by "model"-seq-sharded activations
        gets a Partial cotangent the partitioner must SUM over the mp
        group (the reference's backward hook, emitted by GSPMD). The
        analytic grad of sum(scale * x) wrt scale is x.sum((0, 1)) over
        ALL tokens — a missing mp-axis reduction halves it."""
        mesh = mesh_mp2
        xv = np.random.default_rng(8).standard_normal((2, 8, 4)) \
            .astype(np.float32)
        sv = np.random.default_rng(9).standard_normal((4,)) \
            .astype(np.float32)

        def loss(scale, x):
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(None, "model", None)))
            return jnp.sum(scale * x)

        g = jax.jit(jax.grad(loss))(jnp.asarray(sv), jnp.asarray(xv))
        np.testing.assert_allclose(np.asarray(g), xv.sum(axis=(0, 1)),
                                   rtol=1e-5, atol=1e-5)

    def test_norm_scale_grad_matches_replicated(self, hcg_tp2):
        """The same contract through the real layer stack: RMSNorm scale
        grads with the input seq-sharded (SP residency) vs fully
        replicated must agree."""
        paddle.seed(5)
        norm = nn.RMSNorm(16)
        xv = np.random.default_rng(10).standard_normal((2, 8, 16)) \
            .astype(np.float32)

        def grad(sp):
            x = paddle.to_tensor(xv)
            norm.weight.clear_grad()
            h = ScatterOp.apply(x) if sp else x
            norm(h).sum().backward()
            return norm.weight.grad.numpy().copy()

        np.testing.assert_allclose(grad(True), grad(False),
                                   rtol=1e-5, atol=1e-5)

    def test_mark_and_query(self, hcg_tp2):
        p = paddle.to_tensor(np.zeros((4,), np.float32))
        assert not is_sequence_parallel_parameter(p)
        mark_as_sequence_parallel_parameter(p)
        assert is_sequence_parallel_parameter(p)


# ---------------------------------------------------------------------------
# register_sequence_parallel_allreduce_hooks


class TestRegisterHooks:
    def _model(self):
        class Block(nn.Layer):
            def __init__(self):
                super().__init__()
                self.norm = nn.RMSNorm(16)
                self.col = ColumnSequenceParallelLinear(
                    16, 32, has_bias=False, gather_output=False)
                self.row = RowSequenceParallelLinear(
                    32, 16, has_bias=False, input_is_parallel=True)

        return Block()

    def test_marks_norms_not_tp_weights(self, hcg_tp2):
        m = register_sequence_parallel_allreduce_hooks(
            self._model(), accumulation_steps=4)
        assert is_sequence_parallel_parameter(m.norm.weight)
        assert not is_sequence_parallel_parameter(m.col.weight)
        assert not is_sequence_parallel_parameter(m.row.weight)
        assert m.norm.weight._sp_accumulation_steps == 4

    def test_fused_allreduce_is_loud(self, hcg_tp2):
        with pytest.raises(NotImplementedError, match="fuse"):
            register_sequence_parallel_allreduce_hooks(
                self._model(), fuse_sequence_parallel_allreduce=True)

    def test_bad_accumulation_is_loud(self, hcg_tp2):
        with pytest.raises(ValueError, match="accumulation_steps"):
            register_sequence_parallel_allreduce_hooks(
                self._model(), accumulation_steps=0)


# ---------------------------------------------------------------------------
# model-level resolution + fingerprint


class TestModelResolutionAndFingerprint:
    def test_hybrid_llama_sp_resolution(self, hcg_tp2, monkeypatch):
        from paddle_tpu.models.llama import llama_tiny
        from paddle_tpu.models.llama_parallel import LlamaForCausalLMHybrid

        cfg = llama_tiny(num_hidden_layers=1, num_attention_heads=2,
                         num_key_value_heads=2, hidden_size=32,
                         intermediate_size=64, vocab_size=64)
        paddle.seed(6)
        assert LlamaForCausalLMHybrid(cfg, hcg_tp2).sequence_parallel
        assert not LlamaForCausalLMHybrid(
            cfg, hcg_tp2, sequence_parallel=False).sequence_parallel
        monkeypatch.setenv("PADDLE_TPU_SP", "0")
        assert not LlamaForCausalLMHybrid(cfg, hcg_tp2).sequence_parallel

    def test_hybrid_llama_sp_fwd_parity(self, hcg_tp2):
        """SP on vs off on the full tiny hybrid model: same logits — the
        residency (scatter after embed, sharded norms, SP lm_head) never
        changes the function computed."""
        from paddle_tpu.models.llama import llama_tiny
        from paddle_tpu.models.llama_parallel import LlamaForCausalLMHybrid

        cfg = llama_tiny(num_hidden_layers=1, num_attention_heads=2,
                         num_key_value_heads=2, hidden_size=32,
                         intermediate_size=64, vocab_size=64,
                         max_position_embeddings=16)
        ids = paddle.to_tensor(np.random.default_rng(11)
                               .integers(0, 64, (4, 16)).astype("int32"))

        def logits(sp):
            paddle.seed(7)
            m = LlamaForCausalLMHybrid(cfg, hcg_tp2, sequence_parallel=sp)
            return m(ids).numpy()

        np.testing.assert_allclose(logits(True), logits(False),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("leg", ["ring_vs_fused", "sp_on_vs_off"])
    def test_hybrid_llama_loss_trajectory_parity(self, hcg_tp2, monkeypatch,
                                                 leg):
        """Three float32 SGD steps of the tiny hybrid model under
        ``jit(value_and_grad)``, forward AND the mirrored custom-vjp
        backward: the ring-decomposed TP path against fused GSPMD (SP off),
        and SP on against SP off on the ring path.  The decomposition and
        the residency are layouts, not math: the losses agree to float32
        rounding (not to the bit: XLA:CPU picks its dot kernels by the
        local shapes, see ``test_ring_matches_fused_p2``)."""
        from paddle_tpu.autograd import no_grad
        from paddle_tpu.jit import _StateSwap
        from paddle_tpu.models.llama import llama_tiny
        from paddle_tpu.models.llama_parallel import LlamaForCausalLMHybrid
        from paddle_tpu.tensor.tensor import Tensor

        cfg = llama_tiny(num_hidden_layers=2, num_attention_heads=2,
                         num_key_value_heads=2, hidden_size=32,
                         intermediate_size=64, vocab_size=64,
                         max_position_embeddings=16)
        ids = np.random.default_rng(0).integers(0, 64, (4, 16)) \
            .astype("int32")
        lbl = np.roll(ids, -1, axis=1)
        monkeypatch.setenv("PADDLE_TPU_TP_OVERLAP_MIN_ROWS", "1")

        def losses(sp, overlap):
            monkeypatch.setenv("PADDLE_TPU_TP_OVERLAP", overlap)
            paddle.seed(0)
            hyb = LlamaForCausalLMHybrid(cfg, hcg_tp2, sequence_parallel=sp)
            params = [p for _, p in hyb.named_parameters()]

            def loss_fn(arrs, i, l):
                # no_grad: the custom_vjp rings own the backward, the eager
                # tape must not linearize each layer call a second time
                with _StateSwap(params, arrs), no_grad():
                    return hyb(Tensor(i), labels=Tensor(l))[0]._value

            grad_fn = jax.jit(jax.value_and_grad(loss_fn))
            arrs, out = [p._value for p in params], []
            for _ in range(3):
                lv, g = grad_fn(arrs, ids, lbl)
                out.append(float(lv))
                arrs = [a - 0.1 * gi for a, gi in zip(arrs, g)]
            return out

        a, b = (losses(False, "1"), losses(False, "0")) \
            if leg == "ring_vs_fused" else \
            (losses(True, "1"), losses(False, "1"))
        assert a[-1] < a[0]                 # it trains
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=0)  # measured 1.2e-7

    def test_sp_fingerprint_env_sensitive(self, hcg_tp2, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SP", "1")
        on = sp_fingerprint()
        monkeypatch.setenv("PADDLE_TPU_SP", "0")
        off = sp_fingerprint()
        assert on != off and on["sp"] and not off["sp"]

    def test_compile_fingerprint_splits_on_sp(self, hcg_tp2, monkeypatch):
        from paddle_tpu.compile.aot import fingerprint

        monkeypatch.setenv("PADDLE_TPU_SP", "1")
        a = fingerprint("module @m {}")
        monkeypatch.setenv("PADDLE_TPU_SP", "0")
        b = fingerprint("module @m {}")
        assert a != b

    def test_trainstep_extras_include_sp(self, hcg_tp2, monkeypatch):
        import paddle_tpu.nn.functional as F
        from paddle_tpu.distributed import DistributedTrainStep

        paddle.seed(8)
        m = nn.Sequential(nn.Linear(16, 8))
        opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters())
        step = DistributedTrainStep(m, lambda mm, a, b: F.mse_loss(mm(a), b),
                                    opt, hcg_tp2, sharding_stage=1)
        monkeypatch.setenv("PADDLE_TPU_SP", "1")
        on = step._fingerprint_extras("step")["sp"]
        monkeypatch.setenv("PADDLE_TPU_SP", "0")
        off = step._fingerprint_extras("step")["sp"]
        assert on != off


# ---------------------------------------------------------------------------
# strict-baseline lint mode (rides this PR: the deleted involuntary-remat
# entries must never silently regrow)


class TestStrictBaseline:
    def test_unused_exemption_fails_strict(self, tmp_path, monkeypatch):
        import json

        from paddle_tpu.analysis import lint

        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps({"version": 1, "exemptions": [
            {"rule": "involuntary-remat", "match": "never-matches",
             "reason": "stale entry"}]}))
        monkeypatch.setenv("PADDLE_TPU_LINT_STRICT_BASELINE", "1")
        rep = lint(jax.jit(lambda x: x * 2), args=(jnp.ones((4, 4)),),
                   baseline=str(bl))
        assert not rep.ok
        assert rep.findings[0].rule == "stale-baseline-exemption"
        monkeypatch.setenv("PADDLE_TPU_LINT_STRICT_BASELINE", "0")
        rep = lint(jax.jit(lambda x: x * 2), args=(jnp.ones((4, 4)),),
                   baseline=str(bl))
        assert rep.ok and len(rep.unused_exemptions) == 1

    def test_shipped_baseline_has_no_exemptions(self):
        """The PR's DONE condition, pinned: the involuntary-remat family
        was deleted when engine.py single-homed the spec policy — the
        committed table must stay empty."""
        from paddle_tpu.analysis import load_baseline

        assert load_baseline().exemptions == []


# ---------------------------------------------------------------------------
# ZeRO-3 x TP x SP composition (the combo no dryrun factorization covers)


class TestZero3TPGradBuckets:
    """ZeRO-3 ("sharding") × TP ("model") × SP in ONE compiled step. Flat
    grad buckets tile 1-D over ('sharding','data'); a TP-tiled grad cannot
    ride one — the concat drops the "model" tiling and the partitioner
    gathers it back as an involuntary full remat (surfaced the moment SP's
    ring programs pinned those grad layouts). The bucket plan must skip
    TP-tiled grads (they reduce per-tensor on their native layout) and the
    whole step must lint remat-free with no baseline."""

    def test_bucket_plan_skips_and_passes_through(self):
        from jax.sharding import Mesh

        from paddle_tpu.distributed.overlap import GradientBucketer

        b = GradientBucketer([400] * 4, bucket_bytes=10 ** 6,
                             keys=["f32"] * 4, reverse=True,
                             skip=[False, True, False, True])
        assert sorted(i for bk in b.buckets for i in bk) == [0, 2]
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        grads = [jnp.full((10, 10), float(i)) for i in range(4)]
        out = b.constrain(grads, mesh, axes=("data", "sharding"))
        for g, o in zip(grads, out):  # value identity incl. pass-through
            np.testing.assert_array_equal(np.asarray(o), np.asarray(g))

    def test_zero3_tp_sp_step_lints_remat_free(self, hcg_mp2):
        from paddle_tpu.analysis import lint
        from paddle_tpu.distributed import DistributedTrainStep
        from paddle_tpu.models.llama import llama_tiny
        from paddle_tpu.models.llama_parallel import LlamaForCausalLMHybrid

        paddle.seed(0)
        cfg = llama_tiny(num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=2)
        model = LlamaForCausalLMHybrid(cfg, hcg_mp2)
        assert model.sequence_parallel  # mp>1 default, SP really on
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        step = DistributedTrainStep(
            model, lambda m, x, y: m(x, labels=y)[0], opt, hcg_mp2,
            sharding_stage=3)
        b = step._grad_bucketer
        assert b is not None, "stage-3 over sized reduce axes must bucket"
        assert any(b.skip), "TP-tiled grads must be excluded from buckets"
        assert not all(b.skip), "DP/ZeRO-only grads must still bucket"
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (8, 16)).astype("int32"))
        lbl = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (8, 16)).astype("int32"))
        report = lint(step, args=(ids, lbl), baseline=False)
        remats = [f for f in report.findings
                  if f.rule == "involuntary-remat"]
        assert remats == [], "\n".join(f.format() for f in remats)
