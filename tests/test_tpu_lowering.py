"""Cross-lowering for TPU from the CPU-only test host.

``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
Pallas -> Mosaic lowering without a chip, and that lowering is where a block
spec the TPU cannot take is refused ("last two dimensions of your block
shape [must be] divisible by 8 and 128 ... or equal to the ... array").  Two
kernels on default-on product paths shipped with such specs because every
test ran them in interpret mode, which checks nothing of the kind:
``decode_attention`` blocked one kv head out of ``[b, C, kv, d]`` and
``flash_attention_varlen`` blocked one element out of a rank-1 ``(b,)``.
This file is the test that would have caught both, at ``chip_smoke.py``'s
shapes:

- every Pallas kernel a default-on flag dispatches to;
- every shape a ``*_supported`` gate accepts must lower;
- the full train step, the serving decode and the serving prefill programs
  at two layers and the full Llama-670M widths, with the kernels in them.

What Mosaic then makes of a kernel that lowers (VMEM, layouts) only the chip
can say: ``python chip_smoke.py`` through the chip tool.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.jax_compat import persistent_cache_off
from paddle_tpu.ops.pallas import (decode_attention,
                                   decode_attention_supported,
                                   flash_attention, flash_attention_supported,
                                   flash_attention_varlen,
                                   flash_attention_varlen_supported,
                                   fused_rms_norm, fused_rope,
                                   paged_decode_attention,
                                   paged_decode_attention_refusal)

BF16 = jnp.bfloat16
# Llama-670M widths (chip_smoke.py); depth cut to two layers
WIDTHS = dict(vocab_size=32000, hidden_size=2048, intermediate_size=8192,
              num_hidden_layers=2, num_attention_heads=16,
              num_key_value_heads=16)


def tpu_text(fn, *args, **jit_kw) -> str:
    return jax.jit(fn, **jit_kw).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def kernels_in(text: str) -> dict:
    names = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "rms_norm_fwd",
             "rms_norm_bwd", "fused_rope", "decode_attention",
             "paged_decode_attention", "ssm_state_update",
             "mla_paged_decode_attention", "moe_grouped_matmul")
    return {n: text.count(f'kernel_name = "{n}"') for n in names}


def kernel_uses(text: str, name: str) -> int:
    """Times a lowered program runs the kernel ``name``: one inside a
    private function (a jitted helper the layers share, lowered once)
    counts once a call site of that function."""
    uses = 0
    for body in text.split("func.func ")[1:]:
        head = body.split("(", 1)[0].split()
        calls = 1 if head[0] == "public" else text.count(f"call {head[-1]}(")
        uses += calls * body.count(f'kernel_name = "{name}"')
    return uses


def sds(*shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compile_uncached(jitted, *args):
    """Compile for the described chip with the persistent cache off (what
    is compiled here for a TPU cannot be read back without one)."""
    with persistent_cache_off():
        return jitted.lower(*args).compile()


def _copies_of(text: str, shape: str):
    return [line.strip()[:160] for line in text.splitlines()
            if shape in line.split(" = ", 1)[-1][:len(shape) + 2]
            and (" copy(" in line or " copy-start(" in line)]


def _decode_sds(eng, sharding):
    """The decode program's arguments as the engine passes them, described
    on ``sharding``."""
    pa, ba = eng._param_arrays()
    R, MP = eng.max_batch, eng.max_pages_per_seq
    args = (pa, ba, eng._arenas, jnp.zeros((R, 1), jnp.int32),
            jnp.zeros((R,), jnp.int32), jnp.zeros((R, MP), jnp.int32),
            jnp.ones((R,), jnp.int32))
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), args)


def _engine_decode(eng, sharding):
    """The engine's decode program as ``_decode_program`` builds it (the
    program that chooses the weights' layouts), for the described chip."""
    with persistent_cache_off():
        return eng._decode_program(_decode_sds(eng, sharding))


# the page-walking decode kernel at the benchmark's serving cells (Mistral:
# GQA 32/8 heads of 128, 64 rows x 20 slots of 900 pages; 16 x 33 of 545;
# Granite-4.0-H: 32/8 heads of 64 on the flat arena [900, 128, 512], scores
# scaled by 1/64), at chip_smoke.py's serve phase (MHA 16/16) and at a
# speculative width
PAGED_SHAPES = [(64, 1, (32, 8), 20, 900), (16, 1, (32, 8), 33, 545),
                (8, 1, (16, 16), 16, 129), (64, 3, (32, 8), 20, 900)]
PAGED_IDS = ["chat", "docqa", "smoke", "speculative"]
MERGED_SHAPE = (64, 1, (32, 8), 20, 900)


def paged_args(rows, width, heads, slots, pages, head_dim=128):
    """A 128-wide head's arena is 4-D; a narrower head's keeps a token's
    heads merged, as the engine lays it out."""
    h, kv = heads
    q = sds(rows, width, h, head_dim)
    arena = sds(pages, 128, kv, head_dim) if head_dim % 128 == 0 \
        else sds(pages, 128, kv * head_dim)
    assert paged_decode_attention_refusal(
        q.shape, arena.shape, (rows, slots), BF16) is None
    return (q, arena, arena, sds(rows, slots, dtype=jnp.int32),
            sds(rows, dtype=jnp.int32), sds(rows, dtype=jnp.int32))


def _mosaic_calls(text: str, kernel: str) -> int:
    """Mosaic calls of a compiled program that are the kernel ``kernel``."""
    return sum(line.lstrip().startswith(f"%{kernel}")
               and 'custom_call_target="tpu_custom_call"' in line
               for line in text.splitlines())


def _moves_of(text: str, shape: str):
    """Lines of a compiled program that copy or re-lay out an array whose
    shape starts with ``shape``."""
    return [line.strip()[:160] for line in text.splitlines()
            if shape in line and any(f" {op}(" in line for op in
                                     ("copy", "reshape", "transpose"))]


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e host: the TPU compiler is installed
    here and compiles for it with no chip attached."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Dispatch as on the chip: the functional layer picks the Pallas
    kernels (not interpreted) although the backend here is the CPU."""
    import paddle_tpu.ops as ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    from paddle_tpu.distributed import topology

    monkeypatch.setattr(topology, "_hcg", None)
    # an earlier file of this worker may have left the interpreter on
    # (tests/test_analysis.py sets it and does not give it back): an
    # interpreted kernel is no Mosaic call, and nothing here would find it
    interpreted = paddle.get_flags("pallas_interpret")
    paddle.set_flags({"pallas_interpret": False})
    yield
    paddle.set_flags(interpreted)


class TestKernelsLower:
    @pytest.mark.parametrize("b,s,blocks", [(4, 2048, (512, 512)),
                                            (1, 8192, (1024, 512))])
    def test_flash_forward_and_backward(self, b, s, blocks):
        q = sds(b, s, 16, 128)
        bq, bk = blocks
        assert flash_attention_supported(q.shape, q.shape, has_mask=False,
                                         dropout_p=0.0, causal=True,
                                         block_q=bq, block_k=bk)

        def loss(q, k, v):
            return flash_attention(q, k, v, None, True, bq, bk, False) \
                .astype(jnp.float32).sum()

        k = kernels_in(tpu_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q))
        assert k["flash_fwd"] == k["flash_bwd_dq"] == k["flash_bwd_dkv"] == 1

    @pytest.mark.parametrize("shape,blocks", [
        ((8, 1024, 16, 128), (512, 512)),    # chip_smoke's bucketed prefill
        ((2, 128, 4, 64), (128, 128)),
        ((3, 64, 8, 128), (64, 32))])
    def test_flash_varlen(self, shape, blocks):
        """pad_lens rides scalar prefetch; a rank-1 (1,) SMEM block over
        the (b,) vector is what the lowering refused."""
        q = sds(*shape)
        bq, bk = blocks
        assert flash_attention_varlen_supported(q.shape, q.shape,
                                                block_q=bq, block_k=bk)
        text = tpu_text(lambda q, k, v, p: flash_attention_varlen(
            q, k, v, p, block_q=bq, block_k=bk), q, q, q,
            sds(shape[0], dtype=jnp.int32))
        assert kernels_in(text)["flash_fwd"] == 1

    def test_flash_compiles_for_a_v5e_at_the_default_tiles(self, one_chip):
        """The train cell's attention call (4 x 2048 tokens, 16 heads of
        128, causal), forward and backward, through Mosaic and XLA's TPU
        compiler for a described chip, at the tiles the flags give: one
        Mosaic call a kernel, under the names the benchmark's readers
        look for."""
        from paddle_tpu.ops.sharded import _flag_blocks

        bq, bk = _flag_blocks(2048, 2048)
        q = jax.ShapeDtypeStruct((4, 2048, 16, 128), BF16, sharding=one_chip)

        def loss(q, k, v):
            return flash_attention(q, k, v, None, True, bq, bk, False) \
                .astype(jnp.float32).sum()

        text = _compile_uncached(
            jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, q, q).as_text()
        heads = [line.split(" = ", 1)[0] for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert len(heads) == 3, heads
        # an op's name is its kernel's under jvp / transpose prefixes,
        # with underscores and a counter behind it
        for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            assert sum(h.rstrip("_.0123456789").endswith(kernel)
                       for h in heads) == 1, (kernel, heads)

    @pytest.mark.parametrize("b,C,h,kv,d,blk,dtype", [
        (8, 160, 16, 16, 128, 160, BF16),     # chip_smoke generate, 2K
        (8, 256, 16, 16, 128, 256, BF16),     # a whole 256-row block
        (4, 8192, 16, 16, 128, 256, BF16),    # the 8K point (ROADMAP S4)
        (2, 512, 32, 16, 64, 256, BF16),      # GQA, head_dim 64
        (2, 512, 32, 32, 128, 128, BF16),     # 32 MHA heads
        (2, 256, 16, 8, 128, 64, jnp.float32)])
    def test_decode_attention(self, b, C, h, kv, d, blk, dtype):
        """All kv heads ride every block ((block_k*kv, d) rows of the
        [b, C*kv, d] view); a block of ONE kv head out of [b, C, kv, d] is
        what the lowering refused for kv > 1."""
        q, kn = sds(b, 1, h, d, dtype=dtype), sds(b, 1, kv, d, dtype=dtype)
        cache = sds(b, C, kv, d, dtype=dtype)
        assert decode_attention_supported(q.shape, cache.shape, block_k=blk,
                                          dtype=dtype)
        text = tpu_text(
            lambda q, kn, vn, ck, cv, pos, pad: decode_attention(
                q, kn, vn, ck, cv, pos, pad, block_k=blk),
            q, kn, kn, cache, cache, sds(dtype=jnp.int32),
            sds(b, dtype=jnp.int32), donate_argnums=(3, 4))
        assert kernels_in(text)["decode_attention"] == 1

    def test_decode_gate_rejects_what_the_lowering_rejects(self):
        """kv heads that do not fill whole sublane tiles of the cache
        dtype make the append block (kv, d) unaligned: gate AND lowering
        say no."""
        q, kn, cache = sds(2, 1, 8, 128), sds(2, 1, 4, 128), \
            sds(2, 256, 4, 128)
        assert not decode_attention_supported(q.shape, cache.shape,
                                              block_k=64, dtype=BF16)
        with pytest.raises(Exception, match="divisible by 8 and 128"):
            tpu_text(lambda q, kn, vn, ck, cv: decode_attention(
                q, kn, vn, ck, cv, 3, None, block_k=64),
                q, kn, kn, cache, cache)

    @pytest.mark.parametrize("rows,width,heads,slots,pages", PAGED_SHAPES,
                             ids=PAGED_IDS)
    def test_paged_decode_attention(self, rows, width, heads, slots, pages):
        args = paged_args(rows, width, heads, slots, pages)
        # the default scale left out, given, and another (traced from one
        # line: a Mosaic module carries its source locations)
        text, given, other = [
            tpu_text(lambda *a: paged_decode_attention(*a, scale=scale),
                     *args) for scale in (None, 128 ** -0.5, 1 / 64)]
        assert kernels_in(text)["paged_decode_attention"] == 1
        assert given == text, "the default scale is no longer d ** -0.5"
        assert other != text

    def test_paged_decode_attention_on_merged_heads(self):
        text = tpu_text(
            functools.partial(paged_decode_attention, scale=1 / 64),
            *paged_args(*MERGED_SHAPE, head_dim=64))
        assert kernels_in(text)["paged_decode_attention"] == 1

    @pytest.mark.parametrize(
        "rows,width,heads,slots,pages,head_dim,scale",
        [shape + (128, None) for shape in PAGED_SHAPES]
        + [MERGED_SHAPE + (64, 1 / 64)], ids=PAGED_IDS + ["merged"])
    def test_paged_decode_attention_compiles_for_a_v5e(
            self, one_chip, rows, width, heads, slots, pages, head_dim,
            scale):
        """The whole way through Mosaic and XLA's TPU compiler, for a chip
        that is described and not attached: VMEM, DMA and layouts, which
        the cross-lowering above does not see.  The arenas' ``[N, P*kv,
        d]`` view must stay a bitcast, and a merged arena must be read as
        it lies: a copy of the pool a layer would cost more than the
        gather the kernel replaced."""
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                for a in paged_args(rows, width, heads, slots, pages,
                                    head_dim)]
        text = _compile_uncached(
            jax.jit(functools.partial(paged_decode_attention, scale=scale)),
            *args).as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert not _moves_of(text, f"bf16[{pages},"), "the pool is moved"

    def test_ssm_state_update_compiles_for_a_v5e(self, one_chip):
        """The decode state update at granite-4.0-h-micro's shapes (64 rows
        of ``[64, 64, 128]`` float32), through Mosaic and XLA's TPU
        compiler for a described chip: one kernel, the arena aliased to
        its output and never copied."""
        from paddle_tpu.ops.pallas.ssm_state_update import (
            ssm_state_update, ssm_state_update_refusal)

        R, H, P, N = 64, 64, 64, 128
        shapes = [((R, H, P, N), jnp.float32), ((R,), jnp.bool_),
                  ((R, H, P), BF16), ((R, H), jnp.float32), ((H,), BF16),
                  ((R, 1, N), BF16), ((R, 1, N), BF16), ((H,), BF16)]
        assert ssm_state_update_refusal(shapes[0][0], jnp.float32,
                                        (R, 1, N)) is None
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = _compile_uncached(
            jax.jit(lambda *a: ssm_state_update(*a), donate_argnums=(0,)),
            *args)
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        arena = R * H * P * N * 4
        assert compiled.memory_analysis().alias_size_in_bytes >= arena
        assert not _copies_of(text, f"f32[{R},{H},{P},{N}]")

    def test_ssm_state_update_at_eight_groups_compiles_for_a_v5e(
            self, one_chip):
        """The same update at Nemotron-3-Nano's grouping (64 heads in 8
        B/C groups): a grid step still moves 32 heads' state (1 MB) and
        the 4 groups they lie in, one kernel, the arena aliased."""
        from paddle_tpu.ops.pallas.ssm_state_update import (
            _head_block, ssm_state_update, ssm_state_update_refusal)

        R, H, P, N, G = 64, 64, 64, 128, 8
        assert _head_block(H, P, N, G) == _head_block(H, P, N) == 32
        shapes = [((R, H, P, N), jnp.float32), ((R,), jnp.bool_),
                  ((R, H, P), BF16), ((R, H), jnp.float32), ((H,), BF16),
                  ((R, G, N), BF16), ((R, G, N), BF16), ((H,), BF16)]
        assert ssm_state_update_refusal(shapes[0][0], jnp.float32,
                                        (R, G, N)) is None
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = _compile_uncached(
            jax.jit(lambda *a: ssm_state_update(*a), donate_argnums=(0,)),
            *args)
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            R * H * P * N * 4
        assert not _copies_of(text, f"f32[{R},{H},{P},{N}]")

    @pytest.mark.parametrize("rows,width", [(128, 1), (16, 3)],
                             ids=["reasoning", "speculative"])
    def test_mla_paged_decode_attention_compiles_for_a_v5e(
            self, one_chip, rows, width):
        """The absorbed-MLA page walk at DeepSeek-V3's widths (128 heads
        over latent rows of 512 + 64 lanes padded to 640, 128 rows x 40
        slots of 3000 pages), through Mosaic and XLA's TPU compiler for a
        described chip: one kernel, the arena read where it lies.  A row
        of 576 lanes is what the gate refuses: Mosaic cannot copy a slice
        that is not whole lane registers."""
        from paddle_tpu.ops.pallas.mla_paged_decode_attention import (
            mla_paged_decode_attention, mla_paged_decode_attention_refusal)

        q, arena, tables = (rows, width, 128, 640), (3000, 128, 640), \
            (rows, 40)
        assert mla_paged_decode_attention_refusal(
            q, arena, tables, BF16, 512) is None
        assert mla_paged_decode_attention_refusal(
            (rows, width, 128, 576), (3000, 128, 576), tables, BF16,
            512) == "latent_width"
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in ((q, BF16), (arena, BF16),
                                     (tables, jnp.int32),
                                     ((rows,), jnp.int32),
                                     ((rows,), jnp.int32))]
        text = _compile_uncached(jax.jit(
            lambda *a: mla_paged_decode_attention(
                *a, latent=512, scale=0.135)), *args).as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert not [line for line in text.splitlines()
                    if "bf16[3000,128," in line and " copy(" in line], \
            "the pool is copied"

    @pytest.mark.parametrize("m,k,n", [(1024, 7168, 2048),
                                       (1024, 2048, 7168),
                                       (4096, 7168, 2048),
                                       (384, 2688, 1856),
                                       (384, 1856, 2688),
                                       (3072, 2688, 1856)],
                             ids=["decode-up", "decode-down", "prefill-up",
                                  "two-matrix-decode-up",
                                  "two-matrix-decode-down",
                                  "two-matrix-prefill-up"])
    def test_moe_grouped_matmul_compiles_for_a_v5e(self, one_chip, m, k, n):
        """The grouped expert matmul at DeepSeek-V3's expert widths, 16
        experts held, the pairs of 128 decode rows and of a 512-token
        prefill launch, and at Nemotron-3-Nano's two-matrix experts (2688 x
        1856, 64 rows x 6; 1856 is 14.5 lane registers: the chip keeps such
        weights with 2688 minor and the kernel reads them as they lie): one
        kernel, and no copy of the experts' weights."""
        from paddle_tpu.ops.pallas.grouped_matmul import (
            grouped_matmul, grouped_matmul_refusal)

        assert grouped_matmul_refusal((m, k), (16, k, n), BF16) is None
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in (((m, k), BF16), ((16, k, n), BF16),
                                     ((16,), jnp.int32))]
        text = _compile_uncached(jax.jit(grouped_matmul), *args).as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert not _copies_of(text, f"bf16[16,{k},{n}]")

    def test_rms_norm_and_rope(self):
        x, w = sds(4, 2048, 2048), sds(2048, dtype=jnp.float32)

        def norm_loss(x, w):
            return fused_rms_norm(x, w).astype(jnp.float32).sum()

        k = kernels_in(tpu_text(jax.grad(norm_loss, argnums=(0, 1)), x, w))
        assert k["rms_norm_fwd"] == k["rms_norm_bwd"] == 1
        # decode-sized rows (ServingEngine max_batch 8, one token each)
        assert kernels_in(tpu_text(fused_rms_norm, sds(8, 1, 2048), w)
                          )["rms_norm_fwd"] == 1

        q, t = sds(4, 2048, 16, 128), sds(2048, 128, dtype=jnp.float32)

        def rope_loss(q, k, c, s):
            oq, ok = fused_rope(q, k, c, s)
            return (oq.astype(jnp.float32).sum()
                    + ok.astype(jnp.float32).sum())

        assert kernels_in(tpu_text(fused_rope, q, q, t, t))["fused_rope"] == 1
        # the backward is the same kernel with the sine table negated
        k = kernels_in(tpu_text(jax.grad(rope_loss, argnums=(0, 1)),
                                q, q, t, t))
        assert k["fused_rope"] >= 1


@pytest.mark.usefixtures("on_tpu")
class TestProgramsLower:
    """The programs chip_smoke.py runs, two layers deep at full widths."""

    @pytest.fixture(autouse=True)
    def no_kernel_fell_back(self):
        """No dispatcher in the traced program took an XLA path quietly."""
        import paddle_tpu.telemetry as telemetry

        before = telemetry.counters().get("kernel_fallback.total", 0)
        yield
        assert telemetry.counters().get("kernel_fallback.total", 0) == before

    @pytest.fixture(scope="class")
    def model_and_opt(self):
        """One full-width model for the whole class: nothing here runs a
        program, so the train step and the serving engine can share it."""
        import paddle_tpu.nn as nn
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig(
            **WIDTHS, max_position_embeddings=2048, recompute=False))
        opt = paddle.optimizer.AdamW(
            1e-4, parameters=model.parameters(),
            grad_clip=nn.ClipGradByGlobalNorm(1.0))
        return paddle.amp.decorate(model, opt, level="O2", dtype="bfloat16")

    @pytest.fixture
    def eval_model(self, model_and_opt):
        model_and_opt[0].eval()
        yield model_and_opt[0]
        model_and_opt[0].train()

    def test_train_step(self, model_and_opt):
        model, opt = model_and_opt
        step = paddle.jit.TrainStep(
            model, lambda m, x, y: m(x, labels=y)[0], opt)
        ids = paddle.to_tensor(np.zeros((4, 2048), np.int32))
        text = step.lower(ids, ids, lowering_platforms=("tpu",)).as_text()
        for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                     "rms_norm_fwd", "rms_norm_bwd", "fused_rope"):
            # one per layer at least (the flash kernels are lowered once,
            # inside the jitted function both layers call)
            assert kernel_uses(text, name) >= 2, (name, kernels_in(text))

    def test_serving_decode_and_prefill(self, eval_model):
        from paddle_tpu.serving import ServingEngine

        eng = ServingEngine(eval_model, max_batch=8, page_tokens=128,
                            num_pages=129, max_pages_per_seq=16)
        pa, ba = eng._param_arrays()
        R, MP, P = 8, 16, 128
        tables = jnp.zeros((R, MP), jnp.int32)
        decode = tpu_text(
            eng._decode_fn, pa, ba, eng._arenas,
            jnp.zeros((R, 1), jnp.int32), jnp.zeros((R,), jnp.int32),
            tables, jnp.ones((R,), jnp.int32), donate_argnums=(2,))
        assert kernels_in(decode)["rms_norm_fwd"] >= 2
        # decode walks live pages (one kernel, lowered once inside the one
        # attention function that every layer calls); prefill gathers
        assert kernels_in(decode)["paged_decode_attention"] == 1
        assert decode.count("call @paged_decode_attention") == 1
        assert decode.count("call @_paged_attention") == \
            WIDTHS["num_hidden_layers"]
        prefill = tpu_text(
            eng._prefill_fn, pa, ba, eng._arenas,
            jnp.zeros((1, P), jnp.int32), jnp.int32(0), tables[:1],
            jnp.int32(P - 1), donate_argnums=(2,))
        assert kernels_in(prefill)["rms_norm_fwd"] >= 2
        assert kernels_in(prefill)["paged_decode_attention"] == 0
        # the riding form the engine launches at its narrowest width: the
        # prompt gathers, the decode rows beside it walk their pages as the
        # decode program does
        assert eng.rides_prefill and eng._carries_rows(1)
        ride = tpu_text(
            eng._prefill_fn, pa, ba, eng._arenas,
            jnp.zeros((1, P), jnp.int32), jnp.int32(0), tables[:1],
            jnp.int32(P - 1), jnp.int32(0), jnp.int32(P - 3),
            (jnp.zeros((R, 1), jnp.int32), jnp.zeros((R,), jnp.int32),
             tables, jnp.ones((R,), jnp.int32)), donate_argnums=(2,))
        assert kernels_in(ride)["paged_decode_attention"] == 1
        assert ride.count("call @_paged_attention") == \
            2 * WIDTHS["num_hidden_layers"]

    def test_generate_decode_step(self, eval_model):
        """One decode step of ``generate()``'s loop — the model called the
        way the compiled scan calls it — holds the decode kernel."""
        from paddle_tpu.jit import _StateSwap

        cfg = eval_model.config
        params = [p for _, p in eval_model.named_parameters()]
        buffers = [b for _, b in eval_model.named_buffers()]
        cache = [(jnp.zeros((8, 160, 16, cfg.head_dim), BF16),) * 2
                 for _ in range(cfg.num_hidden_layers)]

        def step(p_arr, b_arr, tok, caches, offset):
            with _StateSwap(params, p_arr), _StateSwap(buffers, b_arr), \
                    paddle.no_grad():
                logits, caches = eval_model(paddle.Tensor(tok),
                                            kv_cache=caches,
                                            position_offset=offset)
            return logits.value, caches

        text = tpu_text(step, [p.value for p in params],
                        [b.value for b in buffers],
                        jnp.zeros((8, 1), jnp.int32), cache, jnp.int32(128))
        assert kernels_in(text)["decode_attention"] == cfg.num_hidden_layers


@pytest.mark.usefixtures("on_tpu")
class TestStateLayerProgramsLower:
    """The serving decode program of a model with state layers (Granite-4.0-H
    widths, one Mamba-2 / attention / Mamba-2 stretch, a small vocabulary),
    compiled for a described v5e: the state update and the page walk over
    merged 64-wide heads are its kernels."""

    @pytest.fixture(scope="class")
    def engine(self):
        from paddle_tpu.models import (GraniteHybridConfig,
                                       GraniteHybridForCausalLM)
        from paddle_tpu.serving import ServingEngine

        paddle.seed(0)
        model = GraniteHybridForCausalLM(GraniteHybridConfig(
            vocab_size=1024, num_hidden_layers=3,
            layer_types=("mamba", "attention", "mamba"),
            max_position_embeddings=4096))
        model.eval()
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
        return ServingEngine(model, max_batch=64, page_tokens=128,
                             num_pages=65, max_pages_per_seq=4)

    def test_decode_updates_the_state_arenas_in_place(self, engine, one_chip):
        """Pages and row state are aliased (donated in, returned), the
        state update is the kernel, and no ``[64, 64, 64, 128]`` float32
        arena is ever copied: a copy would cost a whole arena's traffic a
        layer a step, what the live-rows kernel exists to avoid."""
        from paddle_tpu import telemetry
        from paddle_tpu.jit import named_program
        from paddle_tpu.serving.engine import DECODE_PROGRAM

        eng = engine
        pa, ba = eng._param_arrays()
        R, MP = eng.max_batch, eng.max_pages_per_seq
        args = (pa, ba, eng._arenas, jnp.zeros((R, 1), jnp.int32),
                jnp.zeros((R,), jnp.int32), jnp.zeros((R, MP), jnp.int32),
                jnp.ones((R,), jnp.int32))
        args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), args)
        before = telemetry.counters().get("kernel_fallback.total", 0)
        compiled = _compile_uncached(
            jax.jit(named_program(eng._decode_fn, DECODE_PROGRAM),
                    donate_argnums=(2,)), *args)
        text = compiled.as_text()
        assert eng.state.nbytes == 2 * 64 * (64 * 64 * 128 * 4
                                             + 3 * 4352 * 2)
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            eng._arena_bytes + eng.state.nbytes
        assert not _copies_of(text, "f32[64,64,64,128]")
        # the state update is a Mosaic call a state layer, the page walk
        # one an attention layer: the 64-wide heads are read from the flat
        # arena where it lies, and nothing falls back
        assert _mosaic_calls(text, "ssm_state_update") == 2
        assert _mosaic_calls(text, "paged_decode_attention") == 1
        assert eng._arena_shape == (65, 128, 512)
        assert not _moves_of(text, "bf16[65,128,512]")
        assert telemetry.counters().get("kernel_fallback.total", 0) == before

    def test_both_programs_lower_with_the_kernel_in_decode_only(self, engine):
        eng = engine
        pa, ba = eng._param_arrays()
        R, MP, P = eng.max_batch, eng.max_pages_per_seq, eng.page_tokens
        tables = jnp.zeros((R, MP), jnp.int32)
        decode = tpu_text(
            eng._decode_fn, pa, ba, eng._arenas,
            jnp.zeros((R, 1), jnp.int32), jnp.zeros((R,), jnp.int32),
            tables, jnp.ones((R,), jnp.int32), donate_argnums=(2,))
        # lowered once, inside the one mixer function every state layer calls
        assert kernels_in(decode)["ssm_state_update"] == 1
        assert decode.count("call @ssm_state_update") == 1
        assert decode.count("call @_mamba_mix") == 2
        # and the page walk once, in the one attention layer
        assert kernels_in(decode)["paged_decode_attention"] == 1
        assert decode.count("call @paged_decode_attention") == 1
        prefill = tpu_text(
            eng._prefill_fn, pa, ba, eng._arenas,
            jnp.zeros((1, P), jnp.int32), jnp.int32(0), tables[:1],
            jnp.int32(P - 1), jnp.int32(3), jnp.int32(P - 7),
            donate_argnums=(2,))
        assert kernels_in(prefill)["ssm_state_update"] == 0
        assert kernels_in(prefill)["rms_norm_fwd"] >= 2


@pytest.mark.usefixtures("on_tpu")
class TestLatentLayerProgramsLower:
    """The serving programs of a model with latent-attention layers
    (DeepSeek-V3's MLA widths per head and its latent row, fewer heads and a
    narrow hidden size; one dense and one expert layer), cross-lowered for
    TPU: the absorbed page walk and the grouped expert matmul are in the
    decode program, the prefill program expands and keeps the matmul."""

    @pytest.fixture(scope="class")
    def engine(self):
        from paddle_tpu.models import (DeepseekV3Config,
                                       DeepseekV3ForCausalLM)
        from paddle_tpu.serving import ServingEngine

        paddle.seed(0)
        model = DeepseekV3ForCausalLM(DeepseekV3Config(
            vocab_size=1024, hidden_size=512, intermediate_size=1024,
            moe_intermediate_size=256, num_hidden_layers=2,
            first_k_dense_replace=1, num_attention_heads=16,
            num_key_value_heads=16, q_lora_rank=256, n_routed_experts=32,
            experts_held=(0, 8), max_position_embeddings=4096))
        model.eval()
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
        return ServingEngine(model, max_batch=16, page_tokens=128,
                             num_pages=513, max_pages_per_seq=4)

    def test_both_programs_lower_with_their_kernels(self, engine):
        eng = engine
        pa, ba = eng._param_arrays()
        R, MP, P = eng.max_batch, eng.max_pages_per_seq, eng.page_tokens
        assert eng._arena_shape == (513, 128, 640)
        tables = jnp.zeros((R, MP), jnp.int32)
        decode = tpu_text(
            eng._decode_fn, pa, ba, eng._arenas,
            jnp.zeros((R, 1), jnp.int32), jnp.zeros((R,), jnp.int32),
            tables, jnp.ones((R,), jnp.int32), donate_argnums=(2,))
        # each lowered once, inside the function every layer calls
        assert kernels_in(decode)["mla_paged_decode_attention"] == 1
        assert decode.count("call @mla_paged_decode_attention") == 1
        assert kernels_in(decode)["moe_grouped_matmul"] == 2   # two shapes
        assert kernels_in(decode)["paged_decode_attention"] == 0
        prefill = tpu_text(
            eng._prefill_fn, pa, ba, eng._arenas,
            jnp.zeros((1, P), jnp.int32), jnp.int32(0), tables[:1],
            jnp.int32(P - 1), jnp.int32(3), jnp.int32(P - 7),
            donate_argnums=(2,))
        assert kernels_in(prefill)["mla_paged_decode_attention"] == 0
        assert kernels_in(prefill)["moe_grouped_matmul"] == 2
        # the riding form: the decode rows' absorbed walk beside the
        # prompt's expanded form, the experts over both parts' tokens
        assert eng.rides_prefill
        ride = tpu_text(
            eng._prefill_fn, pa, ba, eng._arenas,
            jnp.zeros((1, P), jnp.int32), jnp.int32(0), tables[:1],
            jnp.int32(P - 1), jnp.int32(3), jnp.int32(P - 7),
            (jnp.zeros((R, 1), jnp.int32), jnp.zeros((R,), jnp.int32),
             tables, jnp.ones((R,), jnp.int32)), donate_argnums=(2,))
        assert kernels_in(ride)["mla_paged_decode_attention"] == 1
        assert kernels_in(ride)["moe_grouped_matmul"] == 2

    def test_decode_updates_the_latent_arenas_in_place(self, engine,
                                                       one_chip):
        """Compiled for a described v5e: the latent arenas are aliased
        (donated in, returned) and never copied."""
        from paddle_tpu.jit import named_program
        from paddle_tpu.serving.engine import DECODE_PROGRAM

        eng = engine
        pa, ba = eng._param_arrays()
        R, MP = eng.max_batch, eng.max_pages_per_seq
        args = (pa, ba, eng._arenas, jnp.zeros((R, 1), jnp.int32),
                jnp.zeros((R,), jnp.int32), jnp.zeros((R, MP), jnp.int32),
                jnp.ones((R,), jnp.int32))
        args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), args)
        compiled = _compile_uncached(
            jax.jit(named_program(eng._decode_fn, DECODE_PROGRAM),
                    donate_argnums=(2,)), *args)
        assert eng._arena_bytes == 2 * 513 * 128 * 640 * 2
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            eng._arena_bytes
        assert not _copies_of(compiled.as_text(), "bf16[513,128,640]")


@pytest.mark.usefixtures("on_tpu")
class TestOnePartABlockProgramsLower:
    """The serving programs of a model whose blocks are ONE part each
    (Nemotron-3-Nano's widths: a Mamba-2 block with 8 B/C groups, an expert
    block of two-matrix experts that keeps nothing per request, an attention
    block with 2 KV heads; 16 of 128 experts held, a small vocabulary)."""

    @pytest.fixture(scope="class")
    def engine(self):
        from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM
        from paddle_tpu.serving import ServingEngine

        paddle.seed(0)
        model = NemotronHForCausalLM(NemotronHConfig(
            vocab_size=1024, num_hidden_layers=3,
            hybrid_override_pattern="ME*", experts_held=(0, 16),
            max_position_embeddings=8192))
        model.eval()
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
        return ServingEngine(model, max_batch=64, page_tokens=128,
                             num_pages=65, max_pages_per_seq=4)

    def test_both_programs_lower_with_their_kernels(self, engine):
        eng = engine
        pa, ba = eng._param_arrays()
        R, MP, P = eng.max_batch, eng.max_pages_per_seq, eng.page_tokens
        tables = jnp.zeros((R, MP), jnp.int32)
        decode = tpu_text(
            eng._decode_fn, pa, ba, eng._arenas,
            jnp.zeros((R, 1), jnp.int32), jnp.zeros((R,), jnp.int32),
            tables, jnp.ones((R,), jnp.int32), donate_argnums=(2,))
        kernels = kernels_in(decode)
        assert kernels["ssm_state_update"] == 1
        assert kernels["paged_decode_attention"] == 1
        assert kernels["moe_grouped_matmul"] == 2       # up and down: no gate
        prefill = tpu_text(
            eng._prefill_fn, pa, ba, eng._arenas,
            jnp.zeros((1, P), jnp.int32), jnp.int32(0), tables[:1],
            jnp.int32(P - 1), jnp.int32(3), jnp.int32(P - 7),
            donate_argnums=(2,))
        assert kernels_in(prefill)["ssm_state_update"] == 0
        assert kernels_in(prefill)["moe_grouped_matmul"] == 2

    def test_decode_compiles_for_a_v5e_and_copies_no_weight(self, engine,
                                                            one_chip):
        """Compiled for a described v5e: pages and row state aliased, one
        state update, one page walk, two grouped matmuls, nothing falls
        back, and neither the state arena nor an expert matrix is copied
        (a copy of the 16 held up-projections would cost 160 MB of traffic
        an expert block a step)."""
        from paddle_tpu import telemetry
        from paddle_tpu.jit import named_program
        from paddle_tpu.serving.engine import DECODE_PROGRAM

        eng = engine
        pa, ba = eng._param_arrays()
        R, MP = eng.max_batch, eng.max_pages_per_seq
        args = (pa, ba, eng._arenas, jnp.zeros((R, 1), jnp.int32),
                jnp.zeros((R,), jnp.int32), jnp.zeros((R, MP), jnp.int32),
                jnp.ones((R,), jnp.int32))
        args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), args)
        before = telemetry.counters().get("kernel_fallback.total", 0)
        compiled = _compile_uncached(
            jax.jit(named_program(eng._decode_fn, DECODE_PROGRAM),
                    donate_argnums=(2,)), *args)
        text = compiled.as_text()
        # one state layer of three blocks: the expert block keeps nothing
        assert eng.state.nbytes == 64 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            eng._arena_bytes + eng.state.nbytes
        assert _mosaic_calls(text, "ssm_state_update") == 1
        assert _mosaic_calls(text, "paged_decode_attention") == 1
        assert _mosaic_calls(text, "moe_grouped_matmul") == 2
        assert not _copies_of(text, "f32[64,64,64,128]")
        assert not _copies_of(text, "bf16[16,2688,1856]")
        assert not _copies_of(text, "bf16[16,1856,2688]")
        assert telemetry.counters().get("kernel_fallback.total", 0) == before



@pytest.mark.usefixtures("on_tpu")
class TestLoopedProgramsLower:
    """The serving programs of a model whose layers run several times a step
    (Ouro-2.6B's widths: MHA 16 heads of 128, SwiGLU 5632; two layers run
    twice, a small vocabulary), compiled for a described v5e."""

    @pytest.fixture(scope="class")
    def engine(self):
        from paddle_tpu.models import OuroConfig, OuroForCausalLM
        from paddle_tpu.serving import ServingEngine

        paddle.seed(0)
        model = OuroForCausalLM(OuroConfig(
            vocab_size=1024, num_hidden_layers=2, total_ut_steps=2,
            max_position_embeddings=4096))
        model.eval()
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
        return ServingEngine(model, max_batch=16, page_tokens=128,
                             num_pages=9, max_pages_per_seq=20)

    def test_decode_walks_each_layer_once_over_pages_of_every_pass(
            self, engine, one_chip):
        """One loop over the passes; the page walk a Mosaic call a layer (at
        16 KV heads, a query group of one), not a layer and pass; every
        pass's pages aliased in one arena a layer, and none copied."""
        from paddle_tpu import telemetry
        from paddle_tpu.jit import named_program
        from paddle_tpu.serving.engine import DECODE_PROGRAM

        eng = engine
        pa, ba = eng._param_arrays()
        R, MP = eng.max_batch, eng.max_pages_per_seq
        args = (pa, ba, eng._arenas, jnp.zeros((R, 1), jnp.int32),
                jnp.zeros((R,), jnp.int32), jnp.zeros((R, MP), jnp.int32),
                jnp.ones((R,), jnp.int32))
        args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), args)
        before = telemetry.counters().get("kernel_fallback.total", 0)
        compiled = _compile_uncached(
            jax.jit(named_program(eng._decode_fn, DECODE_PROGRAM),
                    donate_argnums=(2,)), *args)
        text = compiled.as_text()
        assert eng._arena_shape == (2 * 9, 128, 16, 128)
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            eng._arena_bytes == 2 * 2 * 18 * 128 * 16 * 128 * 2
        assert _mosaic_calls(text, "paged_decode_attention") == 2
        assert text.count(" while(") == 1
        # none copied in HBM (what fits the chip's VMEM may be prefetched
        # there: memory space S(1))
        assert not [c for c in _copies_of(text, "bf16[18,128,16,128]")
                    if "S(1)" not in c]
        assert telemetry.counters().get("kernel_fallback.total", 0) == before

    def test_decode_takes_q_k_v_as_it_reads_them(self, engine, one_chip):
        """Built as the engine builds it, the decode program chooses the
        weights' layouts: no q / k / v matrix is copied on a launch (at the
        default layouts, six ``bf16[2048,2048]`` copies a step ahead of the
        loop over the passes, and 54 MB of temporaries)."""
        eng = engine
        assert eng.param_layout_refusal is None
        compiled = _engine_decode(eng, one_chip)
        assert not [c for c in _copies_of(compiled.as_text(),
                                          "bf16[2048,2048]")
                    if "S(1)" not in c]
        assert compiled.memory_analysis().temp_size_in_bytes < 10 * 2 ** 20


@pytest.mark.usefixtures("on_tpu")
class TestWeightLayoutsLower:
    """The serving programs of an engine at Mistral-7B's widths (hidden
    4096, GQA 32/8 heads of 128, SwiGLU 14336; two layers, a small
    vocabulary), compiled for a described v5e as the engine builds them: the
    decode program chooses the weights' layouts, and both prefill widths —
    the riding one and the plain one — take the weights in them.  At the
    default layouts every one of them copied q (``bf16[4096,4096]``) and k
    and v (``bf16[1024,4096]``) on each launch."""

    @pytest.fixture(scope="class")
    def engine(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import ServingEngine

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=1024, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=2, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=4096,
            recompute=False))
        model.eval()
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
        return ServingEngine(model, max_batch=64, page_tokens=128,
                             num_pages=65, max_pages_per_seq=4)

    def test_no_program_copies_a_q_k_or_v_matrix(self, engine, one_chip):
        from paddle_tpu.serving.engine import PREFILL_PROGRAM

        eng = engine
        R, MP, P = eng.max_batch, eng.max_pages_per_seq, eng.page_tokens
        decode = _engine_decode(eng, one_chip)
        # the weights as the engine's arrays lie once moved
        pa = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=f) for a, f in
              zip(eng._param_arrays()[0], decode.input_formats[0][0])]

        def prefill(width):
            rest = (eng._param_arrays()[1], eng._arenas,
                    jnp.zeros((1, width * P), jnp.int32), jnp.int32(0),
                    jnp.zeros((1, MP), jnp.int32), jnp.int32(P - 1),
                    jnp.int32(0), jnp.int32(P - 3))
            if eng._carries_rows(width):
                rest += ((jnp.zeros((R, 1), jnp.int32),
                          jnp.zeros((R,), jnp.int32),
                          jnp.zeros((R, MP), jnp.int32),
                          jnp.ones((R,), jnp.int32)),)
            rest = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip), rest)
            with persistent_cache_off():
                return eng._compile(eng._prefill_fn, (pa,) + rest,
                                    PREFILL_PROGRAM)

        assert eng.rides_prefill and eng._prefill_widths == (1, 4)
        assert eng._carries_rows(1) and not eng._carries_rows(4)
        for compiled in (decode, prefill(1), prefill(4)):
            text = compiled.as_text()
            assert not _copies_of(text, "bf16[4096,4096]")
            assert not _copies_of(text, "bf16[1024,4096]")
