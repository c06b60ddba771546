"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One invocation, no flags: ``python chip_smoke.py`` from the root of a
checkout, on a machine that holds a TPU.  It drives the three main paths
through the entry points a user calls, at the full widths of Llama-670M
(the one model every record in this repo uses; depth 8, random weights
from a seed):

- probe      platform, device_kind, device count, versions; not a TPU, or a
             device_kind the chip table does not know -> non-zero exit
             before anything else runs
- kernels    every Pallas kernel a default-on flag dispatches to, alone,
             against its XLA reference (fails fast, names the kernel)
- train      ``paddle.jit.TrainStep`` + ``HealthGuard``, AdamW + global-norm
             clip, bf16 AMP-O2, batch 4 x seq 2048
- generate   ``model.generate()`` (compiled static-cache loop) at a 2K and
             at an 8K cache, Pallas decode kernel against the einsum path
- serve      ``ServingEngine`` over 8 requests, first tokens against
             ``generate()`` and a plain forward; the decode program holds
             one ``paged_decode_attention`` call a layer
- four_chips (only when the host holds >= 4 chips) ``DistributedTrainStep``
             over ``LlamaForCausalLMHybrid`` under mp2 x pp2 and
             sharding2 x sep2 (ZeRO-3)

One process runs every phase, so one process owns the chip; each phase
frees what it built before the next starts.  Every phase checks what came
out by the repo's own means and raises on the first thing that is wrong;
the run ends with one summary line per phase and exits non-zero if any
phase failed.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``.  Timings printed here are set-up and
sanity figures, not performance records.

THE SYNC RULE.  A step time is the host clock around work that ends in
``block_until_ready``.  The train phase confirms once that a host read of
the loss gives the same time (both wait for the device), so the two are
interchangeable here; ``block_until_ready`` is the one the repo writes.

THE NEAR-TIE RULE.  Two programs that compute the same logits in a
different accumulation order agree to bf16 noise, not to the bit, and a
greedy argmax over 32 000 random-weight logits breaks near-ties either way.
So two greedy streams are compared like this: per row, tokens must be
equal up to the first step where they differ; at every compared step
(that one included) the two paths' log-probabilities of their own chosen
tokens must agree within ``NOISE``; after a row's first difference the
streams have different prefixes and are not compared.  A first token is
compared to a reference forward: it must score within ``NOISE`` of the
reference's best logit.  bf16 keeps 8 significant bits; the logits here
reach |x| ~ 4 and the log-probs ~ 10, where one bf16 step is 2**-6 to
2**-5, and ``NOISE`` is a few of those steps.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import re
import sys
import time
import traceback
from typing import Tuple

NOISE = 0.125

# kernel_name of every Mosaic custom call the default-on flags ask for in
# the train step (flash fwd + both bwd, RMSNorm fwd/bwd, RoPE)
TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "rms_norm_fwd", "rms_norm_bwd", "fused_rope")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a phase is sized by.  ``FULL`` is what ``main()`` runs;
    tests/test_chip_smoke.py calls the phases with a tiny one."""
    vocab: int = 32000
    hidden: int = 2048
    inter: int = 8192
    layers: int = 8
    heads: int = 16
    kv_heads: int = 16
    amp: bool = True            # bf16 AMP-O2
    # True on the chip: the kernels must be Mosaic custom calls.  False on
    # the CPU test mesh, where the flag ``pallas_interpret`` runs them
    mosaic: bool = True
    loss_band: float = 1.0      # |loss - ln(vocab)| on random tokens
    train_batch: int = 4
    train_seq: int = 2048
    train_steps: int = 6        # fresh batches after warm-up, >= 5
    gen_batch: int = 8
    gen_prompt: int = 128
    gen_new: int = 32
    long_batch: int = 4
    long_prompt: int = 7680     # + long_new = the 8K cache
    long_new: int = 512
    serve_max_batch: int = 8
    serve_page_tokens: int = 128
    serve_pages: int = 129
    serve_pages_per_seq: int = 16
    serve_prompts: Tuple[int, ...] = (128, 256, 512, 1024) * 2
    serve_new: Tuple[int, int] = (32, 64)
    hybrid_steps: int = 3


FULL = Sizes()


class SmokeFailure(Exception):
    pass


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


# ---------------------------------------------------------------------------
# probe
# ---------------------------------------------------------------------------
def probe() -> dict:
    """Name the machine and refuse anything that is not a known TPU."""
    from importlib import metadata

    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("probe", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, python=sys.version.split()[0])
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: jax platform is {dev.platform!r}, not 'tpu' — "
            "this script only passes on the chip")
    from paddle_tpu.telemetry import PEAK_HBM_GBPS, PEAK_TFLOPS, chip_lookup

    # an unknown device_kind raises here, before anything else runs
    say("probe", peak_bf16_tflops=chip_lookup(dev, PEAK_TFLOPS),
        peak_hbm_gbps=chip_lookup(dev, PEAK_HBM_GBPS))
    return device


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------
def _config(sz: Sizes, max_pos: int):
    from paddle_tpu.models import LlamaConfig

    return LlamaConfig(vocab_size=sz.vocab, hidden_size=sz.hidden,
                       intermediate_size=sz.inter,
                       num_hidden_layers=sz.layers,
                       num_attention_heads=sz.heads,
                       num_key_value_heads=sz.kv_heads,
                       max_position_embeddings=max_pos, recompute=False)


def _eval_model(sz: Sizes, max_pos: int):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(_config(sz, max_pos))
    model.eval()
    if sz.amp:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    return model


def _fallbacks() -> dict:
    import paddle_tpu.telemetry as telemetry

    return {k: v for k, v in telemetry.counters().items()
            if k.startswith("kernel_fallback.")}


def _require_no_fallback(phase: str) -> None:
    fb = _fallbacks()
    require(not fb, f"{phase}: a Pallas gate fell back to the XLA path: {fb}")


def _kernel_counts(text: str, names) -> dict:
    return {n: text.count(f'kernel_name = "{n}"') for n in names}


def _require_mosaic(sz: Sizes) -> None:
    from paddle_tpu.ops import pallas_interpret_mode

    require(not sz.mosaic or not pallas_interpret_mode(),
            "pallas_interpret is on: the kernels would not be Mosaic calls")


def _free() -> None:
    import jax

    gc.collect()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _close(tag: str, got, want, rel: float) -> float:
    """max |got - want| within ``rel`` of the reference's largest value."""
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    require(math.isfinite(err) and err <= rel * scale,
            f"{tag}: max |kernel - reference| {err} > {rel} x {scale}")
    return err


def _attention_parity(sz: Sizes, batch: int, cache_len: int) -> float:
    """``generation.cached_attention`` on one random decode step at the
    cache shape, kernel flag on against off: outputs within the bf16
    tolerance tests/test_decode_attention.py uses, caches bit-equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.generation import cached_attention

    d = sz.hidden // sz.heads
    dt = jnp.bfloat16 if sz.amp else jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(keys[0], (batch, 1, sz.heads, d)).astype(dt)
    kn, vn = (jax.random.normal(k, (batch, 1, sz.kv_heads, d)).astype(dt)
              for k in keys[1:3])
    ck, cv = (jax.random.normal(k, (batch, cache_len, sz.kv_heads, d))
              .astype(dt) for k in keys[3:5])
    pos = jnp.int32(cache_len // 2 + 3)
    pads = jnp.asarray([(3 * i) % 7 for i in range(batch)], jnp.int32)
    outs = {}
    for on in (True, False):
        paddle.set_flags({"use_decode_attention": on})
        try:
            # a fresh jit per flag value: the gate runs at trace time
            outs[on] = jax.jit(lambda *a: cached_attention(*a))(
                q, kn, vn, ck, cv, pos, pads)
        finally:
            paddle.set_flags({"use_decode_attention": True})
    err = _close("decode_attention", outs[True][0], outs[False][0],
                 2e-2 if sz.amp else 2e-5)
    for a, b in zip(outs[True][1:], outs[False][1:]):
        require(bool(np.array_equal(np.asarray(a.astype(jnp.float32)),
                                    np.asarray(b.astype(jnp.float32)))),
                "decode kernel's in-place cache append differs from the "
                "einsum path's dynamic_update_slice")
    return err


def _paged_parity(sz: Sizes) -> float:
    """``paged_decode_attention`` on one random decode step over the serve
    phase's pool (ragged rows, the last one idle), against the gather of
    every row's whole padded table and a dense masked softmax."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import paged_decode_attention

    R, P = sz.serve_max_batch, sz.serve_page_tokens
    MP, N = sz.serve_pages_per_seq, sz.serve_pages
    d = sz.hidden // sz.heads
    dt = jnp.bfloat16 if sz.amp else jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (R, 1, sz.heads, d)).astype(dt)
    k, v = (jax.random.normal(key, (N, P, sz.kv_heads, d)).astype(dt)
            for key in keys[1:])
    positions = np.asarray([sz.serve_prompts[r % len(sz.serve_prompts)]
                            for r in range(R)], np.int32)
    n_tok = np.ones((R,), np.int32)
    n_tok[-1] = positions[-1] = 0
    tables, page = np.zeros((R, MP), np.int32), 1
    for r in range(R):
        for j in range(-(-(positions[r] + n_tok[r]) // P)):
            tables[r, j], page = page, page + 1
    require(page <= N, "the serve pool cannot hold the parity rows")
    got = jax.jit(lambda *a: paged_decode_attention(
        *a, interpret=not sz.mosaic))(q, k, v, tables, positions, n_tok)

    def dense(q, k, v, tables, positions):
        g = sz.heads // sz.kv_heads
        kk, vv = (x[tables].reshape(R, MP * P, sz.kv_heads, d)
                  for x in (k, v))
        s = jnp.einsum("bkgd,bckd->bkgc",
                       q.reshape(R, sz.kv_heads, g, d), kk,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        s = jnp.where(jnp.arange(MP * P)[None, None, None, :]
                      <= positions[:, None, None, None], s, -jnp.inf)
        return jnp.einsum("bkgc,bckd->bkgd",
                          jax.nn.softmax(s, -1).astype(dt), vv,
                          preferred_element_type=jnp.float32) \
            .reshape(R, 1, sz.heads, d)

    want = jax.jit(dense)(q, k, v, tables, positions)
    require(not bool(jnp.any(got[-1] != 0)), "the idle row is not zero")
    return _close("paged_decode_attention", got[:-1], want[:-1],
                  2e-2 if sz.amp else 2e-5)


def kernels(sz: Sizes) -> dict:
    """Every Pallas kernel a default-on flag dispatches to, alone, at the
    shapes the other phases use plus the seq-8192 train point
    (``flash_blocks [1024, 512]``): compiled, run, and held to the XLA
    reference — attention on the first two heads only, where the
    reference's [s, s] score matrix still fits at 8K."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.llama import rotate_half_apply
    from paddle_tpu.ops.attention import sdpa_reference
    from paddle_tpu.ops.pallas import (flash_attention,
                                       flash_attention_varlen,
                                       fused_rms_norm, fused_rope)
    from paddle_tpu.ops.sharded import _auto_block

    _require_mosaic(sz)
    interp = not sz.mosaic
    d = sz.hidden // sz.heads
    dt = jnp.bfloat16 if sz.amp else jnp.float32
    rel = 2e-2 if sz.amp else 2e-4
    long_seq = sz.long_prompt + sz.long_new
    report = {}

    def rand(seed, *shape):
        return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dt)

    # cotangents ride as arguments: closed over, they would be baked into
    # the program as 67 MB constants
    def attention_loss(fn):
        return lambda q, k, v, ct: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * ct)

    for b, s, caps in ((sz.train_batch, sz.train_seq, (512, 512)),
                       (1, long_seq, (1024, 512))):
        bq, bk = _auto_block(s, caps[0]), _auto_block(s, caps[1])
        require(bq and bk, f"seq {s} cannot be tiled for the flash kernel")
        q, k, v = (rand(i, b, s, sz.heads, d) for i in range(3))
        ct = rand(3, b, s, sz.heads, d).astype(jnp.float32)
        got = jax.jit(jax.value_and_grad(attention_loss(
            lambda q, k, v: flash_attention(q, k, v, None, True, bq, bk,
                                            interp)), argnums=(0, 1, 2))
        )(q, k, v, ct)
        want = jax.jit(jax.value_and_grad(attention_loss(
            lambda q, k, v: sdpa_reference(q, k, v, is_causal=True)),
            argnums=(0, 1, 2)))(*(x[:, :, :2] for x in (q, k, v, ct)))
        # heads are independent, and so are their gradients
        errs = [_close(f"flash {name} seq {s}", g[:, :, :2], w, rel)
                for name, g, w in zip(("dq", "dk", "dv"), got[1], want[1])]
        require(math.isfinite(float(got[0])), f"flash seq {s}: loss not finite")
        report[f"flash_{s}"] = max(errs)
        say("kernels", kernel="flash fwd+bwd", batch=b, seq=s,
            blocks=[bq, bk], max_grad_err=max(errs))

    n, s = len(sz.serve_prompts), max(sz.serve_prompts)
    bq = _auto_block(s, 512)
    q, k, v = (rand(10 + i, n, s, sz.heads, d) for i in range(3))
    pads = jnp.asarray([s - p for p in sz.serve_prompts], jnp.int32)
    got = jax.jit(lambda q, k, v, p: flash_attention_varlen(
        q, k, v, p, block_q=bq, block_k=bq, interpret=interp))(q, k, v, pads)
    keep = (jnp.arange(s)[None, :] >= pads[:, None]).astype(jnp.float32)
    mask = (1.0 - keep)[:, None, None, :] * jnp.finfo(jnp.float32).min
    want = jax.jit(lambda q, k, v, mask: sdpa_reference(
        q, k, v, mask=mask, is_causal=True))(
            *(x[:, :, :2] for x in (q, k, v)), mask)
    # query rows inside the left padding have no valid key: undefined
    valid = keep[:, :, None, None]
    report["flash_varlen"] = _close("flash_attention_varlen",
                                    got[:, :, :2] * valid, want * valid, rel)
    say("kernels", kernel="flash_attention_varlen", batch=n, seq=s,
        max_err=report["flash_varlen"])

    for b, cache in ((sz.gen_batch, -(-(sz.gen_prompt + sz.gen_new) // 8) * 8),
                     (sz.long_batch, -(-long_seq // 8) * 8)):
        report[f"decode_{cache}"] = _attention_parity(sz, b, cache)
        say("kernels", kernel="decode_attention", batch=b, cache=cache,
            max_err=report[f"decode_{cache}"])

    report["paged_decode"] = _paged_parity(sz)
    say("kernels", kernel="paged_decode_attention",
        rows=sz.serve_max_batch, table=sz.serve_pages_per_seq,
        max_err=report["paged_decode"])

    x = rand(20, sz.train_batch, sz.train_seq, sz.hidden)
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(21), (sz.hidden,))
    ct = rand(22, *x.shape).astype(jnp.float32)

    def norm_ref(x, w, eps=1e-6):
        xf = x.astype(jnp.float32)
        return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                   + eps) * w).astype(x.dtype)

    def norm_loss(fn):
        return lambda x, w, ct: jnp.sum(fn(x, w).astype(jnp.float32) * ct)

    got = jax.jit(jax.grad(norm_loss(
        lambda x, w: fused_rms_norm(x, w, 1e-6, interp)),
        argnums=(0, 1)))(x, w, ct)
    want = jax.jit(jax.grad(norm_loss(norm_ref), argnums=(0, 1)))(x, w, ct)
    report["rms_norm"] = max(_close(f"rms_norm {name}", g, r, rel)
                             for name, g, r in zip(("dx", "dw"), got, want))
    say("kernels", kernel="rms_norm fwd+bwd", rows=x.shape[0] * x.shape[1],
        max_grad_err=report["rms_norm"])

    q, k = (rand(30 + i, sz.train_batch, sz.train_seq, sz.heads, d)
            for i in range(2))
    pos = jnp.arange(sz.train_seq, dtype=jnp.float32)[:, None] \
        * jnp.exp(-jnp.arange(d, dtype=jnp.float32) / d)[None, :]
    cos, sin = jnp.cos(pos), jnp.sin(pos)
    got = jax.jit(lambda q, k, cos, sin: fused_rope(
        q, k, cos, sin, interp))(q, k, cos, sin)
    want = jax.jit(lambda q, k, cos, sin: rotate_half_apply(
        q, k, cos[None, :, None, :], sin[None, :, None, :]))(q, k, cos, sin)
    report["rope"] = max(_close(f"fused_rope {name}", g, r, rel)
                         for name, g, r in zip(("q", "k"), got, want))
    say("kernels", kernel="fused_rope", max_err=report["rope"])
    _require_no_fallback("kernels")
    return report


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def train(sz: Sizes) -> dict:
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.health import HealthGuard, HealthPolicy
    from paddle_tpu.models import LlamaForCausalLM

    _require_mosaic(sz)
    t_setup = time.perf_counter()
    paddle.seed(0)
    cfg = _config(sz, sz.train_seq)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 grad_clip=nn.ClipGradByGlobalNorm(1.0))
    if sz.amp:
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")
    guard = HealthGuard(HealthPolicy(), name="chip_smoke",
                        on_escalate="raise")
    step = paddle.jit.TrainStep(model, lambda m, x, y: m(x, labels=y)[0], opt,
                                health_guard=guard)
    rng = np.random.default_rng(0)

    def batch():
        ids = rng.integers(0, sz.vocab, (sz.train_batch, sz.train_seq)) \
            .astype("int32")
        return (paddle.to_tensor(ids),
                paddle.to_tensor(np.roll(ids, -1, axis=1)))

    # the program the step compiles must hold the kernels its flags ask for
    x, y = batch()
    text = step.lower(x, y).as_text()
    counts = _kernel_counts(text, TRAIN_KERNELS)
    say("train", tpu_custom_calls=text.count("tpu_custom_call"), **counts)
    if sz.mosaic:
        missing = [k for k, n in counts.items() if n == 0]
        require(not missing,
                f"train step lowered without Mosaic kernels {missing}")
    del text

    losses = [float(step(x, y)), float(step(*batch()))]  # warm-up: compiles
    say("train", setup_s=round(time.perf_counter() - t_setup, 1))

    # >= 5 fresh-batch steps; the first half timed to block_until_ready, the
    # second to a host read of the loss — the sync rule's one confirmation
    half = sz.train_steps // 2
    times = {"block_until_ready": [], "host_read": []}
    for i in range(sz.train_steps):
        xb, yb = batch()
        t0 = time.perf_counter()
        loss = step(xb, yb)
        if i < half:
            loss.value.block_until_ready()
            times["block_until_ready"].append(time.perf_counter() - t0)
            losses.append(float(loss))
        else:
            losses.append(float(loss))
            times["host_read"].append(time.perf_counter() - t0)
    t_block = float(np.median(times["block_until_ready"]))
    t_read = float(np.median(times["host_read"]))
    say("train", step_s_block_until_ready=round(t_block, 4),
        step_s_host_read=round(t_read, 4))
    # a statement about the device: held at the chip's sizes, where a step
    # is a quarter of a second, not at the interpreted tiny size, where a
    # 10 ms CPU step under the test workers' load is scheduler noise
    require(not sz.mosaic or 0.5 < t_read / t_block < 2.0,
            f"host read ({t_read:.4f}s) and block_until_ready "
            f"({t_block:.4f}s) disagree about a step: one of them does not "
            "wait for the device")

    target = math.log(sz.vocab)
    say("train", ln_vocab=round(target, 3),
        losses=[round(v, 3) for v in losses])
    for v in losses:
        require(math.isfinite(v) and abs(v - target) <= sz.loss_band,
                f"loss {v} not within {sz.loss_band} of ln(vocab) {target:.3f}")

    # one repeated batch: the second pass must have learnt from the first
    first, second = float(step(x, y)), float(step(x, y))
    say("train", repeated_batch=[round(first, 4), round(second, 4)])
    require(math.isfinite(second) and second < first,
            f"repeated batch did not improve: {first} -> {second}")

    guard.flush()
    say("train", steps_skipped=guard.steps_skipped, rewinds=guard.rewinds)
    require(guard.steps_skipped == 0 and guard.rewinds == 0,
            f"HealthGuard skipped {guard.steps_skipped} steps, "
            f"{guard.rewinds} rewinds")
    _require_no_fallback("train")
    return {"losses": losses, "step_s": t_block}


# ---------------------------------------------------------------------------
# generate
# ---------------------------------------------------------------------------
def _generate_program_text(model, ids) -> str:
    """Lowered text of the program ``generate()`` just built for ``ids``
    (the newest entry of the model's generate cache, called the way
    ``generate()`` calls it)."""
    import jax
    import jax.numpy as jnp

    prog = next(reversed(model._generate_cache.values()))
    params = [p.value for _, p in model.named_parameters()]
    buffers = [b.value for _, b in model.named_buffers()]
    return prog.lower(params, buffers, jnp.asarray(ids, jnp.int32),
                      jnp.zeros((ids.shape[0],), jnp.int32),
                      jax.random.PRNGKey(0)).as_text()


def _compare_streams(tag: str, tok_a, lp_a, tok_b, lp_b) -> dict:
    """THE NEAR-TIE RULE (module docstring) over two greedy streams
    ``[rows, steps]`` with their chosen-token log-probs."""
    import numpy as np

    rows, steps = tok_a.shape
    agreed, worst = [], 0.0
    for r in range(rows):
        diff = np.nonzero(tok_a[r] != tok_b[r])[0]
        first = int(diff[0]) if diff.size else steps
        upto = min(first, steps - 1)
        gap = float(np.max(np.abs(lp_a[r, :upto + 1] - lp_b[r, :upto + 1])))
        worst = max(worst, gap)
        require(gap <= NOISE,
                f"{tag} row {r}: the two paths' log-probs differ by {gap} "
                f"(> {NOISE}) within the first {upto + 1} steps — not a "
                "near-tie, the paths compute different distributions")
        agreed.append(first)
    require(all(a >= 1 for a in agreed),
            f"{tag}: first tokens differ, but both paths share one prefill")
    return {"rows": rows, "steps": steps, "agreed_prefix": agreed,
            "max_logprob_gap": round(worst, 4)}


def _generate_both(model, ids, new: int):
    """Greedy ``generate()`` under the Pallas decode kernel, then under
    the einsum path; returns (kernel stream, einsum stream, kernel-path
    program text)."""
    import paddle_tpu as paddle

    t0 = time.perf_counter()
    tok, lp = model.generate(paddle.to_tensor(ids), max_new_tokens=new)
    kern = (tok.numpy(), lp.numpy())
    text = _generate_program_text(model, ids)
    t_kernel = time.perf_counter() - t0
    paddle.set_flags({"use_decode_attention": False})
    try:
        tok, lp = model.generate(paddle.to_tensor(ids), max_new_tokens=new)
        eins = (tok.numpy(), lp.numpy())
    finally:
        paddle.set_flags({"use_decode_attention": True})
    return kern, eins, text, t_kernel


def generate(sz: Sizes) -> dict:
    import numpy as np

    from paddle_tpu.framework.flags import get_flags

    _require_mosaic(sz)
    require(get_flags("use_decode_attention")["use_decode_attention"],
            "use_decode_attention is off: nothing to prove")
    model = _eval_model(sz, sz.long_prompt + sz.long_new)
    rng = np.random.default_rng(5)
    report = {}
    for tag, b, prompt, new in (
            ("2k", sz.gen_batch, sz.gen_prompt, sz.gen_new),
            ("8k", sz.long_batch, sz.long_prompt, sz.long_new)):
        cache_len = -(-(prompt + new) // 8) * 8
        ids = rng.integers(0, sz.vocab, (b, prompt)).astype("int32")
        kern, eins, text, secs = _generate_both(model, ids, new)
        n_kernel = _kernel_counts(text, ("decode_attention",))[
            "decode_attention"]
        # interpreted kernels leave no Mosaic call to count: there the
        # fallback counters (checked below) are the evidence
        path = "pallas decode_attention" if n_kernel else \
            "einsum" if sz.mosaic else "pallas decode_attention, interpreted"
        say(f"generate.{tag}", batch=b, prompt=prompt, new=new,
            cache=cache_len, attention_path=path,
            decode_kernel_calls=n_kernel, first_run_s=round(secs, 1))
        require(not sz.mosaic or n_kernel > 0,
                f"generate.{tag}: use_decode_attention is on but the "
                "compiled program holds no decode_attention kernel")
        for name, (tok, lp) in (("kernel", kern), ("einsum", eins)):
            require(tok.shape == (b, new) and lp.shape == (b, new),
                    f"generate.{tag} {name}: shape {tok.shape}")
            require(bool(np.all((tok >= 0) & (tok < sz.vocab)))
                    and bool(np.all(np.isfinite(lp))),
                    f"generate.{tag} {name}: bad token or log-prob")
        report[tag] = _compare_streams(f"generate.{tag}", *kern, *eins)
        say(f"generate.{tag}", **report[tag])
    _require_no_fallback("generate")
    return report


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def serve(sz: Sizes) -> dict:
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine

    _require_mosaic(sz)
    longest = max(sz.serve_prompts)
    model = _eval_model(sz, sz.serve_pages_per_seq * sz.serve_page_tokens)
    eng = ServingEngine(model, max_batch=sz.serve_max_batch,
                        page_tokens=sz.serve_page_tokens,
                        num_pages=sz.serve_pages,
                        max_pages_per_seq=sz.serve_pages_per_seq,
                        max_queue=len(sz.serve_prompts) + 1)
    rng = np.random.default_rng(7)
    prompts, wanted, rids = [], [], []
    for n in sz.serve_prompts:
        prompts.append(rng.integers(1, sz.vocab, n).astype(np.int32))
        wanted.append(int(rng.integers(sz.serve_new[0], sz.serve_new[1] + 1)))
        rids.append(eng.submit(prompts[-1], max_new_tokens=wanted[-1]))
    t0 = time.perf_counter()
    outs = eng.run()
    wall = time.perf_counter() - t0
    s = eng.meter.summary()
    say("serve", requests=len(rids), wall_s=round(wall, 1),
        decode_compiles=eng._decode_compiles, steps=eng.steps_total,
        shed=s["requests_shed"], rejected=s["requests_rejected"],
        donation_lint="pass" if eng.lint_report is not None
        and eng.lint_report.ok else "FAIL")
    for rid, want in zip(rids, wanted):
        require(rid in outs and len(outs[rid]) == want,
                f"request {rid} finished with "
                f"{len(outs.get(rid, ()))} of {want} tokens")
    require(not eng.shed and s["requests_shed"] == 0
            and s["requests_rejected"] == 0, f"requests shed: {eng.shed}")
    require(eng._decode_compiles == 1,
            f"{eng._decode_compiles} decode compiles, expected one")
    require(eng.lint_report is not None and eng.lint_report.ok,
            "decode program failed the donation lint")
    # the decode program walks live pages: one Mosaic call a layer
    # (interpreted kernels leave none to count)
    if sz.mosaic:
        calls = len(re.findall(r"%paged_decode_attention[.\d]* = ",
                               eng._decode_exec.as_text()))
        say("serve", paged_decode_attention_calls=calls)
        require(calls == sz.layers,
                f"{calls} paged_decode_attention calls in the decode "
                f"program, expected {sz.layers}")
    # the engine raises on a non-finite live row every step; the last
    # step's logits stayed on the device and are fetched here
    logits = eng.last_decode_logits
    require(logits is not None and logits.shape[-1] == sz.vocab
            and bool(np.isfinite(logits).any()),
            "no finite decode logits were kept")

    # first tokens: generate() over the same prompts, left-padded into one
    # batch (bucketed prefill -> the varlen flash kernel), and a plain
    # forward as the reference both are held to
    n = len(prompts)
    lens = np.asarray([len(p) for p in prompts])
    left = np.zeros((n, longest), np.int32)
    right = np.zeros((n, longest), np.int32)
    mask = np.zeros((n, longest), np.int32)
    for i, p in enumerate(prompts):
        left[i, longest - len(p):] = p
        mask[i, longest - len(p):] = 1
        right[i, :len(p)] = p
    gen_first = model.generate(paddle.to_tensor(left), max_new_tokens=1,
                               attention_mask=mask)[0].numpy()[:, 0]
    eng_first = np.asarray([outs[rid][0] for rid in rids])
    forward = paddle.jit.to_static(lambda ids: model(ids))
    with paddle.no_grad():
        full = forward(paddle.to_tensor(right)).value      # [n, s, vocab]
    ref = np.asarray(full[jnp.arange(n), jnp.asarray(lens - 1)]
                     .astype(jnp.float32))
    best = ref.max(axis=1)
    for name, first in (("engine", eng_first), ("generate", gen_first)):
        short = best - ref[np.arange(n), first]
        say("serve", path=name, first_tokens=first.tolist(),
            max_short_of_best=round(float(short.max()), 4))
        require(bool(np.all(short <= NOISE)),
                f"{name} first tokens score {short.max()} below the "
                f"reference's best logit (> {NOISE}): not a near-tie")
    same = int(np.sum(eng_first == gen_first))
    say("serve", first_tokens_equal=f"{same}/{n}")
    require(2 * same >= n,
            f"engine and generate() agree on only {same}/{n} first tokens")
    _require_no_fallback("serve")
    return {"requests": n, "first_tokens_equal": same}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
               "collective-permute", "all-to-all")

# degrees of the two factorizations __graft_entry__.dryrun_multichip(4)
# uses, the mesh axes whose parameters must be sharded, and the collectives
# each implies in the compiled HLO: collective-permute for pipe / sep / the
# TP rings, all-reduce for TP, all-gather + reduce-scatter for ZeRO-3 (a
# backend without a native reduce-scatter — the CPU test mesh — compiles
# it as all-reduce + slice, so either spelling counts)
FACTORIZATIONS = (
    ("mp2xpp2", {"mp": 2, "pp": 2}, ("model", "pipe"),
     (("collective-permute",), ("all-reduce",))),
    ("sharding2xsep2", {"sharding": 2, "sep": 2}, ("sharding",),
     (("all-gather",), ("reduce-scatter", "all-reduce"),
      ("collective-permute",))),
)


def four_chips(sz: Sizes) -> dict:
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import topology
    from paddle_tpu.models.llama_parallel import LlamaForCausalLMHybrid

    _require_mosaic(sz)
    devices = jax.devices()[:4]
    require(len(devices) == 4, "four_chips needs four devices")
    rng = np.random.default_rng(11)
    ids_np = rng.integers(0, sz.vocab, (sz.train_batch, sz.train_seq)) \
        .astype("int32")
    report = {}
    for name, degrees, sharded_axes, collectives in FACTORIZATIONS:
        before = [_bytes_in_use(d) for d in devices]
        hcg = topology.HybridCommunicateGroup(
            mesh=topology.build_mesh(devices=devices, **degrees))
        topology.set_hybrid_communicate_group(hcg)
        try:
            paddle.seed(0)
            model = LlamaForCausalLMHybrid(_config(sz, sz.train_seq), hcg)
            opt = paddle.optimizer.AdamW(
                1e-4, parameters=model.parameters(),
                grad_clip=nn.ClipGradByGlobalNorm(1.0))
            if sz.amp:
                model, opt = paddle.amp.decorate(model, opt, level="O2",
                                                 dtype="bfloat16")
            step = dist.DistributedTrainStep(
                model, lambda m, x, y: m(x, labels=y)[0], opt, hcg,
                sharding_stage=3)
            ids = paddle.to_tensor(ids_np)
            labels = paddle.to_tensor(np.roll(ids_np, -1, axis=1))
            t0 = time.perf_counter()
            lowered = step.lower(ids, labels)
            calls = lowered.as_text().count("tpu_custom_call")
            hlo = lowered.compile().as_text()
            # opcodes, not names: "... = bf16[..] all-gather(" and its
            # async "-start" spelling ("-done" would count the op twice)
            found = {c: len(re.findall(rf" {c}(?:-start)?\(", hlo))
                     for c in COLLECTIVES}
            losses = [float(step(ids, labels))
                      for _ in range(sz.hybrid_steps)]
            say(f"four_chips.{name}", setup_and_steps_s=round(
                time.perf_counter() - t0, 1), tpu_custom_calls=calls,
                losses=[round(v, 4) for v in losses], **found)
            require(not sz.mosaic or calls > 0,
                    f"{name}: no Mosaic kernel in the hybrid step")
            require(all(math.isfinite(v) for v in losses)
                    and all(b < a for a, b in zip(losses, losses[1:])),
                    f"{name}: loss not finite and falling: {losses}")
            missing = [alts for alts in collectives
                       if not any(found[c] for c in alts)]
            require(not missing,
                    f"{name}: compiled HLO holds none of {missing}")
            _require_sharded(name, model, set(devices), sharded_axes)
            after = [_bytes_in_use(d) for d in devices]
            spread = None
            if None not in after:
                used = [a - b for a, b in zip(after, before)]
                spread = round(max(used) / max(min(used), 1), 3)
                say(f"four_chips.{name}", bytes_in_use=used,
                    max_over_min=spread)
                require(spread <= 2.0,
                        f"{name}: per-device memory {used} is lopsided")
            else:
                say(f"four_chips.{name}",
                    bytes_in_use="not reported by this backend")
            report[name] = {"losses": losses, "collectives": found,
                            "memory_max_over_min": spread}
        finally:
            topology._hcg = None   # back to one-device programs
        del model, opt, step, lowered, hlo
        _free()
    _require_no_fallback("four_chips")
    return report


def _bytes_in_use(device):
    stats = device.memory_stats()
    return None if not stats else int(stats["bytes_in_use"])


def _require_sharded(name: str, model, devices: set, axes) -> None:
    """Every parameter lives on all four devices, each mesh axis the
    factorization shards is named by some parameter's spec, and a sharded
    parameter's local shard is really smaller than the whole."""
    used = set()
    for pname, p in model.named_parameters():
        sh = p.value.sharding
        require(set(sh.device_set) == devices,
                f"{name}: {pname} lives on {len(sh.device_set)} device(s)")
        spec_axes = {a for entry in sh.spec if entry is not None
                     for a in (entry if isinstance(entry, tuple)
                               else (entry,))}
        used |= spec_axes
        if spec_axes:
            local = p.value.addressable_shards[0].data.shape
            require(math.prod(local) < math.prod(p.value.shape),
                    f"{name}: {pname} spec {sh.spec} but shard {local} is "
                    f"the whole {p.value.shape}")
    require(set(axes) <= used,
            f"{name}: no parameter is sharded over {set(axes) - used}")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------
def main() -> int:
    t_start = time.perf_counter()
    device = probe()

    import paddle_tpu as paddle
    from paddle_tpu.compile import enable_persistent_cache

    # before the first compile: every program of every phase is cached
    cache = enable_persistent_cache()
    paddle.set_flags({"pallas_interpret": False})

    phases = [("kernels", kernels), ("train", train),
              ("generate", generate), ("serve", serve)]
    if device["count"] >= 4:
        phases.append(("four_chips", four_chips))
    summary = [("probe", "pass", 0.0)]
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase(FULL)
            status = "pass"
        except Exception:
            # the traceback is the report; the phase is counted as failed
            # and the run exits non-zero
            traceback.print_exc()
            status = "FAIL"
        summary.append((name, status, time.perf_counter() - t0))
        _free()
    if device["count"] < 4:
        summary.append(("four_chips", f"did not run: {device['count']} "
                        "chip(s) on this host", 0.0))

    failed = [n for n, status, _ in summary if status == "FAIL"]
    print("chip_smoke summary", flush=True)
    for name, status, secs in summary:
        print(f"  {name:<11} {status}  ({secs:.0f} s)")
    print(f"  compile cache {cache.dir}: {cache.hits} hits, "
          f"{cache.misses} misses; total {time.perf_counter() - t_start:.0f} s")
    print(json.dumps({"ok": not failed, "device": device,
                      **({"failed": failed} if failed else {})}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
