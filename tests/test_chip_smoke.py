"""chip_smoke.py off the chip: it must refuse to pass, and its phases must
work.

The script's verdict only counts on a TPU, so here it has to exit non-zero
and name the platform it found.  Its phase functions take their sizes as an
argument, so this file calls each one at a tiny size — Pallas kernels
interpreted, the four-chip phase on four of the eight virtual CPU devices —
and a broken phase is found by tier-1, not by a chip run.
"""

import os
import subprocess
import sys

import pytest

import paddle_tpu as paddle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

# every shape multiple is the smallest the kernels' gates take: 8 f32 kv
# heads, seq and prompt lengths divisible by 8
TINY = chip_smoke.Sizes(
    vocab=128, hidden=64, inter=128, layers=2, heads=8, kv_heads=8,
    amp=False, mosaic=False, train_batch=2, train_seq=32, train_steps=6,
    gen_batch=2, gen_prompt=8, gen_new=8, long_batch=1, long_prompt=16,
    long_new=8, serve_max_batch=3, serve_page_tokens=8, serve_pages=24,
    serve_pages_per_seq=6, serve_prompts=(5, 9, 14, 16), serve_new=(2, 4),
    hybrid_steps=3)


def test_refuses_to_pass_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "platform is 'cpu'" in run.stderr
    # no result line: nothing on stdout parses as the JSON verdict
    assert '"ok"' not in run.stdout


def test_unknown_device_kind_raises():
    from paddle_tpu.telemetry import PEAK_TFLOPS, chip_lookup

    class Unknown:
        platform = "tpu"
        device_kind = "TPU v99"

    with pytest.raises(KeyError, match="TPU v99"):
        chip_lookup(Unknown, PEAK_TFLOPS)


@pytest.fixture
def interpreted():
    from paddle_tpu.distributed import topology

    prior_flags = paddle.get_flags(["pallas_interpret"])
    prior_hcg = topology.get_hybrid_communicate_group()
    topology._hcg = None          # an earlier distributed test's mesh
    paddle.set_flags({"pallas_interpret": True})
    yield
    paddle.set_flags(prior_flags)
    topology._hcg = prior_hcg


@pytest.mark.parametrize("phase", ["kernels", "train", "generate", "serve",
                                   "four_chips"])
def test_phase_passes_at_tiny_size(phase, interpreted):
    import paddle_tpu.telemetry as telemetry

    # the phases read the fallback counters absolutely: start them clean
    telemetry.reset()
    getattr(chip_smoke, phase)(TINY)


def test_near_tie_rule():
    """Streams may part at a near-tie, never where the two paths' log-probs
    disagree; a failed check raises, it is not turned into a field."""
    import numpy as np

    tok = np.array([[5, 6, 7, 8]])
    lp = np.full((1, 4), -7.0)
    other = np.array([[5, 6, 9, 1]])           # parts at step 2
    out = chip_smoke._compare_streams("t", tok, lp, other, lp - 0.01)
    assert out["agreed_prefix"] == [2]
    far = lp.copy()
    far[0, 2] -= 1.0                           # ... but not at a near-tie
    with pytest.raises(chip_smoke.SmokeFailure, match="not a near-tie"):
        chip_smoke._compare_streams("t", tok, lp, other, far)
    late = lp.copy()
    late[0, 3] -= 1.0                          # after the split: not compared
    chip_smoke._compare_streams("t", tok, lp, other, late)
    with pytest.raises(chip_smoke.SmokeFailure, match="first tokens differ"):
        chip_smoke._compare_streams("t", tok, lp, tok + 1, lp)


def test_mosaic_sizes_refuse_interpreted_kernels(interpreted):
    with pytest.raises(chip_smoke.SmokeFailure, match="pallas_interpret"):
        chip_smoke.train(chip_smoke.FULL)
