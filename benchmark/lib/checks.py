"""The comparison that decides ``correct``.

The system computes in bfloat16 (8 significant bits: one rounding is 2**-9
relative) and the reference in float32, so two sound programs agree to
bfloat16 noise and not to the bit.  How much noise that is depends on the
depth and width, so a configuration's file states its own limit
(``check.logit_rms_tol``) with the chip measurement it was set from; the
rule is: above what bfloat16 gives, under what int8 or fp8 KV pages or
matmuls give.  Measured on the v5e in PR 23 (``PERF.md`` section 2), rows of
decode logits of the 16-layer Mistral cut against the reference: bfloat16
3.0-4.0 % (median 3.6 %), the model's own flash forward 3.8 %; int8 pages
5.5-7.8 % (median 6.7 %); fp8 pages 19-26 %.  GPT 12 layers, bfloat16: 1.0 %.

A row's error is rms(got - ref) / rms(ref) over the vocabulary.  The MEDIAN
row is held to the limit (one row's noise does not decide a run) and the
worst row to twice the limit (one corrupted row does).

``NEAR_TIE``: how far below the reference's best logit a greedily chosen
token may score.  With random weights the best two of 32k logits are often
closer than the noise (0.05 at these logits' rms of 1.3), so the argmax may
differ; the choice must still be a near-tie.  bfloat16 gave up to 0.09, int8
pages 0.22, fp8 pages 0.98; the limit is five times the noise.
``LOSS_TOL``: absolute difference of a mean cross-entropy near ln(vocab)
~ 10.8, where bfloat16's own step is 2**-5 = 0.03 (measured: 0.0008)."""

from __future__ import annotations

import numpy as np

NEAR_TIE = 0.25
LOSS_TOL = 0.03


def row_errors(got, ref) -> np.ndarray:
    """rms(got - ref) / rms(ref) of every row (rows are the last axis)."""
    got = np.asarray(got, np.float32).reshape(-1, np.shape(got)[-1])
    ref = np.asarray(ref, np.float32).reshape(got.shape)
    num = np.sqrt(np.mean((got - ref) ** 2, -1))
    den = np.sqrt(np.mean(ref ** 2, -1))
    return num / np.maximum(den, 1e-30)


def decide(verdict: dict) -> dict:
    """``ok`` is every compared number within its limit.  ``limits`` names
    each number a verdict compares beside its limit; the run's line and its
    last lines on standard error print them (``run.compared``)."""
    verdict["ok"] = all(bool(verdict[name] <= limit)
                        for name, limit in verdict["limits"].items())
    return verdict


def logits_agree(errors, tol: float) -> dict:
    errors = np.asarray(errors, np.float64)
    return decide({"logits_rms_rel_err_median": float(np.median(errors)),
                   "logits_rms_rel_err_worst": float(np.max(errors)),
                   "rows": int(errors.size),
                   "limits": {"logits_rms_rel_err_median": tol,
                              "logits_rms_rel_err_worst": 2 * tol}})


def short_of_best(ref_logits, chosen) -> float:
    """How far the chosen tokens score below the reference's best, worst
    row (0 where every choice is the reference's argmax)."""
    ref = np.asarray(ref_logits, np.float32)
    rows = np.arange(ref.shape[0])
    return float(np.max(ref.max(-1) - ref[rows, np.asarray(chosen)]))
