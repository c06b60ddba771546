"""OpTest-grade numerics harness (reference `test/legacy_test/op_test.py`:
``check_output`` :420 — per-dtype forward vs a trusted reference with a
tolerance table; ``check_grad`` :2973 — analytic vs numeric gradients).

Usage (see tests/test_op_numerics.py):

    check_op("tanh", lambda x: paddle.tanh(x), ref=np.tanh,
             inputs=[rand(4, 8)])

For each dtype in ``dtypes``:
  1. forward: paddle op vs ``ref`` (numpy/jnp trusted impl) under the dtype's
     tolerance; bf16 inputs are compared against the fp32 reference run
     (matching the reference's bf16 convert-and-compare convention);
  2. grad (fp32): analytic grad from the eager vjp tape vs central-difference
     numeric grad of the op itself;
  3. grad (bf16): analytic bf16 grad vs analytic fp32 grad under the loose
     bf16 tolerance (numeric differencing is meaningless at bf16 eps —
     the reference likewise compares bf16 grads against an fp32 anchor).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.tensor.tensor import Tensor

# tolerance table (reference op_test keeps per-dtype defaults; bf16 has
# ~3 mantissa digits → 2% relative)
TOLERANCES: Dict[str, Dict[str, float]] = {
    "float32": {"rtol": 2e-5, "atol": 1e-6},
    "bfloat16": {"rtol": 2e-2, "atol": 2e-2},
    "float16": {"rtol": 1e-3, "atol": 1e-3},
}

GRAD_TOLERANCES: Dict[str, Dict[str, float]] = {
    "float32": {"rtol": 5e-3, "atol": 1e-4},   # vs numeric differencing
    "bfloat16": {"rtol": 4e-2, "atol": 4e-2},  # vs fp32 analytic anchor
}


def sum_order_atol(ref: np.ndarray, k: int) -> float:
    """How far two float32 evaluations of the same ``k``-term dots may
    differ when they add the products in different orders: each of the
    ``k`` additions rounds by at most half an ulp of the running sum."""
    return k * np.finfo(np.float32).eps * float(np.abs(ref).max())


def _run_op(op: Callable, arrays: Sequence[np.ndarray], dtype: str,
            stop_gradient: bool = True):
    tensors = [paddle.to_tensor(a.astype(np.float32)).astype(dtype)
               if a.dtype.kind == "f" else paddle.to_tensor(a)
               for a in arrays]
    for t in tensors:
        t.stop_gradient = stop_gradient
    out = op(*tensors)
    return out, tensors


def _analytic_grads(op: Callable, arrays: Sequence[np.ndarray], dtype: str,
                    grad_indices: Sequence[int]) -> list:
    out, tensors = _run_op(op, arrays, dtype, stop_gradient=False)
    # scalarize with a fixed cotangent pattern so every output element
    # contributes distinctly (reference uses a user loss; cos pattern avoids
    # symmetric cancellation)
    w = np.cos(np.arange(int(np.prod(out.shape)) or 1, dtype=np.float32))
    wt = paddle.to_tensor(w.reshape(out.shape if out.shape else (1,))).astype(out.dtype)
    loss = (out * wt).sum() if out.shape else out * wt.reshape([])
    loss.backward()
    grads = []
    for i in grad_indices:
        g = tensors[i].grad
        assert g is not None, f"no grad reached input {i}"
        grads.append(np.asarray(g.astype("float32").numpy()))
    return grads


def _numeric_grads(op: Callable, arrays: Sequence[np.ndarray],
                   grad_indices: Sequence[int], eps: float = 1e-3) -> list:
    """Central differences of sum(op * w) in fp32 (reference delta=0.005)."""

    def scalar(arrs):
        out, _ = _run_op(op, arrs, "float32")
        o = np.asarray(out.numpy(), dtype=np.float32)
        w = np.cos(np.arange(o.size or 1, dtype=np.float32)).reshape(o.shape or (1,))
        return float((o * w).sum())

    grads = []
    for i in grad_indices:
        base = arrays[i]
        g = np.zeros_like(base, dtype=np.float32)
        flat = base.reshape(-1)
        gf = g.reshape(-1)
        for j in range(flat.size):
            orig = flat[j]
            step = eps * max(1.0, abs(float(orig)))
            flat[j] = orig + step
            up = scalar(arrays)
            flat[j] = orig - step
            down = scalar(arrays)
            flat[j] = orig
            gf[j] = (up - down) / (2 * step)
        grads.append(g)
    return grads


def check_op(name: str, op: Callable, ref: Optional[Callable],
             inputs: Sequence[np.ndarray], dtypes: Sequence[str] = ("float32", "bfloat16"),
             grad: bool = True, grad_indices: Optional[Sequence[int]] = None,
             tol: Optional[Dict[str, Dict[str, float]]] = None,
             grad_tol: Optional[Dict[str, Dict[str, float]]] = None,
             numeric_eps: float = 1e-3) -> None:
    """Full per-op numerics check; raises AssertionError with context on any
    mismatch. ``inputs`` are float32/int numpy arrays (float ones are cast
    per dtype). ``ref(*np_arrays) -> np_array`` is the trusted forward."""
    tol = {**TOLERANCES, **(tol or {})}
    grad_tol = {**GRAD_TOLERANCES, **(grad_tol or {})}
    inputs = [np.asarray(a) for a in inputs]
    if grad_indices is None:
        grad_indices = [i for i, a in enumerate(inputs) if a.dtype.kind == "f"]

    # -- forward, per dtype -------------------------------------------------
    ref_out = None
    if ref is not None:
        ref_out = np.asarray(ref(*inputs), dtype=np.float32)
    else:
        out32, _ = _run_op(op, inputs, "float32")
        ref_out = np.asarray(out32.numpy(), dtype=np.float32)
    for dt in dtypes:
        out, _ = _run_op(op, inputs, dt)
        got = np.asarray(out.astype("float32").numpy())
        t = tol[dt]
        np.testing.assert_allclose(
            got, ref_out, rtol=t["rtol"], atol=t["atol"],
            err_msg=f"[{name}] forward mismatch at dtype={dt}")

    # -- gradients ----------------------------------------------------------
    if not grad or not grad_indices:
        return
    analytic32 = _analytic_grads(op, inputs, "float32", grad_indices)
    numeric32 = _numeric_grads(op, inputs, grad_indices, eps=numeric_eps)
    t = grad_tol["float32"]
    for i, (a, n) in enumerate(zip(analytic32, numeric32)):
        np.testing.assert_allclose(
            a, n, rtol=t["rtol"], atol=t["atol"],
            err_msg=f"[{name}] analytic-vs-numeric grad mismatch, input {grad_indices[i]}")
    if "bfloat16" in dtypes:
        analytic_bf = _analytic_grads(op, inputs, "bfloat16", grad_indices)
        t = grad_tol["bfloat16"]
        for i, (a, b) in enumerate(zip(analytic32, analytic_bf)):
            np.testing.assert_allclose(
                b, a, rtol=t["rtol"], atol=t["atol"],
                err_msg=f"[{name}] bf16 grad vs fp32 anchor mismatch, "
                        f"input {grad_indices[i]}")
