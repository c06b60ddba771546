"""OpTest-grade numerics sweep over the hottest ops (reference
`test/legacy_test/op_test.py:420` check_output / `:2973` check_grad; SURVEY
§7 hard-part #6). Each entry: forward vs trusted numpy reference at
fp32+bf16, analytic-vs-numeric grad at fp32, bf16 grad vs fp32 anchor.

ISSUE 13 widened the table past 100 ops so the speculative-verify and
int8-KV dequant paths land against derivable references, and moved all
per-op exemptions into WHITE_LIST (reference keeps the same split in
`test/white_list/op_accuracy_white_list.py`): the default tolerance table
is the contract; any op deviating from it must be listed with a reason."""

import numpy as np
import pytest
from scipy.special import erf as sp_erf, erfinv as sp_erfinv, \
    gammaln as sp_gammaln, psi as sp_psi

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.serving import dequantize_kv, quantize_kv
from op_test import check_op


def rand(*shape, lo=-1.0, hi=1.0, seed=0):
    rng = np.random.default_rng(seed + sum(shape))
    return (lo + (hi - lo) * rng.random(shape)).astype(np.float32)


def pos(*shape, seed=0):
    return rand(*shape, lo=0.3, hi=2.0, seed=seed)


def away_from_zero(*shape, seed=0):
    x = rand(*shape, seed=seed)
    return (np.sign(x) * (np.abs(x) + 0.2)).astype(np.float32)


def off_grid(*shape, seed=0):
    """Integers + (0.2, 0.8) fraction: keeps floor/trunc/mod numeric grads
    away from the jump discontinuities at integer boundaries."""
    rng = np.random.default_rng(seed + sum(shape))
    return (rng.integers(-2, 3, shape) + 0.2 + 0.6 * rng.random(shape)
            ).astype(np.float32)


def sep_pair(seed=0):
    """(a, b) with |a-b| >= 0.2 everywhere: comparison outputs can't flip
    when the operands are rounded to bf16."""
    a = rand(4, 8, seed=seed)
    return a, (a + away_from_zero(4, 8, seed=seed + 1)).astype(np.float32)


def eq_pair(seed=0):
    """(a, b) exactly equal on a fixed mask, separated by 0.5 elsewhere —
    equality survives the bf16 round-trip on both branches."""
    a = rand(4, 8, seed=seed)
    mask = np.arange(32).reshape(4, 8) % 3 == 0
    return a, np.where(mask, a, a + 0.5).astype(np.float32)


def spd(n=4, seed=0):
    a = rand(n, n, seed=seed)
    return (a @ a.T + n * np.eye(n, dtype=np.float32)).astype(np.float32)


def np_softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


def np_gelu(x):
    return 0.5 * x * (1.0 + sp_erf(x / np.sqrt(2.0)))


def np_layer_norm(x, w, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * w + b


def np_rms_norm(x, w, eps=1e-6):
    ms = np.mean(np.square(x), -1, keepdims=True)
    return x / np.sqrt(ms + eps) * w


def np_group_norm(x, w, b, groups=2, eps=1e-5):
    n, c, h, wd = x.shape
    g = x.reshape(n, groups, c // groups, h, wd)
    mu = g.mean((2, 3, 4), keepdims=True)
    var = g.var((2, 3, 4), keepdims=True)
    out = ((g - mu) / np.sqrt(var + eps)).reshape(x.shape)
    return out * w[None, :, None, None] + b[None, :, None, None]


def np_sdpa(q, k, v):
    d = q.shape[-1]
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    p = np_softmax(logits, -1)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


def np_conv2d(x, w):
    n, cin, h, wd = x.shape
    cout, _, kh, kw = w.shape
    out = np.zeros((n, cout, h - kh + 1, wd - kw + 1), np.float32)
    for i in range(out.shape[2]):
        for j in range(out.shape[3]):
            patch = x[:, :, i:i + kh, j:j + kw]
            out[:, :, i, j] = np.tensordot(patch, w, axes=([1, 2, 3], [1, 2, 3]))
    return out


def np_conv1d(x, w):
    n, cin, ln = x.shape
    cout, _, kw = w.shape
    out = np.zeros((n, cout, ln - kw + 1), np.float32)
    for j in range(out.shape[2]):
        out[:, :, j] = np.tensordot(x[:, :, j:j + kw], w, axes=([1, 2], [1, 2]))
    return out


def np_pool2d(x, k, reduce):
    n, c, h, w = x.shape
    return reduce(x.reshape(n, c, h // k, k, w // k, k), (3, 5))


def np_cross_entropy(logits, label):
    ls = logits - logits.max(-1, keepdims=True)
    lse = np.log(np.exp(ls).sum(-1)) - ls[np.arange(len(label)), label]
    return lse.mean()


def np_kv_roundtrip(x):
    """Derivable reference for the int8 KV page round-trip (serving.kv_quant:
    symmetric per-token absmax over the trailing feature axis)."""
    s = np.maximum(np.abs(x).max(-1, keepdims=True) / 127.0, 1e-8)
    q = np.clip(np.rint(x / s), -127, 127)
    return (q * s).astype(np.float32)


def np_kv_scale(x):
    return np.maximum(np.abs(x).max(-1) / 127.0, 1e-8).astype(np.float32)


def _kv_roundtrip_op(x):
    q, s = quantize_kv(paddle.unwrap(x))
    return paddle.wrap(dequantize_kv(q, s))


def _kv_scale_op(x):
    return paddle.wrap(quantize_kv(paddle.unwrap(x))[1])


def _kv_dequant_op(q, s):
    return paddle.wrap(dequantize_kv(paddle.unwrap(q.astype("float32")).astype("int8"),
                                     paddle.unwrap(s)))


# Per-op exemption table (reference: `test/white_list/op_accuracy_white_list.py`
# — ops that may deviate from the default tolerance/grad contract must be
# listed HERE, each with a reason; OP_TABLE itself stays exemption-free).
# Values are check_op kwarg overrides merged over the table entry's kwargs.
WHITE_LIST = {
    # subgradient choice at ties / piecewise-constant forward: no numeric grad
    "max": {"grad": False},
    "min": {"grad": False},
    "amax": {"grad": False},
    "amin": {"grad": False},
    "cummax": {"grad": False},
    "median": {"grad": False},
    "quantile": {"grad": False},
    "floor": {"grad": False},
    "ceil": {"grad": False},
    "round": {"grad": False},
    "trunc": {"grad": False},
    "sign": {"grad": False},
    "heaviside": {"grad": False},
    "mod": {"grad": False},          # jump at multiples of the divisor
    "copysign": {"grad": False},     # sign transfer is piecewise-constant
    "nextafter": {"grad": False},    # ulp step, not differentiable
    "argsort": {"grad": False},      # integer output
    "searchsorted": {"grad": False},
    # loss terms with O(eps^2) curvature at the sampled points: central
    # differencing needs a larger step to stay above fp32 noise
    "softmax_ce": {"numeric_eps": 5e-3},
    "lgamma": {"numeric_eps": 5e-3},  # steep slope near 0: fp32 diff noise
    "digamma": {"numeric_eps": 5e-3},
    "rad2deg": {"numeric_eps": 5e-3},     # 57.3x slope amplifies fp32 noise
    "log_softmax": {"numeric_eps": 5e-3},  # pre-existing marginal failure at
    # the default eps (0.98% vs 0.5%): logsumexp curvature + fp32 diff noise
    # mod wraps at multiples of the divisor: bf16 rounding of the operands
    # crosses the discontinuity (|error| = divisor), so fp32 forward only
    "mod": {"grad": False, "dtypes": ("float32",)},
    "masked_select": {"grad": False},  # boolean gather exits the vjp tape
    "sdpa": {"numeric_eps": 5e-3},
    "conv2d": {"numeric_eps": 5e-3},
    "conv1d": {"numeric_eps": 5e-3},
    "bce": {"grad_indices": [0]},    # 0/1 labels sit AT the log boundary
    "bce_logits": {"grad_indices": [0]},
    "group_norm": {"numeric_eps": 5e-3},
    # the op is exact (forward bit-equal to numpy; analytic grad within 2e-7
    # of the closed form, the reversed cumsum of the cotangent).  The loose
    # side is the float32 central difference: the prefix sums add up to a
    # scalar of magnitude 8.2, whose float32 spacing 9.5e-7 over a 2e-3
    # step is 4.8e-4 of noise an evaluation.  cumsum is linear, so a larger
    # step has no truncation error and divides that noise by a hundred
    "cumsum": {"numeric_eps": 0.1},
    # decompositions/solves: analytic grads route through the factorization
    # (numeric differencing of the factor is ill-conditioned) and XLA's
    # linalg kernels are fp32-only — forward-only at fp32
    "cholesky": {"grad": False, "dtypes": ("float32",)},
    "solve": {"grad": False, "dtypes": ("float32",)},
    "inv": {"grad": False, "dtypes": ("float32",)},
    "det": {"grad": False},
    "matrix_power": {"grad": False},
    # int8 KV round-trip: rint() is piecewise-constant; bf16 inputs can land
    # one quantization bucket over, error bounded by one scale step (~1/127)
    "kv_quant_roundtrip": {"grad": False,
                           "tol": {"float32": {"rtol": 1e-5, "atol": 1e-5},
                                   "bfloat16": {"rtol": 5e-2, "atol": 2e-2}}},
    "kv_quant_scale": {"grad": False},
    "kv_dequant": {"grad": False},
    # comparison / logical / predicate family: boolean outputs, forward-only
    **{n: {"grad": False} for n in
       ("greater_than", "less_than", "greater_equal", "less_equal",
        "equal", "not_equal", "isfinite", "isnan", "argmax", "argmin",
        "count_nonzero", "bucketize", "one_hot")},
}

# (name, op, trusted_ref, inputs, kwargs-for-check_op)
OP_TABLE = [
    # elementwise
    ("tanh", lambda x: paddle.tanh(x), np.tanh, [rand(4, 8)], {}),
    ("sigmoid", lambda x: F.sigmoid(x), lambda x: 1 / (1 + np.exp(-x)), [rand(4, 8)], {}),
    ("exp", lambda x: paddle.exp(x), np.exp, [rand(4, 8)], {}),
    ("expm1", lambda x: paddle.expm1(x), np.expm1, [rand(4, 8)], {}),
    ("log", lambda x: paddle.log(x), np.log, [pos(4, 8)], {}),
    ("log1p", lambda x: paddle.log1p(x), np.log1p, [pos(4, 8)], {}),
    ("log2", lambda x: paddle.log2(x), np.log2, [pos(4, 8)], {}),
    ("log10", lambda x: paddle.log10(x), np.log10, [pos(4, 8)], {}),
    ("sqrt", lambda x: paddle.sqrt(x), np.sqrt, [pos(4, 8)], {}),
    ("rsqrt", lambda x: paddle.rsqrt(x), lambda x: 1 / np.sqrt(x), [pos(4, 8)], {}),
    ("reciprocal", lambda x: paddle.reciprocal(x), lambda x: 1 / x,
     [away_from_zero(4, 8)], {}),
    ("erf", lambda x: paddle.erf(x), sp_erf, [rand(4, 8)], {}),
    ("erfinv", lambda x: paddle.erfinv(x), sp_erfinv,
     [rand(4, 8, lo=-0.9, hi=0.9)], {}),
    ("lgamma", lambda x: paddle.lgamma(x), sp_gammaln, [pos(4, 8)], {}),
    ("digamma", lambda x: paddle.digamma(x), sp_psi, [pos(4, 8)], {}),
    ("square", lambda x: paddle.square(x), np.square, [rand(4, 8)], {}),
    ("pow3", lambda x: paddle.pow(x, 3), lambda x: x ** 3, [rand(4, 8)], {}),
    ("pow_tensor", lambda a, b: paddle.pow(a, b), np.power,
     [pos(4, 8), rand(4, 8, seed=1)], {}),
    ("abs", lambda x: paddle.abs(x), np.abs, [away_from_zero(4, 8)], {}),
    ("neg", lambda x: paddle.neg(x), np.negative, [rand(4, 8)], {}),
    ("sin", lambda x: paddle.sin(x), np.sin, [rand(4, 8)], {}),
    ("cos", lambda x: paddle.cos(x), np.cos, [rand(4, 8)], {}),
    ("tan", lambda x: paddle.tan(x), np.tan, [rand(4, 8)], {}),
    ("asin", lambda x: paddle.asin(x), np.arcsin, [rand(4, 8, lo=-0.9, hi=0.9)], {}),
    ("acos", lambda x: paddle.acos(x), np.arccos, [rand(4, 8, lo=-0.9, hi=0.9)], {}),
    ("atan", lambda x: paddle.atan(x), np.arctan, [rand(4, 8)], {}),
    ("sinh", lambda x: paddle.sinh(x), np.sinh, [rand(4, 8)], {}),
    ("cosh", lambda x: paddle.cosh(x), np.cosh, [rand(4, 8)], {}),
    ("asinh", lambda x: paddle.asinh(x), np.arcsinh, [rand(4, 8)], {}),
    ("acosh", lambda x: paddle.acosh(x), np.arccosh,
     [(pos(4, 8) + 1.0).astype(np.float32)], {}),
    ("atanh", lambda x: paddle.atanh(x), np.arctanh, [rand(4, 8, lo=-0.9, hi=0.9)], {}),
    ("floor", lambda x: paddle.floor(x), np.floor, [off_grid(4, 8)], {}),
    ("ceil", lambda x: paddle.ceil(x), np.ceil, [off_grid(4, 8)], {}),
    ("round", lambda x: paddle.round(x), np.round, [off_grid(4, 8)], {}),
    ("trunc", lambda x: paddle.trunc(x), np.trunc, [off_grid(4, 8)], {}),
    ("frac", lambda x: paddle.frac(x), lambda x: x - np.trunc(x),
     [off_grid(4, 8)], {}),
    ("sign", lambda x: paddle.sign(x), np.sign, [away_from_zero(4, 8)], {}),
    ("logit", lambda x: paddle.logit(x), lambda p: np.log(p / (1 - p)),
     [rand(4, 8, lo=0.1, hi=0.9)], {}),
    ("deg2rad", lambda x: paddle.deg2rad(x), np.deg2rad, [rand(4, 8, lo=-90, hi=90)], {}),
    ("rad2deg", lambda x: paddle.rad2deg(x), np.rad2deg, [rand(4, 8)], {}),
    ("clip", lambda x: paddle.clip(x, -0.5, 0.5),
     lambda x: np.clip(x, -0.5, 0.5), [rand(4, 8)], {}),
    ("nan_to_num", lambda x: paddle.nan_to_num(x), lambda x: x, [rand(4, 8)], {}),
    ("add", lambda a, b: a + b, np.add, [rand(4, 8), rand(4, 8, seed=1)], {}),
    ("sub", lambda a, b: a - b, np.subtract, [rand(4, 8), rand(4, 8, seed=1)], {}),
    ("mul", lambda a, b: a * b, np.multiply, [rand(4, 8), rand(4, 8, seed=1)], {}),
    ("div", lambda a, b: a / b, np.divide, [rand(4, 8), pos(4, 8, seed=1)], {}),
    ("mod", lambda a, b: paddle.mod(a, b), np.mod, [pos(4, 8), pos(4, 8, seed=1)], {}),
    ("maximum", lambda a, b: paddle.maximum(a, b), np.maximum,
     [rand(4, 8), rand(4, 8, seed=9)], {}),
    ("minimum", lambda a, b: paddle.minimum(a, b), np.minimum,
     [rand(4, 8), rand(4, 8, seed=9)], {}),
    ("fmax", lambda a, b: paddle.fmax(a, b), np.fmax,
     [rand(4, 8), rand(4, 8, seed=9)], {}),
    ("fmin", lambda a, b: paddle.fmin(a, b), np.fmin,
     [rand(4, 8), rand(4, 8, seed=9)], {}),
    ("atan2", lambda a, b: paddle.atan2(a, b), np.arctan2,
     [rand(4, 8), pos(4, 8, seed=1)], {}),
    ("hypot", lambda a, b: paddle.hypot(a, b), np.hypot,
     [away_from_zero(4, 8), away_from_zero(4, 8, seed=1)], {}),
    ("logaddexp", lambda a, b: paddle.logaddexp(a, b), np.logaddexp,
     [rand(4, 8), rand(4, 8, seed=1)], {}),
    ("heaviside", lambda a, b: paddle.heaviside(a, b), np.heaviside,
     [away_from_zero(4, 8), rand(4, 8, seed=1)], {}),
    ("copysign", lambda a, b: paddle.copysign(a, b), np.copysign,
     [pos(4, 8), away_from_zero(4, 8, seed=1)], {}),
    ("nextafter", lambda a, b: paddle.nextafter(a, b), np.nextafter,
     [rand(4, 8), rand(4, 8, seed=1)], {}),
    ("lerp", lambda a, b: paddle.lerp(a, b, 0.3), lambda a, b: a + 0.3 * (b - a),
     [rand(4, 8), rand(4, 8, seed=1)], {}),
    ("scale", lambda x: paddle.scale(x, scale=2.0, bias=1.0),
     lambda x: 2.0 * x + 1.0, [rand(4, 8)], {}),
    # comparisons / predicates (bf16 forward safe: operands separated or
    # exactly equal by construction — see sep_pair/eq_pair)
    ("greater_than", lambda a, b: paddle.greater_than(a, b), np.greater,
     list(sep_pair()), {}),
    ("less_than", lambda a, b: paddle.less_than(a, b), np.less,
     list(sep_pair(seed=3)), {}),
    ("greater_equal", lambda a, b: paddle.greater_equal(a, b), np.greater_equal,
     list(eq_pair()), {}),
    ("less_equal", lambda a, b: paddle.less_equal(a, b), np.less_equal,
     list(eq_pair(seed=3)), {}),
    ("equal", lambda a, b: paddle.equal(a, b), np.equal, list(eq_pair()), {}),
    ("not_equal", lambda a, b: paddle.not_equal(a, b), np.not_equal,
     list(eq_pair()), {}),
    ("isfinite", lambda x: paddle.isfinite(x), np.isfinite, [rand(4, 8)], {}),
    ("isnan", lambda x: paddle.isnan(x), np.isnan, [rand(4, 8)], {}),
    ("logical_and", lambda a, b: paddle.logical_and(a, b), np.logical_and,
     [np.arange(12) % 2 == 0, np.arange(12) % 3 == 0], {}),
    ("logical_or", lambda a, b: paddle.logical_or(a, b), np.logical_or,
     [np.arange(12) % 2 == 0, np.arange(12) % 3 == 0], {}),
    ("logical_xor", lambda a, b: paddle.logical_xor(a, b), np.logical_xor,
     [np.arange(12) % 2 == 0, np.arange(12) % 3 == 0], {}),
    ("logical_not", lambda x: paddle.logical_not(x), np.logical_not,
     [np.arange(12) % 2 == 0], {}),
    ("where", lambda c, a, b: paddle.where(c, a, b), np.where,
     [np.arange(32).reshape(4, 8) % 2 == 0, rand(4, 8), rand(4, 8, seed=1)], {}),
    # activations
    ("relu", lambda x: F.relu(x), lambda x: np.maximum(x, 0), [away_from_zero(4, 8)], {}),
    ("relu6", lambda x: F.relu6(x), lambda x: np.minimum(np.maximum(x, 0), 6),
     [away_from_zero(4, 8)], {}),
    ("leaky_relu", lambda x: F.leaky_relu(x), lambda x: np.where(x > 0, x, 0.01 * x),
     [away_from_zero(4, 8)], {}),
    ("elu", lambda x: F.elu(x), lambda x: np.where(x > 0, x, np.expm1(x)),
     [away_from_zero(4, 8)], {}),
    ("celu", lambda x: F.celu(x), lambda x: np.where(x > 0, x, np.expm1(x)),
     [away_from_zero(4, 8)], {}),
    ("selu", lambda x: F.selu(x),
     lambda x: 1.0507009873554805 * np.where(
         x > 0, x, 1.6732632423543772 * np.expm1(x)),
     [away_from_zero(4, 8)], {}),
    ("prelu", lambda x, w: F.prelu(x, w),
     lambda x, w: np.where(x > 0, x, w * x),
     [away_from_zero(4, 8), np.array([0.25], np.float32)], {}),
    ("gelu", lambda x: F.gelu(x), np_gelu, [rand(4, 8)], {}),
    ("silu", lambda x: F.silu(x), lambda x: x / (1 + np.exp(-x)), [rand(4, 8)], {}),
    ("mish", lambda x: F.mish(x), lambda x: x * np.tanh(np.log1p(np.exp(x))),
     [rand(4, 8)], {}),
    ("hardsigmoid", lambda x: F.hardsigmoid(x),
     lambda x: np.clip(x / 6 + 0.5, 0, 1), [rand(4, 8)], {}),
    ("hardswish", lambda x: F.hardswish(x),
     lambda x: x * np.clip(x + 3, 0, 6) / 6, [rand(4, 8)], {}),
    ("hardtanh", lambda x: F.hardtanh(x), lambda x: np.clip(x, -1, 1),
     [rand(4, 8, lo=-0.8, hi=0.8)], {}),
    ("log_sigmoid", lambda x: F.log_sigmoid(x),
     lambda x: -np.log1p(np.exp(-x)), [rand(4, 8)], {}),
    ("softplus", lambda x: F.softplus(x), lambda x: np.log1p(np.exp(x)),
     [rand(4, 8)], {}),
    ("softsign", lambda x: F.softsign(x), lambda x: x / (1 + np.abs(x)),
     [away_from_zero(4, 8)], {}),
    ("tanhshrink", lambda x: F.tanhshrink(x), lambda x: x - np.tanh(x),
     [rand(4, 8)], {}),
    ("softshrink", lambda x: F.softshrink(x),
     lambda x: np.sign(x) * (np.abs(x) - 0.5),
     [(np.sign(rand(4, 8)) * (0.7 + 0.4 * np.abs(rand(4, 8, seed=1)))
       ).astype(np.float32)], {}),
    ("hardshrink", lambda x: F.hardshrink(x), lambda x: x,
     [(np.sign(rand(4, 8)) * (0.7 + 0.4 * np.abs(rand(4, 8, seed=1)))
       ).astype(np.float32)], {}),
    ("softmax", lambda x: F.softmax(x), np_softmax, [rand(4, 8)], {}),
    ("log_softmax", lambda x: F.log_softmax(x), lambda x: np.log(np_softmax(x)),
     [rand(4, 8)], {}),
    ("swiglu", lambda x: F.swiglu(x),
     lambda x: (lambda a, b: a / (1 + np.exp(-a)) * b)(x[..., :4], x[..., 4:]),
     [rand(3, 8)], {}),
    ("glu", lambda x: F.glu(x),
     lambda x: x[..., :4] / (1 + np.exp(-x[..., 4:])), [rand(3, 8)], {}),
    ("normalize", lambda x: F.normalize(x),
     lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True)).clip(1e-12),
     [rand(4, 8)], {}),
    ("cosine_similarity", lambda a, b: F.cosine_similarity(a, b),
     lambda a, b: (a * b).sum(-1) / (np.linalg.norm(a, axis=-1)
                                     * np.linalg.norm(b, axis=-1)),
     [rand(4, 8), rand(4, 8, seed=1)], {}),
    # reductions
    ("sum", lambda x: paddle.sum(x, axis=-1), lambda x: x.sum(-1), [rand(4, 8)], {}),
    ("mean", lambda x: paddle.mean(x, axis=0), lambda x: x.mean(0), [rand(4, 8)], {}),
    ("prod", lambda x: paddle.prod(x, axis=-1), lambda x: x.prod(-1), [pos(4, 8)], {}),
    ("std", lambda x: paddle.std(x, axis=-1), lambda x: x.std(-1, ddof=1),
     [rand(4, 8)], {}),
    ("var", lambda x: paddle.var(x, axis=-1), lambda x: x.var(-1, ddof=1),
     [rand(4, 8)], {}),
    ("logsumexp", lambda x: paddle.logsumexp(x, axis=-1),
     lambda x: np.log(np.exp(x).sum(-1)), [rand(4, 8)], {}),
    ("max", lambda x: paddle.max(x, axis=-1), lambda x: x.max(-1), [rand(4, 8)], {}),
    ("min", lambda x: paddle.min(x, axis=-1), lambda x: x.min(-1), [rand(4, 8)], {}),
    ("amax", lambda x: paddle.amax(x, axis=-1), lambda x: x.max(-1), [rand(4, 8)], {}),
    ("amin", lambda x: paddle.amin(x, axis=-1), lambda x: x.min(-1), [rand(4, 8)], {}),
    ("median", lambda x: paddle.median(x, axis=-1), lambda x: np.median(x, -1),
     [rand(4, 8)], {}),
    ("quantile", lambda x: paddle.quantile(x, 0.5, axis=-1),
     lambda x: np.quantile(x, 0.5, axis=-1), [rand(4, 8)], {}),
    ("nansum", lambda x: paddle.nansum(x, axis=-1), lambda x: x.sum(-1),
     [rand(4, 8)], {}),
    ("nanmean", lambda x: paddle.nanmean(x, axis=-1), lambda x: x.mean(-1),
     [rand(4, 8)], {}),
    ("count_nonzero", lambda x: paddle.count_nonzero(x, axis=-1),
     lambda x: (x != 0).sum(-1), [away_from_zero(4, 8)], {}),
    ("argmax", lambda x: paddle.argmax(x, axis=-1), lambda x: x.argmax(-1),
     [rand(4, 8)], {}),
    ("argmin", lambda x: paddle.argmin(x, axis=-1), lambda x: x.argmin(-1),
     [rand(4, 8)], {}),
    ("cumsum", lambda x: paddle.cumsum(x, axis=-1), lambda x: x.cumsum(-1),
     [rand(4, 8)], {}),
    ("cumprod", lambda x: paddle.cumprod(x, dim=-1), lambda x: x.cumprod(-1),
     [pos(4, 8)], {}),
    ("cummax", lambda x: paddle.cummax(x, axis=-1)[0],
     lambda x: np.maximum.accumulate(x, -1), [rand(4, 8)], {}),
    ("sort", lambda x: paddle.sort(x, axis=-1), lambda x: np.sort(x, -1),
     [rand(4, 8)], {}),
    ("argsort", lambda x: paddle.argsort(x, axis=-1), lambda x: np.argsort(x, -1),
     [rand(4, 8)], {}),
    ("topk", lambda x: paddle.topk(x, 3)[0],
     lambda x: np.sort(x, -1)[..., ::-1][..., :3], [rand(4, 8)], {}),
    ("norm_fro", lambda x: paddle.norm(x), lambda x: np.sqrt((x * x).sum()),
     [rand(4, 8)], {}),
    ("vector_norm", lambda x: paddle.vector_norm(x, axis=-1),
     lambda x: np.linalg.norm(x, axis=-1), [rand(4, 8)], {}),
    # linalg / manipulation
    ("matmul", lambda a, b: paddle.matmul(a, b), np.matmul,
     [rand(4, 6), rand(6, 5, seed=1)], {}),
    ("bmm", lambda a, b: paddle.bmm(a, b), np.matmul,
     [rand(2, 3, 4), rand(2, 4, 5, seed=1)], {}),
    ("dot", lambda a, b: paddle.dot(a, b), np.dot, [rand(8), rand(8, seed=1)], {}),
    ("outer", lambda a, b: paddle.outer(a, b), np.outer,
     [rand(4), rand(6, seed=1)], {}),
    ("einsum_ij_kj", lambda a, b: paddle.einsum("ij,kj->ik", a, b),
     lambda a, b: a @ b.T, [rand(4, 6), rand(5, 6, seed=1)], {}),
    ("tensordot", lambda a, b: paddle.tensordot(a, b, axes=1), lambda a, b: a @ b,
     [rand(4, 6), rand(6, 5, seed=1)], {}),
    ("addmm", lambda c, a, b: paddle.addmm(c, a, b), lambda c, a, b: c + a @ b,
     [rand(4, 5), rand(4, 6, seed=1), rand(6, 5, seed=2)], {}),
    ("kron", lambda a, b: paddle.kron(a, b), np.kron,
     [rand(2, 3), rand(3, 2, seed=1)], {}),
    ("trace", lambda x: paddle.trace(x), np.trace, [rand(5, 5)], {}),
    ("tril", lambda x: paddle.tril(x), np.tril, [rand(4, 4)], {}),
    ("triu", lambda x: paddle.triu(x), np.triu, [rand(4, 4)], {}),
    ("diag", lambda x: paddle.diag(x), np.diag, [rand(5)], {}),
    ("diagonal", lambda x: paddle.diagonal(x), lambda x: np.diagonal(x),
     [rand(4, 4)], {}),
    ("linear", lambda x, w, b: F.linear(x, w, b),
     lambda x, w, b: x @ w + b, [rand(3, 6), rand(6, 4, seed=1), rand(4, seed=2)], {}),
    ("cholesky", lambda x: paddle.cholesky(x), np.linalg.cholesky, [spd()], {}),
    ("solve", lambda a, b: paddle.solve(a, b), np.linalg.solve,
     [spd(), rand(4, 2, seed=1)], {}),
    ("inv", lambda x: paddle.inv(x), np.linalg.inv, [spd()], {}),
    ("det", lambda x: paddle.det(x), np.linalg.det, [spd(3)], {}),
    ("matrix_power", lambda x: paddle.matrix_power(x, 2), lambda x: x @ x,
     [rand(4, 4)], {}),
    ("transpose", lambda x: paddle.transpose(x, [1, 0]), lambda x: x.T, [rand(4, 6)], {}),
    ("swapaxes", lambda x: paddle.swapaxes(x, 0, 2),
     lambda x: np.swapaxes(x, 0, 2), [rand(2, 3, 4)], {}),
    ("reshape", lambda x: paddle.reshape(x, [8, 4]), lambda x: x.reshape(8, 4),
     [rand(4, 8)], {}),
    ("flatten", lambda x: paddle.flatten(x), lambda x: x.reshape(-1),
     [rand(2, 3, 4)], {}),
    ("squeeze", lambda x: paddle.squeeze(x, axis=1), lambda x: x[:, 0],
     [rand(4, 1, 8)], {}),
    ("unsqueeze", lambda x: paddle.unsqueeze(x, axis=1),
     lambda x: x[:, None], [rand(4, 8)], {}),
    ("concat", lambda a, b: paddle.concat([a, b], axis=1),
     lambda a, b: np.concatenate([a, b], 1), [rand(4, 3), rand(4, 5, seed=1)], {}),
    ("stack", lambda a, b: paddle.stack([a, b], axis=0),
     lambda a, b: np.stack([a, b], 0), [rand(4, 8), rand(4, 8, seed=1)], {}),
    ("split0", lambda x: paddle.split(x, 2, axis=1)[0], lambda x: x[:, :4],
     [rand(4, 8)], {}),
    ("unbind0", lambda x: paddle.unbind(x, axis=0)[0], lambda x: x[0],
     [rand(3, 8)], {}),
    ("slice", lambda x: x[1:3, 2:6], lambda x: x[1:3, 2:6], [rand(4, 8)], {}),
    ("tile", lambda x: paddle.tile(x, [2, 1]), lambda x: np.tile(x, (2, 1)),
     [rand(4, 8)], {}),
    ("expand", lambda x: paddle.expand(x, [4, 8]),
     lambda x: np.broadcast_to(x, (4, 8)).copy(), [rand(1, 8)], {}),
    ("flip", lambda x: paddle.flip(x, axis=1), lambda x: x[:, ::-1].copy(),
     [rand(4, 8)], {}),
    ("roll", lambda x: paddle.roll(x, 2, axis=1), lambda x: np.roll(x, 2, 1),
     [rand(4, 8)], {}),
    ("rot90", lambda x: paddle.rot90(x), lambda x: np.rot90(x).copy(),
     [rand(4, 8)], {}),
    ("pad", lambda x: paddle.pad(x, [1, 2]),
     lambda x: np.pad(x, [(0, 0), (1, 2)]), [rand(4, 8)], {}),
    ("gather", lambda x, i: paddle.gather(x, i, axis=0), lambda x, i: x[i],
     [rand(4, 8), np.array([0, 2, 3])], {}),
    ("index_select", lambda x, i: paddle.index_select(x, i, axis=1),
     lambda x, i: x[:, i], [rand(4, 8), np.array([1, 5, 0])], {}),
    ("take_along_axis", lambda x, i: paddle.take_along_axis(x, i, axis=1),
     lambda x, i: np.take_along_axis(x, i, 1),
     [rand(4, 8), np.array([[0, 3], [1, 2], [7, 0], [4, 4]])], {}),
    ("repeat_interleave", lambda x: paddle.repeat_interleave(x, 2, axis=1),
     lambda x: np.repeat(x, 2, 1), [rand(4, 8)], {}),
    ("masked_fill", lambda x: paddle.masked_fill(
        x, paddle.to_tensor(np.arange(32).reshape(4, 8) % 2 == 0), 0.5),
     lambda x: np.where(np.arange(32).reshape(4, 8) % 2 == 0, 0.5, x),
     [rand(4, 8)], {}),
    ("masked_select", lambda x: paddle.masked_select(
        x, paddle.to_tensor(np.arange(32).reshape(4, 8) % 2 == 0)),
     lambda x: x[np.arange(32).reshape(4, 8) % 2 == 0], [rand(4, 8)], {}),
    ("bucketize", lambda x, edges: paddle.bucketize(x, edges),
     lambda x, edges: np.searchsorted(edges, x),
     [rand(4, 8), np.array([-0.5, 0.0, 0.5], np.float32)], {}),
    ("searchsorted", lambda edges, x: paddle.searchsorted(edges, x),
     lambda edges, x: np.searchsorted(edges, x),
     [np.array([-0.5, 0.0, 0.5], np.float32), rand(4, 8)], {}),
    ("one_hot", lambda i: F.one_hot(i, 6),
     lambda i: np.eye(6, dtype=np.float32)[i], [np.array([0, 4, 2, 5])], {}),
    # nn ops
    ("layer_norm", lambda x, w, b: F.layer_norm(x, [8], weight=w, bias=b),
     np_layer_norm, [rand(4, 8), pos(8, seed=1), rand(8, seed=2)], {}),
    ("rms_norm", lambda x, w: F.rms_norm(x, w), np_rms_norm,
     [rand(4, 8), pos(8, seed=1)], {}),
    ("group_norm", lambda x, w, b: F.group_norm(x, 2, weight=w, bias=b),
     np_group_norm, [rand(2, 4, 3, 3), pos(4, seed=1), rand(4, seed=2)], {}),
    ("embedding", lambda idx, w: F.embedding(idx, w), lambda idx, w: w[idx],
     [np.array([0, 2, 3, 1]), rand(5, 6)], {}),
    ("mse_loss", lambda a, b: F.mse_loss(a, b), lambda a, b: np.mean((a - b) ** 2),
     [rand(4, 8), rand(4, 8, seed=1)], {}),
    ("l1_loss", lambda a, b: F.l1_loss(a, b), lambda a, b: np.mean(np.abs(a - b)),
     list(sep_pair(seed=11)), {}),
    ("smooth_l1_loss", lambda a, b: F.smooth_l1_loss(a, b),
     lambda a, b: np.mean(np.where(np.abs(a - b) < 1.0,
                                   0.5 * (a - b) ** 2, np.abs(a - b) - 0.5)),
     list(sep_pair(seed=12)), {}),
    ("square_error_cost", lambda a, b: F.square_error_cost(a, b),
     lambda a, b: (a - b) ** 2, [rand(4, 8), rand(4, 8, seed=1)], {}),
    ("kl_div", lambda lp, t: F.kl_div(lp, t),
     lambda lp, t: np.mean(t * (np.log(t) - lp)),
     [np.log(np_softmax(rand(4, 8))), np_softmax(rand(4, 8, seed=1))], {}),
    ("bce", lambda p, t: F.binary_cross_entropy(p, t),
     lambda p, t: -np.mean(t * np.log(p) + (1 - t) * np.log(1 - p)),
     [rand(4, 8, lo=0.1, hi=0.9),
      (np.arange(32).reshape(4, 8) % 2).astype(np.float32)], {}),
    ("bce_logits", lambda x, t: F.binary_cross_entropy_with_logits(x, t),
     lambda x, t: np.mean(np.log1p(np.exp(-np.abs(x)))
                          + np.maximum(x, 0) - x * t),
     [rand(4, 8), (np.arange(32).reshape(4, 8) % 2).astype(np.float32)], {}),
    ("nll_loss", lambda lp, t: F.nll_loss(lp, t),
     lambda lp, t: -np.mean(lp[np.arange(len(t)), t]),
     [np.log(np_softmax(rand(4, 8))), np.array([1, 0, 7, 3])], {}),
    ("softmax_ce", lambda lg, lb: F.cross_entropy(lg, lb), np_cross_entropy,
     [rand(6, 10), np.array([0, 3, 9, 1, 4, 7])], {}),
    ("sdpa", lambda q, k, v: F.scaled_dot_product_attention(q, k, v), np_sdpa,
     [rand(1, 4, 2, 8), rand(1, 4, 2, 8, seed=1), rand(1, 4, 2, 8, seed=2)], {}),
    ("conv2d", lambda x, w: F.conv2d(x, w), np_conv2d,
     [rand(1, 2, 5, 5), rand(3, 2, 3, 3, seed=1)], {}),
    ("conv1d", lambda x, w: F.conv1d(x, w), np_conv1d,
     [rand(1, 2, 6), rand(3, 2, 3, seed=1)], {}),
    ("avg_pool2d", lambda x: F.avg_pool2d(x, 2),
     lambda x: np_pool2d(x, 2, np.mean), [rand(1, 2, 4, 4)], {}),
    ("max_pool2d", lambda x: F.max_pool2d(x, 2),
     lambda x: np_pool2d(x, 2, np.max), [rand(1, 2, 4, 4)], {}),
    ("adaptive_avg_pool2d", lambda x: F.adaptive_avg_pool2d(x, 1),
     lambda x: x.mean((2, 3), keepdims=True), [rand(1, 2, 4, 4)], {}),
    # int8 KV-cache quantization (ISSUE 13: the dequant math fused into the
    # decode kernel, checked against a derivable numpy reference)
    ("kv_quant_roundtrip", _kv_roundtrip_op, np_kv_roundtrip, [rand(4, 8)], {}),
    ("kv_quant_scale", _kv_scale_op, np_kv_scale, [rand(4, 8)], {}),
    ("kv_dequant", _kv_dequant_op,
     lambda q, s: q.astype(np.float32) * s[..., None],
     [np.arange(-16, 16).reshape(4, 8).astype(np.float32),
      pos(4, seed=1) / 100.0], {}),
]

assert len(OP_TABLE) >= 100, f"OP_TABLE shrank to {len(OP_TABLE)} (< 100)"
assert len({t[0] for t in OP_TABLE}) == len(OP_TABLE), "duplicate op names"
assert not (set(WHITE_LIST) - {t[0] for t in OP_TABLE}), \
    "WHITE_LIST names an op missing from OP_TABLE"


@pytest.mark.parametrize("name,op,ref,inputs,kw",
                         OP_TABLE, ids=[t[0] for t in OP_TABLE])
def test_op_numerics(name, op, ref, inputs, kw):
    check_op(name, op, ref, inputs, **{**kw, **WHITE_LIST.get(name, {})})


class TestHarnessSelfChecks:
    def test_catches_wrong_forward(self):
        with pytest.raises(AssertionError, match="forward mismatch"):
            check_op("bad_fwd", lambda x: paddle.tanh(x), np.sinh, [rand(3, 3)])

    def test_catches_wrong_grad(self):
        # op whose forward is fine vs ref but produces a wrong-by-construction
        # gradient: detach inside cuts the true path
        def bad(x):
            return paddle.tanh(x.detach()) + x * 0.0

        with pytest.raises(AssertionError, match="grad mismatch|no grad"):
            check_op("bad_grad", bad, np.tanh, [rand(3, 3)])

    def test_int_inputs_skip_grad(self):
        check_op("embedding_nograd", lambda i, w: F.embedding(i, w),
                 lambda i, w: w[i], [np.array([1, 0]), rand(3, 4)])
