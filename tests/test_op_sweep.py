"""OpTest sweep — the analog of the reference's OpTest harness
(/root/reference/test/legacy_test/op_test.py:418): every registered op gets
at least one case; forward is checked against a NumPy oracle where one
exists; differentiable ops are checked against central finite differences.

The completeness gate (test_every_op_has_a_case) fails whenever a new op
lands without a case here — enforcing SURVEY.md §4's "≥1 case per op".
"""
import math

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops.registry import OPS

rng = np.random.RandomState(1234)


def T(arr):
    return paddle.to_tensor(np.asarray(arr))


def P(shape, lo=-1.0, hi=1.0):
    return (rng.rand(*shape) * (hi - lo) + lo).astype(np.float32)


def PP(shape):  # strictly positive
    return (rng.rand(*shape) * 0.9 + 0.1).astype(np.float32)


def off_zero(arr, by=0.05):
    # a kink at 0 has no finite difference: |x| >= by, the sign kept, and as
    # many draws taken from ``rng`` as without it
    return np.where(np.abs(arr) < by, np.copysign(by, arr), arr).astype(
        np.float32)


def _np(x):
    if isinstance(x, Tensor):
        return np.asarray(x._value)
    if isinstance(x, (tuple, list)):
        return [_np(v) for v in x]
    return np.asarray(x)


def _sigmoid(v):
    return 1.0 / (1.0 + np.exp(-v))


# ---------------------------------------------------------------- case table
# op -> (args_fn, ref_fn | None, check_grad: bool)
# args_fn returns (args, kwargs); ref_fn gets the *numpy* args.

A = {}


def case(name, args_fn, ref=None, grad=True):
    A[name] = (args_fn, ref, grad)


# ---- smooth unary elementwise: (domain_fn, numpy_ref)
UNARY = {
    "abs": (lambda: P((3, 4), 0.2, 1.0), np.abs),
    "acos": (lambda: P((3, 4), -0.8, 0.8), np.arccos),
    "acosh": (lambda: P((3, 4), 1.2, 3.0), np.arccosh),
    "asin": (lambda: P((3, 4), -0.8, 0.8), np.arcsin),
    "asinh": (lambda: P((3, 4)), np.arcsinh),
    "atan": (lambda: P((3, 4)), np.arctan),
    "atanh": (lambda: P((3, 4), -0.8, 0.8), np.arctanh),
    "cos": (lambda: P((3, 4)), np.cos),
    "cosh": (lambda: P((3, 4)), np.cosh),
    "erf": (lambda: P((3, 4)), None),
    "erfinv": (lambda: P((3, 4), -0.7, 0.7), None),
    "exp": (lambda: P((3, 4)), np.exp),
    "expm1": (lambda: P((3, 4)), np.expm1),
    "log": (lambda: PP((3, 4)), np.log),
    "log10": (lambda: PP((3, 4)), np.log10),
    "log1p": (lambda: PP((3, 4)), np.log1p),
    "log2": (lambda: PP((3, 4)), np.log2),
    "negative": (lambda: P((3, 4)), np.negative),
    "reciprocal": (lambda: PP((3, 4)), np.reciprocal),
    "rsqrt": (lambda: PP((3, 4)), lambda v: 1 / np.sqrt(v)),
    "sigmoid": (lambda: P((3, 4)), _sigmoid),
    "sin": (lambda: P((3, 4)), np.sin),
    "sinh": (lambda: P((3, 4)), np.sinh),
    "sqrt": (lambda: PP((3, 4)), np.sqrt),
    "square": (lambda: P((3, 4)), np.square),
    "tan": (lambda: P((3, 4), -1.0, 1.0), np.tan),
    "tanh": (lambda: P((3, 4)), np.tanh),
    "log_sigmoid": (lambda: P((3, 4)), lambda v: np.log(_sigmoid(v))),
    "softsign": (lambda: P((3, 4)), lambda v: v / (1 + np.abs(v))),
    "silu": (lambda: P((3, 4)), lambda v: v * _sigmoid(v)),
    "swish": (lambda: P((3, 4)), lambda v: v * _sigmoid(v)),
    "mish": (lambda: P((3, 4)), None),
    "hardswish": (lambda: P((3, 4), 1.0, 2.0), None),
    "gelu": (lambda: P((3, 4)), None),
    "relu": (lambda: P((3, 4), 0.1, 1.0), lambda v: np.maximum(v, 0)),
    "relu6": (lambda: P((3, 4), 0.1, 1.0), lambda v: np.clip(v, 0, 6)),
    "elu": (lambda: P((3, 4), 0.1, 1.0), None),
    "celu": (lambda: P((3, 4), 0.1, 1.0), None),
    "selu": (lambda: P((3, 4), 0.1, 1.0), None),
    "tanhshrink": (lambda: P((3, 4)), lambda v: v - np.tanh(v)),
    "frac": (lambda: P((3, 4), 0.1, 0.9), lambda v: v - np.trunc(v)),
    "logit": (lambda: P((3, 4), 0.2, 0.8), lambda v: np.log(v / (1 - v))),
}
for name, (dom, ref) in UNARY.items():
    case(name, lambda dom=dom: (((T(dom())),), {}),
         (lambda v, _r=ref: _r(v)) if ref else None)

# ---- non-differentiable unary
for name, dom, ref in [
    ("ceil", lambda: P((3, 4)), np.ceil),
    ("floor", lambda: P((3, 4)), np.floor),
    ("round", lambda: P((3, 4)), np.round),
    ("trunc", lambda: P((3, 4)), np.trunc),
    ("sign", lambda: P((3, 4)), np.sign),
    ("isfinite", lambda: P((3, 4)), np.isfinite),
    ("isinf", lambda: P((3, 4)), np.isinf),
    ("isnan", lambda: P((3, 4)), np.isnan),
    ("logical_not", lambda: rng.rand(3, 4) > 0.5, np.logical_not),
    ("bitwise_not", lambda: rng.randint(0, 8, (3, 4)), np.bitwise_not),
]:
    case(name, lambda dom=dom: ((T(dom()),), {}),
         (lambda v, _r=ref: _r(v)) if ref else None, grad=False)

# ---- binary elementwise
BINARY = {
    "add": np.add, "subtract": np.subtract, "multiply": np.multiply,
    "atan2": np.arctan2,
}
for name, ref in BINARY.items():
    case(name, lambda: ((T(P((3, 4))), T(P((3, 4)))), {}),
         (lambda x, y, _r=ref: _r(x, y)))
case("divide", lambda: ((T(P((3, 4))), T(PP((3, 4)))), {}), np.divide)
# tie-free operands: finite differences flip the selected branch when
# |x - y| < 2*eps
case("maximum", lambda: ((T(P((3, 4), 0.0, 1.0)), T(P((3, 4), 1.1, 2.0))),
                         {}), np.maximum)
case("minimum", lambda: ((T(P((3, 4), 0.0, 1.0)), T(P((3, 4), 1.1, 2.0))),
                         {}), np.minimum)
# base away from 0 and exponents away from integers: pow's finite
# difference is ill-conditioned near either
case("pow", lambda: ((T(P((3, 4), 0.5, 1.0)), T(P((3, 4), 1.4, 1.9))), {}),
     np.power)
case("remainder", lambda: ((T(PP((3, 4))), T(PP((3, 4)))), {}),
     np.remainder, grad=False)
case("floor_divide", lambda: ((T(PP((3, 4)) * 10), T(PP((3, 4)) * 3)), {}),
     np.floor_divide, grad=False)
for name, ref in [("equal", np.equal), ("not_equal", np.not_equal),
                  ("greater_than", np.greater), ("greater_equal", np.greater_equal),
                  ("less_than", np.less), ("less_equal", np.less_equal)]:
    case(name, lambda: ((T(P((3, 4))), T(P((3, 4)))), {}),
         (lambda x, y, _r=ref: _r(x, y)), grad=False)
for name, ref in [("logical_and", np.logical_and), ("logical_or", np.logical_or),
                  ("logical_xor", np.logical_xor)]:
    case(name, lambda: ((T(rng.rand(3, 4) > 0.5), T(rng.rand(3, 4) > 0.5)), {}),
         (lambda x, y, _r=ref: _r(x, y)), grad=False)
for name, ref in [("bitwise_and", np.bitwise_and), ("bitwise_or", np.bitwise_or),
                  ("bitwise_xor", np.bitwise_xor)]:
    case(name, lambda: ((T(rng.randint(0, 8, (3, 4))),
                         T(rng.randint(0, 8, (3, 4)))), {}),
         (lambda x, y, _r=ref: _r(x, y)), grad=False)

# ---- reductions
case("sum", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: v.sum(axis=1))
case("mean", lambda: ((T(P((3, 4))),), {"axis": 0}),
     lambda v: v.mean(axis=0))
case("prod", lambda: ((T(PP((3, 3))),), {"axis": 1}),
     lambda v: v.prod(axis=1))
case("max", lambda: ((T((lambda: rng.permutation(np.arange(12, dtype=np.float32)).reshape(3, 4) * 0.1)()),), {"axis": 1}), lambda v: v.max(axis=1))
case("min", lambda: ((T((lambda: rng.permutation(np.arange(12, dtype=np.float32)).reshape(3, 4) * 0.1)()),), {"axis": 1}), lambda v: v.min(axis=1))
case("amax", lambda: ((T((lambda: rng.permutation(np.arange(12, dtype=np.float32)).reshape(3, 4) * 0.1)()),), {"axis": 1}), lambda v: v.max(axis=1))
case("amin", lambda: ((T((lambda: rng.permutation(np.arange(12, dtype=np.float32)).reshape(3, 4) * 0.1)()),), {"axis": 1}), lambda v: v.min(axis=1))
case("var", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: v.var(axis=1, ddof=1))
case("std", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: v.std(axis=1, ddof=1))
case("logsumexp", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: np.log(np.exp(v).sum(axis=1)))
case("median", lambda: ((T(P((3, 5))),), {"axis": 1}),
     lambda v: np.median(v, axis=1), grad=False)
case("quantile", lambda: ((T(P((3, 5))),), {"q": 0.5, "axis": 1}),
     lambda v: np.quantile(v, 0.5, axis=1), grad=False)
case("nansum", lambda: ((T(P((3, 4))),), {}), np.nansum)
case("nanmean", lambda: ((T(P((3, 4))),), {}), np.nanmean)
case("all", lambda: ((T(rng.rand(3, 4) > 0.2),), {}), np.all, grad=False)
case("any", lambda: ((T(rng.rand(3, 4) > 0.8),), {}), np.any, grad=False)
case("count_nonzero", lambda: ((T(rng.randint(0, 2, (3, 4))),), {}),
     np.count_nonzero, grad=False)
case("cumsum", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: v.cumsum(axis=1))
case("cumprod", lambda: ((T(PP((3, 4))),), {"dim": 1}),
     lambda v: v.cumprod(axis=1))
case("cummax", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: np.maximum.accumulate(v, axis=1), grad=False)

# ---- matmul family
case("matmul", lambda: ((T(P((3, 4))), T(P((4, 5)))), {}), np.matmul)
case("mm", lambda: ((T(P((3, 4))), T(P((4, 5)))), {}), np.matmul)
case("bmm", lambda: ((T(P((2, 3, 4))), T(P((2, 4, 5)))), {}), np.matmul)
case("mv", lambda: ((T(P((3, 4))), T(P((4,)))), {}), np.matmul)
case("dot", lambda: ((T(P((4,))), T(P((4,)))), {}), np.dot)
case("inner", lambda: ((T(P((3, 4))), T(P((5, 4)))), {}), np.inner)
case("outer", lambda: ((T(P((3,))), T(P((4,)))), {}), np.outer)
case("kron", lambda: ((T(P((2, 2))), T(P((2, 3)))), {}), np.kron)
case("addmm", lambda: ((T(P((3, 5))), T(P((3, 4))), T(P((4, 5)))), {}),
     lambda i, x, y: i + x @ y)
case("einsum", lambda: (("ij,jk->ik", T(P((3, 4))), T(P((4, 5)))), {}),
     None)
case("linear", lambda: ((T(P((3, 4))), T(P((4, 5))), T(P((5,)))), {}),
     lambda x, w, b: x @ w + b)
case("trace", lambda: ((T(P((4, 4))),), {}), np.trace)

# ---- shape / indexing (forward vs numpy; grads via finite diff where cheap)
case("reshape", lambda: ((T(P((3, 4))),), {"shape": [4, 3]}),
     lambda v: v.reshape(4, 3))
case("transpose", lambda: ((T(P((3, 4))),), {"perm": [1, 0]}),
     lambda v: v.T)
case("flatten", lambda: ((T(P((2, 3, 4))),), {"start_axis": 1}),
     lambda v: v.reshape(2, 12))
case("squeeze", lambda: ((T(P((3, 1, 4))),), {"axis": 1}),
     lambda v: v.squeeze(1))
case("unsqueeze", lambda: ((T(P((3, 4))),), {"axis": 0}),
     lambda v: v[None])
case("flip", lambda: ((T(P((3, 4))),), {"axis": [0]}),
     lambda v: np.flip(v, 0))
case("roll", lambda: ((T(P((3, 4))),), {"shifts": 1, "axis": 0}),
     lambda v: np.roll(v, 1, 0))
case("tile", lambda: ((T(P((2, 3))),), {"repeat_times": [2, 2]}),
     lambda v: np.tile(v, (2, 2)))
case("expand", lambda: ((T(P((1, 4))),), {"shape": [3, 4]}),
     lambda v: np.broadcast_to(v, (3, 4)))
case("expand_as", lambda: ((T(P((1, 4))), T(P((3, 4)))), {}),
     lambda v, y: np.broadcast_to(v, (3, 4)))
case("broadcast_to", lambda: ((T(P((1, 4))),), {"shape": [3, 4]}),
     lambda v: np.broadcast_to(v, (3, 4)))
case("concat", lambda: (([T(P((2, 3))), T(P((2, 3)))],), {"axis": 0}),
     None)
case("stack", lambda: (([T(P((2, 3))), T(P((2, 3)))],), {"axis": 0}), None)
case("split", lambda: ((T(P((4, 6))),), {"num_or_sections": 2, "axis": 1}),
     None, grad=False)
case("chunk", lambda: ((T(P((4, 6))),), {"chunks": 2, "axis": 1}),
     None, grad=False)
case("unbind", lambda: ((T(P((3, 4))),), {"axis": 0}), None, grad=False)
case("slice", lambda: ((T(P((4, 6))),),
                       {"axes": [0, 1], "starts": [1, 0], "ends": [3, 4]}),
     lambda v: v[1:3, 0:4])
case("strided_slice", lambda: ((T(P((6,))),),
                               {"axes": [0], "starts": [0], "ends": [6],
                                "strides": [2]}),
     lambda v: v[0:6:2])
case("gather", lambda: ((T(P((5, 3))), T(np.array([0, 2]))), {"axis": 0}),
     lambda v, i: v[[0, 2]])
case("gather_nd", lambda: ((T(P((3, 4))),
                            T(np.array([[0, 1], [2, 2]]))), {}),
     lambda v, i: v[[0, 2], [1, 2]])
case("index_select", lambda: ((T(P((5, 3))), T(np.array([0, 2]))),
                              {"axis": 0}),
     lambda v, i: v[[0, 2]])
case("take_along_axis", lambda: ((T(P((3, 4))),
                                  T(np.array([[0], [1], [2]]))), {"axis": 1}),
     lambda v, i: np.take_along_axis(v, np.array([[0], [1], [2]]), 1))
case("put_along_axis", lambda: ((T(P((3, 4))), T(np.array([[0], [1], [2]])),
                                 T(P((3, 1)))), {"axis": 1}), None,
     grad=False)
case("index_put", lambda: ((T(P((3, 4))), [T(np.array([0, 1]))],
                            T(P((2, 4)))), {}), None, grad=False)
case("scatter", lambda: ((T(P((4, 3))), T(np.array([1, 3])),
                          T(P((2, 3)))), {}), None, grad=False)
case("scatter_nd_add", lambda: ((T(P((4,))), T(np.array([[1], [2]])),
                                 T(P((2,)))), {}), None, grad=False)
case("masked_fill", lambda: ((T(P((3, 4))), T(rng.rand(3, 4) > 0.5)),
                             {"value": 0.5}), None)
case("masked_select", lambda: ((T(P((3, 4))), T(rng.rand(3, 4) > 0.5)), {}),
     None, grad=False)
case("where", lambda: ((T(rng.rand(3, 4) > 0.5), T(P((3, 4))),
                        T(P((3, 4)))), {}),
     lambda c, x, y: np.where(c, x, y))
case("nonzero", lambda: ((T(np.array([0.0, 1.0, 0.0, 2.0])),), {}),
     None, grad=False)
case("tril", lambda: ((T(P((4, 4))),), {}), np.tril)
case("triu", lambda: ((T(P((4, 4))),), {}), np.triu)
case("diag", lambda: ((T(P((4,))),), {}), np.diag)
case("diagonal", lambda: ((T(P((4, 4))),), {}),
     lambda v: np.diagonal(v, 0, 0, 1))
case("pad", lambda: ((T(P((2, 3))),), {"paddings": [1, 1, 0, 0]}), None)
case("repeat_interleave", lambda: ((T(P((3,))),), {"repeats": 2}),
     lambda v: np.repeat(v, 2))
case("meshgrid", lambda: (([T(P((3,))), T(P((4,)))],), {}), None,
     grad=False)
case("_getitem", lambda: ((T(P((4, 5))),), {"idx": (slice(1, 3),)}),
     lambda v: v[1:3])
case("as_strided", lambda: ((T(P((4, 4))),),
                            {"shape": [2, 2], "stride": [4, 1],
                             "offset": 0}), None, grad=False)

# ---- sort / search
case("sort", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: np.sort(v, 1), grad=False)
case("argsort", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: np.argsort(v, 1, kind="stable"), grad=False)
case("argmax", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: v.argmax(1), grad=False)
case("argmin", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: v.argmin(1), grad=False)
case("topk", lambda: ((T(P((3, 6))),), {"k": 2}), None, grad=False)
case("searchsorted", lambda: ((T(np.array([1.0, 3.0, 5.0])),
                               T(np.array([2.0, 4.0]))), {}),
     lambda s, v: np.searchsorted(s, v), grad=False)
case("unique", lambda: ((T(np.array([3, 1, 2, 1, 3])),), {}),
     None, grad=False)
case("bincount", lambda: ((T(np.array([0, 1, 1, 3])), None), {}),
     lambda v: np.bincount(v), grad=False)
case("histogram", lambda: ((T(P((20,), 0.0, 1.0)),),
                           {"bins": 4, "min": 0.0, "max": 1.0}),
     None, grad=False)
case("allclose", lambda: ((T(P((3,))), T(P((3,)))), {}), None, grad=False)
case("isclose", lambda: ((T(P((3,))), T(P((3,)))), {}), None, grad=False)

# ---- creation (forward-only)
case("arange", lambda: ((), {"start": 0, "end": 5, "step": 1}),
     None, grad=False)
case("linspace", lambda: ((), {"start": 0.0, "stop": 1.0, "num": 5}),
     None, grad=False)
case("eye", lambda: ((), {"num_rows": 3}), None, grad=False)
case("full", lambda: ((), {"shape": [2, 2], "fill_value": 7.0}),
     None, grad=False)
case("full_like", lambda: ((T(P((2, 2))),), {"fill_value": 7.0}),
     None, grad=False)
case("ones", lambda: ((), {"shape": [2, 3]}), None, grad=False)
case("ones_like", lambda: ((T(P((2, 3))),), {}), None, grad=False)
case("zeros", lambda: ((), {"shape": [2, 3]}), None, grad=False)
case("zeros_like", lambda: ((T(P((2, 3))),), {}), None, grad=False)
case("assign", lambda: ((T(P((2, 3))),), {}), lambda v: v)
case("cast", lambda: ((T(P((2, 3))),), {"dtype": "float64"}), None,
     grad=False)
case("one_hot", lambda: ((T(np.array([0, 2, 1])),), {"num_classes": 3}),
     None, grad=False)

# ---- random (statistical smoke only)
for name, kwargs in [
    ("uniform", {"shape": [64], "min": 0.0, "max": 1.0}),
    ("gaussian", {"shape": [64], "mean": 0.0, "std": 1.0}),
    ("randint", {"low": 0, "high": 5, "shape": [64]}),
    ("randperm", {"n": 16}),
]:
    case(name, lambda kwargs=kwargs: ((), kwargs), None, grad=False)
case("bernoulli", lambda: ((T(np.full((64,), 0.5, np.float32)),), {}),
     None, grad=False)
case("multinomial", lambda: ((T(np.full((4,), 0.25, np.float32)),),
                             {"num_samples": 2}), None, grad=False)
case("dropout", lambda: ((T(P((8, 8))),), {"p": 0.5}), None, grad=False)


def _bdrln_ref(x, res, bias, g, b):
    z = x + bias + res
    m = z.mean(-1, keepdims=True)
    v = ((z - m) ** 2).mean(-1, keepdims=True)
    return (z - m) / np.sqrt(v + 1e-5) * g + b


case("fused_bias_dropout_residual_layer_norm",
     lambda: ((T(P((4, 64))), T(P((4, 64))), T(P((64,))), T(PP((64,))),
               T(P((64,)))),
              {"dropout_rate": 0.0, "training": False}),
     _bdrln_ref, grad=True)
case("alpha_dropout", lambda: ((T(P((8, 8))),), {"p": 0.5}), None,
     grad=False)
case("gumbel_softmax", lambda: ((T(P((4, 5))),), {}), None, grad=False)

# ---- linalg
case("cholesky", lambda: ((T(np.eye(3, dtype=np.float32) * 2.0),), {}),
     lambda v: np.linalg.cholesky(v))
case("det", lambda: ((T(P((3, 3)) + 2 * np.eye(3, dtype=np.float32)),), {}),
     np.linalg.det)
case("slogdet", lambda: ((T(P((3, 3)) + 2 * np.eye(3, dtype=np.float32)),),
                         {}), None, grad=False)
case("inverse", lambda: ((T(P((3, 3)) + 2 * np.eye(3, dtype=np.float32)),),
                         {}), np.linalg.inv)
case("matrix_power", lambda: ((T(P((3, 3))),), {"n": 2}),
     lambda v: v @ v)
case("matrix_norm", lambda: ((T(P((3, 4))),), {}),
     lambda v: np.linalg.norm(v, "fro"), grad=False)
case("norm", lambda: ((T(P((3, 4))),), {}),
     lambda v: np.linalg.norm(v), grad=False)
case("p_norm", lambda: ((T(P((3, 4))),), {"porder": 2, "axis": 1}),
     lambda v: np.linalg.norm(v, 2, 1))
case("l2_normalize", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: v / np.linalg.norm(v, 2, 1, keepdims=True))
case("qr", lambda: ((T(P((4, 3))),), {}), None, grad=False)
case("svd", lambda: ((T(P((4, 3))),), {}), None, grad=False)
case("eig", lambda: ((T(P((3, 3))),), {}), None, grad=False)
case("eigh", lambda: ((T(np.eye(3, dtype=np.float32)),), {}), None,
     grad=False)
case("pinv", lambda: ((T(P((4, 3))),), {}), np.linalg.pinv, grad=False)
case("solve", lambda: ((T(P((3, 3)) + 2 * np.eye(3, dtype=np.float32)),
                        T(P((3, 2)))), {}),
     lambda a, b: np.linalg.solve(a, b))
case("lstsq", lambda: ((T(P((4, 3))), T(P((4, 2)))), {}), None,
     grad=False)
case("triangular_solve",
     lambda: ((T(np.triu(P((3, 3)) + 2 * np.eye(3, dtype=np.float32))),
               T(P((3, 2)))), {}),
     lambda a, b: np.linalg.solve(a, b))
case("cross", lambda: ((T(P((2, 3))), T(P((2, 3)))), {}),
     lambda x, y: np.cross(x, y))
case("lerp", lambda: ((T(P((3,))), T(P((3,))), T(PP((3,)))), {}),
     lambda x, y, w: x + w * (y - x))
case("nan_to_num", lambda: ((T(np.array([1.0, np.nan, np.inf])),), {}),
     np.nan_to_num, grad=False)
case("clip", lambda: ((T(P((3, 4))),), {"min": -0.5, "max": 0.5}),
     lambda v: np.clip(v, -0.5, 0.5))
case("scale", lambda: ((T(P((3, 4))),), {"scale": 2.0, "bias": 1.0}),
     lambda v: 2 * v + 1)

# ---- fft
case("fft", lambda: ((T(P((8,))),), {}), np.fft.fft, grad=False)
case("ifft", lambda: ((T(P((8,)).astype(np.complex64)),), {}),
     np.fft.ifft, grad=False)
case("rfft", lambda: ((T(P((8,))),), {}), np.fft.rfft, grad=False)
case("irfft", lambda: ((T(np.fft.rfft(P((8,))).astype(np.complex64)),), {}),
     None, grad=False)
case("fft2", lambda: ((T(P((4, 4))),), {}), np.fft.fft2, grad=False)
case("ifft2", lambda: ((T(P((4, 4)).astype(np.complex64)),), {}),
     np.fft.ifft2, grad=False)
case("fftshift", lambda: ((T(P((5,))),), {}), np.fft.fftshift, grad=False)
case("ifftshift", lambda: ((T(P((5,))),), {}), np.fft.ifftshift,
     grad=False)

# ---- nn ops
case("softmax", lambda: ((T(P((3, 4))),), {}),
     lambda v: np.exp(v) / np.exp(v).sum(-1, keepdims=True))
case("log_softmax", lambda: ((T(P((3, 4))),), {}),
     lambda v: v - v.max(-1, keepdims=True)
     - np.log(np.exp(v - v.max(-1, keepdims=True)).sum(-1, keepdims=True)))
case("leaky_relu", lambda: ((T(P((3, 4), 0.1, 1.0)),), {}),
     lambda v: np.where(v > 0, v, 0.01 * v))
case("hardtanh", lambda: ((T(P((3, 4))),), {}),
     lambda v: np.clip(v, -1, 1))
case("hardsigmoid", lambda: ((T(P((3, 4))),), {}), None)
case("hardshrink", lambda: ((T(P((3, 4), 0.6, 1.0)),), {}), None)
case("softshrink", lambda: ((T(P((3, 4), 0.6, 1.0)),), {}), None)
case("softplus", lambda: ((T(P((3, 4))),), {}),
     lambda v: np.log1p(np.exp(v)))
case("maxout", lambda: ((T(P((2, 4, 3, 3))),), {"groups": 2}), None)
case("prelu", lambda: ((T(P((2, 3), 0.2, 1.0)), T(np.array([0.25], np.float32))), {}),
     None)
case("glu", lambda: ((T(P((3, 4))),), {}),
     lambda v: v[:, :2] * _sigmoid(v[:, 2:]))
case("embedding", lambda: ((T(np.array([[0, 2]])), T(P((5, 3)))), {}),
     lambda i, w: w[[[0, 2]]])
case("label_smooth", lambda: ((T(np.eye(3, dtype=np.float32)), None),
                              {"epsilon": 0.1}), None)
case("cosine_similarity", lambda: ((T(P((3, 4))), T(P((3, 4)))), {}),
     lambda x, y: (x * y).sum(-1) /
     (np.linalg.norm(x, 2, -1) * np.linalg.norm(y, 2, -1)))
case("layer_norm", lambda: ((T(P((3, 4))), T(PP((4,))), T(P((4,)))), {}),
     lambda x, w, b: (x - x.mean(-1, keepdims=True)) /
     np.sqrt(x.var(-1, keepdims=True) + 1e-5) * w + b)
case("rms_norm", lambda: ((T(P((3, 4))), T(PP((4,))), None), {}),
     lambda x, w: x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * w)
case("group_norm", lambda: ((T(P((2, 4, 3, 3))), T(PP((4,))),
                             T(P((4,)))), {"groups": 2}), None)
case("instance_norm", lambda: ((T(P((2, 3, 4, 4))), None, None), {}), None)
case("batch_norm", lambda: ((T(P((4, 3))), T(np.zeros(3, np.float32)),
                             T(np.ones(3, np.float32)),
                             T(np.ones(3, np.float32)),
                             T(np.zeros(3, np.float32))),
                            {"training": False}), None)
case("local_response_norm", lambda: ((T(P((2, 4, 3, 3))),), {"size": 3}),
     None)
case("spectral_norm", lambda: ((T(P((4, 3))), T(P((4,))), T(P((3,)))), {}),
     None, grad=False)

# ---- conv / pool / vision
case("conv2d", lambda: ((T(P((1, 2, 5, 5))), T(P((3, 2, 3, 3))), None),
                        {"padding": 1}), None)
case("conv1d", lambda: ((T(P((1, 2, 8))), T(P((3, 2, 3))), None),
                        {"padding": 1}), None)
case("conv3d", lambda: ((T(P((1, 1, 4, 4, 4))), T(P((2, 1, 3, 3, 3))),
                         None), {}), None)
case("conv2d_transpose", lambda: ((T(P((1, 2, 4, 4))),
                                   T(P((2, 3, 3, 3))), None), {}), None)
case("max_pool2d", lambda: ((T(P((1, 2, 4, 4))),), {"kernel_size": 2}),
     None)
case("avg_pool2d", lambda: ((T(P((1, 2, 4, 4))),), {"kernel_size": 2}),
     None)
case("max_pool1d", lambda: ((T(P((1, 2, 6))),), {"kernel_size": 2}), None)
case("avg_pool1d", lambda: ((T(P((1, 2, 6))),), {"kernel_size": 2}), None)
case("adaptive_avg_pool2d", lambda: ((T(P((1, 2, 4, 4))),),
                                     {"output_size": 2}), None)
case("adaptive_max_pool2d", lambda: ((T(P((1, 2, 4, 4))),),
                                     {"output_size": 2}), None)
case("interpolate", lambda: ((T(P((1, 2, 4, 4))),), {"scale_factor": 2}),
     None)
case("pixel_shuffle", lambda: ((T(P((1, 4, 2, 2))),),
                               {"upscale_factor": 2}), None)
case("unfold", lambda: ((T(P((1, 2, 4, 4))),), {"kernel_sizes": 2}), None)

# ---- losses
case("mse_loss", lambda: ((T(P((3, 4))), T(P((3, 4)))), {}),
     lambda a, b: ((a - b) ** 2).mean())
case("l1_loss", lambda: ((T(P((3, 4))), T(P((3, 4)))), {}),
     lambda a, b: np.abs(a - b).mean())
case("smooth_l1_loss", lambda: ((T(P((3, 4))), T(P((3, 4)))), {}), None)
case("kl_div", lambda: ((T(np.log(PP((3, 4)))), T(PP((3, 4)))), {}), None)
case("nll_loss", lambda: ((T(np.log(PP((3, 4)))), T(np.array([0, 1, 2])),
                           None), {}), None)
case("cross_entropy", lambda: ((T(P((3, 4))), T(np.array([[0], [1], [2]])),
                                None), {}), None)
case("softmax_with_cross_entropy",
     lambda: ((T(P((3, 4))), T(np.array([[0], [1], [2]]))), {}), None)
case("c_softmax_with_cross_entropy",
     lambda: ((T(P((3, 4))), T(np.array([[0], [1], [2]]))), {}), None)
case("fused_linear_cross_entropy",
     lambda: ((T(P((3, 8))), T(P((20, 8))),
               T(np.array([0, 5, 19]))), {}), None)
case("binary_cross_entropy", lambda: ((T(PP((3,)) * 0.8),
                                       T((rng.rand(3) > 0.5).astype(np.float32)),
                                       None), {}), None)
case("binary_cross_entropy_with_logits",
     lambda: ((T(P((3,))), T((rng.rand(3) > 0.5).astype(np.float32)),
               None, None), {}), None)
case("hinge_embedding_loss",
     lambda: ((T(P((3,))), T(np.array([1.0, -1.0, 1.0], np.float32))), {}),
     None)

# ---- attention / rope / misc covered elsewhere but need table entries
case("scaled_dot_product_attention",
     lambda: ((T(P((1, 4, 2, 8))), T(P((1, 4, 2, 8))), T(P((1, 4, 2, 8)))),
              {}), None)
case("rotary_position_embedding",
     lambda: ((T(P((1, 4, 2, 8))), T(P((1, 4, 2, 8))),
               T(P((16, 8))), T(P((16, 8)))), {}), None, grad=False)

# ---- extended surface (kernels_ext.py)
case("angle", lambda: ((T(P((3,)).astype(np.complex64)),), {}), np.angle,
     grad=False)
case("conj", lambda: ((T(P((3,)).astype(np.complex64)),), {}), np.conj,
     grad=False)
case("real", lambda: ((T(P((3,)).astype(np.complex64)),), {}), np.real,
     grad=False)
case("imag", lambda: ((T(P((3,)).astype(np.complex64)),), {}), np.imag,
     grad=False)
case("copysign", lambda: ((T(P((3,))), T(P((3,)))), {}), np.copysign,
     grad=False)
case("bitwise_left_shift",
     lambda: ((T(np.array([1, 2, 4], np.int32)),
               T(np.array([2, 1, 0], np.int32))), {}),
     lambda x, y: np.left_shift(x, y), grad=False)
case("bitwise_right_shift",
     lambda: ((T(np.array([8, 4, 2], np.int32)),
               T(np.array([2, 1, 0], np.int32))), {}),
     lambda x, y: np.right_shift(x, y), grad=False)
case("pdist", lambda: ((T(P((4, 3))),), {}),
     lambda x: np.sqrt(((x[:, None, :] - x[None, :, :]) ** 2).sum(-1))[
         np.triu_indices(x.shape[0], k=1)])
case("reduce_as", lambda: ((T(P((4, 3, 2))), T(P((3, 1)))), {}),
     lambda x, t: x.sum(0).sum(-1, keepdims=True))
case("histogram_bin_edges",
     lambda: ((T(P((20,), 0.0, 1.0)),), {"bins": 4, "min": 0.0, "max": 1.0}),
     lambda x: np.histogram_bin_edges(x, bins=4, range=(0.0, 1.0)),
     grad=False)
case("deg2rad", lambda: ((T(P((3,)) * 180),), {}), np.deg2rad)
case("rad2deg", lambda: ((T(P((3,))),), {}), np.rad2deg)
case("digamma", lambda: ((T(PP((3,)) + 1),), {}), None)
case("lgamma", lambda: ((T(PP((3,)) + 1),), {}), None)
case("gammaln", lambda: ((T(PP((3,)) + 1),), {}), None)
case("gammainc", lambda: ((T(PP((3,))), T(PP((3,)))), {}), None, grad=False)
case("gammaincc", lambda: ((T(PP((3,))), T(PP((3,)))), {}), None, grad=False)
case("fmax", lambda: ((T(P((3,))), T(P((3,)))), {}), np.fmax)
case("fmin", lambda: ((T(P((3,))), T(P((3,)))), {}), np.fmin)
case("gcd", lambda: ((T(np.array([4, 6])), T(np.array([6, 9]))), {}),
     np.gcd, grad=False)
case("lcm", lambda: ((T(np.array([4, 6])), T(np.array([6, 9]))), {}),
     np.lcm, grad=False)
case("heaviside", lambda: ((T(P((3,), 0.2, 1.0)), T(P((3,)))), {}),
     np.heaviside)
case("hypot", lambda: ((T(PP((3,))), T(PP((3,)))), {}), np.hypot)
case("i0", lambda: ((T(P((3,))),), {}), None)
case("i0e", lambda: ((T(P((3,))),), {}), None, grad=False)
case("i1", lambda: ((T(P((3,))),), {}), None, grad=False)
case("i1e", lambda: ((T(P((3,))),), {}), None, grad=False)
case("isneginf", lambda: ((T(np.array([1.0, -np.inf])),), {}), np.isneginf,
     grad=False)
case("isposinf", lambda: ((T(np.array([1.0, np.inf])),), {}), np.isposinf,
     grad=False)
case("isreal", lambda: ((T(P((3,))),), {}), np.isreal, grad=False)
case("isin", lambda: ((T(np.array([1, 2, 3])), T(np.array([2]))), {}),
     None, grad=False)
case("ldexp", lambda: ((T(P((3,))), T(np.array([1.0, 2.0, 3.0]))), {}),
     lambda x, y: np.ldexp(x, y.astype(np.int32)), grad=False)
case("frexp", lambda: ((T(PP((3,))),), {}), None, grad=False)
case("logaddexp", lambda: ((T(P((3,))), T(P((3,)))), {}), np.logaddexp)
case("neg", lambda: ((T(P((3,))),), {}), np.negative)
case("nextafter", lambda: ((T(P((3,))), T(P((3,)))), {}), np.nextafter,
     grad=False)
case("polar", lambda: ((T(PP((3,))), T(P((3,)))), {}),
     lambda a, t: a * np.exp(1j * t).astype(np.complex64), grad=False)
case("sgn", lambda: ((T(P((3,))),), {}), np.sign, grad=False)
case("signbit", lambda: ((T(P((3,))),), {}), np.signbit, grad=False)
case("sinc", lambda: ((T(P((3,))),), {}), np.sinc)
case("stanh", lambda: ((T(P((3,))),), {}),
     lambda v: 1.7159 * np.tanh(0.67 * v))
case("complex", lambda: ((T(P((3,))), T(P((3,)))), {}),
     lambda r, i: r + 1j * i, grad=False)
case("as_complex", lambda: ((T(P((3, 2))),), {}),
     lambda v: v[..., 0] + 1j * v[..., 1], grad=False)
case("as_real", lambda: ((T(P((3,)).astype(np.complex64)),), {}),
     lambda v: np.stack([v.real, v.imag], -1), grad=False)
case("logcumsumexp", lambda: ((T(P((5,))),), {}),
     lambda v: np.log(np.cumsum(np.exp(v))))
case("cummin", lambda: ((T(P((5,))),), {}), None, grad=False)
case("nanquantile", lambda: ((T(P((5,))),), {"q": 0.5}),
     lambda v: np.nanquantile(v, 0.5), grad=False)
case("nanmedian", lambda: ((T(P((5,))),), {}), np.nanmedian, grad=False)
case("mode", lambda: ((T(np.array([1.0, 2.0, 2.0, 3.0])),), {}), None,
     grad=False)
case("kthvalue", lambda: ((T(P((5,))),), {"k": 2}), None, grad=False)
case("dist", lambda: ((T(P((3,))), T(P((3,)))), {}),
     lambda x, y: np.linalg.norm(x - y))
case("vector_norm", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda v: np.linalg.norm(v, 2, 1))
case("trapezoid", lambda: ((T(P((5,))), None), {}),
     lambda y: np.trapezoid(y) if hasattr(np, "trapezoid") else np.trapz(y))
case("cumulative_trapezoid", lambda: ((T(P((5,))), None), {}), None)
case("corrcoef", lambda: ((T(P((3, 6))),), {}), np.corrcoef, grad=False)
case("cov", lambda: ((T(P((3, 6))),), {}), lambda v: np.cov(v, ddof=1))
case("add_n", lambda: (([T(P((3,))), T(P((3,))), T(P((3,)))],), {}), None)
case("atleast_1d", lambda: ((T(np.float32(3.0)),), {}), np.atleast_1d)
case("atleast_2d", lambda: ((T(P((3,))),), {}), np.atleast_2d)
case("atleast_3d", lambda: ((T(P((3,))),), {}), np.atleast_3d)
case("block_diag", lambda: (([T(P((2, 2))), T(P((3, 3)))],), {}), None)
case("broadcast_tensors", lambda: (([T(P((1, 4))), T(P((3, 1)))],), {}),
     None, grad=False)
case("bucketize", lambda: ((T(np.array([0.5, 2.5])),
                            T(np.array([1.0, 2.0, 3.0]))), {}),
     None, grad=False)
case("cdist", lambda: ((T(P((3, 4))), T(P((5, 4)))), {}), None)
case("clone", lambda: ((T(P((3,))),), {}), lambda v: v)
case("column_stack", lambda: (([T(P((3,))), T(P((3,)))],), {}),
     None)
case("row_stack", lambda: (([T(P((2, 3))), T(P((2, 3)))],), {}), None)
case("hstack", lambda: (([T(P((3,))), T(P((3,)))],), {}), None)
case("vstack", lambda: (([T(P((2, 3))), T(P((2, 3)))],), {}), None)
case("dstack", lambda: (([T(P((2, 3))), T(P((2, 3)))],), {}), None)
case("hsplit", lambda: ((T(P((4, 4))),), {"num_or_indices": 2}), None,
     grad=False)
case("vsplit", lambda: ((T(P((4, 4))),), {"num_or_indices": 2}), None,
     grad=False)
case("dsplit", lambda: ((T(P((2, 2, 4))),), {"num_or_indices": 2}), None,
     grad=False)
case("tensor_split", lambda: ((T(P((5, 2))),), {"num_or_indices": 2}),
     None, grad=False)
case("combinations", lambda: ((T(P((4,))),), {"r": 2}), None, grad=False)
case("diag_embed", lambda: ((T(P((2, 3))),), {}), None)
case("diagflat", lambda: ((T(P((3,))),), {}), np.diagflat)
case("diagonal_scatter", lambda: ((T(P((3, 3))), T(P((3,)))), {}), None)
case("diff", lambda: ((T(P((5,))),), {}), np.diff)
case("equal_all", lambda: ((T(P((3,))), T(P((3,)))), {}), None, grad=False)
case("fill_diagonal_tensor", lambda: ((T(P((3, 3))), T(P((3,)))), {}),
     None)
case("index_add", lambda: ((T(P((4, 3))), T(np.array([0, 2]))),
                           {"axis": 0, "value": T(P((2, 3)))}), None,
     grad=False)
case("index_fill", lambda: ((T(P((4, 3))), T(np.array([0, 2]))),
                            {"axis": 0, "value": 0.0}), None, grad=False)
case("index_sample", lambda: ((T(P((3, 5))),
                               T(np.array([[0, 1], [2, 3], [4, 0]]))), {}),
     lambda v, i: np.take_along_axis(v, np.array([[0, 1], [2, 3], [4, 0]]), 1))
case("masked_scatter", lambda: ((T(P((4,))), T(np.array([True, False, True, False])),
                                 T(P((4,)))), {}), None, grad=False)
case("moveaxis", lambda: ((T(P((2, 3, 4))),),
                          {"source": 0, "destination": 2}),
     lambda v: np.moveaxis(v, 0, 2))
case("renorm", lambda: ((T(P((3, 4))),), {"p": 2.0, "axis": 0,
                                          "max_norm": 1.0}), None)
case("rot90", lambda: ((T(P((3, 4))),), {}), lambda v: np.rot90(v))
case("select_scatter", lambda: ((T(P((3, 4))), T(P((4,)))),
                                {"axis": 0, "index": 1}), None)
case("slice_scatter", lambda: ((T(P((4, 4))), T(P((2, 4)))),
                               {"axes": [0], "starts": [0], "ends": [2],
                                "strides": [1]}), None)
case("scatter_nd", lambda: ((T(np.array([[1], [3]])), T(P((2,)))),
                            {"shape": [5]}), None, grad=False)
case("t", lambda: ((T(P((3, 4))),), {}), lambda v: v.T)
case("take", lambda: ((T(P((3, 4))), T(np.array([0, 5, 11]))), {}),
     lambda v, i: v.flatten()[[0, 5, 11]], grad=False)
case("tensordot", lambda: ((T(P((3, 4))), T(P((4, 5)))), {"axes": 1}),
     lambda x, y: np.tensordot(x, y, 1))
case("unflatten", lambda: ((T(P((6,))),), {"axis": 0, "shape": [2, 3]}),
     lambda v: v.reshape(2, 3))
case("unstack", lambda: ((T(P((3, 4))),), {}), None, grad=False)
case("unique_consecutive", lambda: ((T(np.array([1, 1, 2, 3, 3])),), {}),
     None, grad=False)
case("vander", lambda: ((T(P((3,))),), {}), np.vander, grad=False)
case("crop", lambda: ((T(P((4, 4))),), {"shape": [2, 2],
                                        "offsets": [1, 1]}),
     lambda v: v[1:3, 1:3])
case("multiplex", lambda: (([T(P((3, 2))), T(P((3, 2)))],
                            T(np.array([[0], [1], [0]]))), {}), None,
     grad=False)
case("shard_index", lambda: ((T(np.array([0, 5, 9])),),
                             {"index_num": 10, "nshards": 2, "shard_id": 0}),
     None, grad=False)
case("increment", lambda: ((T(P((3,))),), {}), lambda v: v + 1)
case("logspace", lambda: ((), {"start": 0, "stop": 2, "num": 3}), None,
     grad=False)
case("tril_indices", lambda: ((), {"row": 3}), None, grad=False)
case("triu_indices", lambda: ((), {"row": 3}), None, grad=False)
case("cholesky_solve",
     lambda: ((T(P((3, 1))),
               T(np.linalg.cholesky((lambda a: a @ a.T + 3 * np.eye(3))(
                   P((3, 3)))).astype(np.float32))), {}), None, grad=False)
case("cholesky_inverse",
     lambda: ((T(np.linalg.cholesky((lambda a: a @ a.T + 3 * np.eye(3))(
         P((3, 3)))).astype(np.float32)),), {}), None, grad=False)
case("eigvals", lambda: ((T(P((3, 3))),), {}), None, grad=False)
case("eigvalsh", lambda: ((T(np.eye(3, dtype=np.float32) * 2),), {}),
     lambda v: np.linalg.eigvalsh(v), grad=False)
case("matrix_exp", lambda: ((T(P((3, 3)) * 0.1),), {}), None, grad=False)
case("lu", lambda: ((T(P((3, 3)) + 2 * np.eye(3, dtype=np.float32)),), {}),
     None, grad=False)
case("multi_dot", lambda: (([T(P((2, 3))), T(P((3, 4))), T(P((4, 2)))],),
                           {}), None)
for name, kwargs in [
    ("normal", {"mean": 0.0, "std": 1.0, "shape": [32]}),
    ("standard_normal", {"shape": [32]}),
    ("log_normal", {"shape": [16]}),
]:
    case(name, lambda kwargs=kwargs: ((), kwargs), None, grad=False)
case("standard_gamma", lambda: ((T(PP((16,)) * 3),), {}), None, grad=False)
case("poisson", lambda: ((T(PP((16,)) * 4),), {}), None, grad=False)
case("binomial", lambda: ((T(np.full((8,), 10.0, np.float32)),
                           T(np.full((8,), 0.5, np.float32))), {}), None,
     grad=False)
case("randint_like", lambda: ((T(P((8,))),), {"low": 0, "high": 5}), None,
     grad=False)
case("rank", lambda: ((T(P((2, 3))),), {}), None, grad=False)

# internal composite ops covered by their own dedicated test files

case("cartesian_prod", lambda: (([T(P((2,))), T(P((3,)))],), {}), None,
     grad=False)
case("fill_constant", lambda: ((), {"shape": [2, 2], "dtype": "float32",
                                    "value": 5.0}), None, grad=False)
case("polygamma", lambda: ((T(PP((3,)) + 1),), {}), None)
case("multigammaln", lambda: ((T(PP((3,)) + 3),), {"p": 2}), None)
case("histogramdd", lambda: ((T(P((10, 2))),), {"bins": 3}), None,
     grad=False)
case("lu_unpack", lambda: (tuple(
    __import__("paddle_tpu").lu(T(P((3, 3)) + 2 * np.eye(3, dtype=np.float32)))
), {}), None, grad=False)
case("householder_product",
     lambda: ((T(np.linalg.qr(P((4, 3)))[0][:, :3]), T(P((3,)))), {}),
     None, grad=False)
case("svd_lowrank", lambda: ((T(P((6, 5))),), {"q": 3}), None, grad=False)
case("pca_lowrank", lambda: ((T(P((6, 5))),), {"q": 3}), None, grad=False)
case("top_p_sampling", lambda: ((T(P((2, 8))),), {"ps": 0.9}), None,
     grad=False)

case("affine_grid", lambda: ((T(np.tile(np.array([[1, 0, 0], [0, 1, 0]],
                                                 np.float32), (2, 1, 1))),),
                             {"out_shape": [2, 3, 4, 4]}), None)
case("grid_sample", lambda: ((T(P((1, 2, 4, 4))),
                              T(np.zeros((1, 2, 2, 2), np.float32))), {}),
     None)

# (exemptions)
# ---- op tail (kernels_tail.py)

case("logsigmoid", lambda: ((T(P((3, 4))),), {}),
     lambda x: np.log(_sigmoid(x)))
case("tanh_shrink", lambda: ((T(P((3, 4))),), {}),
     lambda x: x - np.tanh(x))
case("thresholded_relu", lambda: ((T(P((3, 4))),), {"threshold": 0.2}),
     lambda x: np.where(x > 0.2, x, 0.0))
case("rrelu", lambda: ((T(P((3, 4))),), {"training": False}),
     lambda x: np.where(x >= 0, x, x * ((1 / 8 + 1 / 3) / 2)), grad=False)
case("swiglu", lambda: ((T(P((3, 8))),), {}),
     lambda x: (lambda a, b: a * _sigmoid(a) * b)(x[:, :4], x[:, 4:]))
case("mean_all", lambda: ((T(P((3, 4))),), {}), lambda x: x.mean())
case("numel", lambda: ((T(P((3, 4))),), {}), lambda x: np.int64(12))
case("shape", lambda: ((T(P((3, 4))),), {}),
     lambda x: np.asarray([3, 4], np.int32))
case("is_empty", lambda: ((T(P((3, 4))),), {}), lambda x: np.asarray(False))
case("l1_norm", lambda: ((T(off_zero(P((3, 4)))),), {}),
     lambda x: np.abs(x).sum())
case("squared_l2_norm", lambda: ((T(P((3, 4))),), {}),
     lambda x: (x ** 2).sum())
case("frobenius_norm", lambda: ((T(P((3, 4))),), {}),
     lambda x: np.sqrt((x ** 2).sum()))
case("clip_by_norm", lambda: ((T(P((3, 4), 1.0, 2.0)),), {"max_norm": 1.0}),
     lambda x: x / np.sqrt((x ** 2).sum()))
case("fill", lambda: ((T(P((3, 4))),), {"value": 2.5}),
     lambda x: np.full_like(x, 2.5), grad=False)
case("fill_diagonal", lambda: ((T(P((4, 4))),), {"value": 9.0}),
     lambda x: x * (1 - np.eye(4)) + 9.0 * np.eye(4))
case("empty", lambda: ((), {"shape": [2, 3]}), None, grad=False)
case("empty_like", lambda: ((T(P((2, 3))),), {}), None, grad=False)
case("reverse", lambda: ((T(P((3, 4))),), {"axis": 1}),
     lambda x: x[:, ::-1])
case("sequence_mask",
     lambda: ((T(np.asarray([2, 4])),), {"maxlen": 5}),
     lambda x: (np.arange(5)[None] < x[:, None]).astype(np.int64),
     grad=False)
case("share_data", lambda: ((T(P((3, 4))),), {}), lambda x: x)
case("split_with_num", lambda: ((T(P((4, 4))),), {"num": 2}),
     lambda x: x[:2], grad=False)
case("partial_sum",
     lambda: (([T(P((3, 6))), T(P((3, 6)))],), {"start_index": 1,
                                                "length": 3}),
     None, grad=False)
case("partial_concat",
     lambda: (([T(P((3, 6))), T(P((3, 6)))],), {"start_index": 1,
                                                "length": 3}),
     None, grad=False)
case("hinge_loss", lambda: ((T(P((4, 1))), T(np.asarray(
    [[1.0], [0.0], [1.0], [0.0]], np.float32))), {}),
     lambda x, y: np.maximum(1 - x * (2 * y - 1), 0))
case("huber_loss", lambda: ((T(P((3, 4))), T(P((3, 4)))), {"delta": 0.5}),
     lambda x, y: np.where(np.abs(x - y) <= 0.5,
                           0.5 * (x - y) ** 2,
                           0.5 * (np.abs(x - y) - 0.25)))
case("log_loss", lambda: ((T(PP((3, 1)) * 0.8), T(np.asarray(
    [[1.0], [0.0], [1.0]], np.float32))), {}),
     lambda x, y: -y * np.log(x + 1e-4) - (1 - y) * np.log(1 - x + 1e-4))
case("sigmoid_cross_entropy_with_logits",
     lambda: ((T(P((3, 4))), T((rng.rand(3, 4) > 0.5).astype(np.float32))),
              {}),
     lambda x, y: np.maximum(x, 0) - x * y + np.log1p(np.exp(-np.abs(x))))
case("identity_loss", lambda: ((T(P((3, 4))),), {"reduction": 1}),
     lambda x: x.mean())
case("margin_cross_entropy",
     lambda: ((T(P((4, 8), -0.9, 0.9)), T(np.asarray([0, 1, 2, 3]))),
              {"margin1": 1.0, "margin2": 0.0, "margin3": 0.0,
               "scale": 1.0}),
     None, grad=False)
case("accuracy",
     lambda: ((T(P((4, 3))), T(np.asarray([[0, 1, 2]] * 4)),
               T(np.asarray([[0], [5], [1], [9]]))), {}),
     None, grad=False)
case("auc",
     lambda: ((T(PP((16,))), T((rng.rand(16) > 0.5).astype(np.int64))), {}),
     None, grad=False)
case("dirichlet", lambda: ((T(PP((4, 3)) * 3),), {}), None, grad=False)
case("truncated_gaussian_random",
     lambda: ((), {"shape": [64], "mean": 0.0, "std": 1.0}), None,
     grad=False)
case("exponential_", lambda: ((T(P((8, 8))),), {}), None, grad=False)
case("uniform_inplace", lambda: ((T(P((8, 8))),), {}), None, grad=False)
case("gaussian_inplace", lambda: ((T(P((8, 8))),), {}), None, grad=False)
case("fake_quantize_abs_max", lambda: ((T(P((4, 4))),), {}),
     lambda x: np.clip(np.round(x / np.abs(x).max() * 127), -127, 127),
     grad=False)
case("fake_quantize_dequantize_abs_max", lambda: ((T(P((4, 4))),), {}),
     lambda x: np.clip(np.round(x / np.abs(x).max() * 127), -127,
                       127) * np.abs(x).max() / 127, grad=False)
case("fake_channel_wise_quantize_abs_max", lambda: ((T(P((3, 4))),), {}),
     None, grad=False)
case("fake_channel_wise_quantize_dequantize_abs_max",
     lambda: ((T(P((3, 4))),), {}), None, grad=False)
case("fake_dequantize_max_abs",
     lambda: ((T(P((3, 4))), T(np.float32(2.0))), {"max_range": 127.0}),
     lambda x, s: x * 2.0 / 127.0, grad=False)
case("dequantize_abs_max",
     lambda: ((T(P((3, 4))), T(np.float32(2.0))), {"max_range": 127.0}),
     lambda x, s: x * 2.0 / 127.0, grad=False)
case("check_finite_and_unscale_",
     lambda: (([T(P((3, 4))), T(P((2, 2)))], T(np.float32(2.0))), {}),
     None, grad=False)
def _uls_check():
    import paddle_tpu.ops as ops

    # decr_every_n_nan_or_inf=2: first inf step must NOT shrink the scale
    s1, g1, b1 = ops.update_loss_scaling_(
        T(np.float32(1024.0)), T(np.asarray(True)),
        T(np.asarray(5, np.int32)), T(np.asarray(0, np.int32)),
        decr_every_n_nan_or_inf=2)
    assert float(s1._value) == 1024.0 and int(b1._value) == 1
    s2, g2, b2 = ops.update_loss_scaling_(
        s1, T(np.asarray(True)), g1, b1, decr_every_n_nan_or_inf=2)
    assert float(s2._value) == 512.0 and int(b2._value) == 0
    return (T(np.float32(1024.0)), T(np.asarray(False)),
            T(np.asarray(5, np.int32)), T(np.asarray(0, np.int32))), {}


case("update_loss_scaling_", _uls_check, None, grad=False)
case("sgd_",
     lambda: ((T(P((4,))), T(np.float32(0.1)), T(P((4,)))), {}),
     lambda p, lr, g: p - 0.1 * g, grad=False)
case("momentum_",
     lambda: ((T(P((4,))), T(P((4,))), T(P((4,))), T(np.float32(0.1))), {}),
     None, grad=False)
case("adam_",
     lambda: ((T(P((4,))), T(P((4,))), T(P((4,))), T(PP((4,))),
               T(np.float32(0.9)), T(np.float32(0.999)),
               T(np.float32(0.1))), {}),
     None, grad=False)
case("adamw_",
     lambda: ((T(P((4,))), T(P((4,))), T(P((4,))), T(PP((4,))),
               T(np.float32(0.9)), T(np.float32(0.999)),
               T(np.float32(0.1))), {}),
     None, grad=False)
case("adagrad_",
     lambda: ((T(P((4,))), T(P((4,))), T(PP((4,))), T(np.float32(0.1))),
              {}),
     None, grad=False)
case("rmsprop_",
     lambda: ((T(P((4,))), T(P((4,))), T(PP((4,))), T(np.float32(0.1))),
              {}),
     None, grad=False)
case("merged_momentum_",
     lambda: (([T(P((4,))), T(P((3,)))], [T(P((4,))), T(P((3,)))],
               [T(P((4,))), T(P((3,)))], T(np.float32(0.1))), {}),
     None, grad=False)
case("pixel_unshuffle", lambda: ((T(P((1, 2, 4, 4))),),
                                 {"downscale_factor": 2}),
     None)
case("channel_shuffle", lambda: ((T(P((1, 4, 2, 2))),), {"groups": 2}),
     None)
case("shuffle_channel", lambda: ((T(P((1, 4, 2, 2))),), {"groups": 2}),
     None)
case("temporal_shift", lambda: ((T(P((4, 8, 2, 2))),), {"seg_num": 2}),
     None)
case("add_position_encoding", lambda: ((T(P((2, 4, 8))),), {}), None)
case("bilinear",
     lambda: ((T(P((3, 4))), T(P((3, 5))), T(P((2, 4, 5))), T(P((2,)))),
              {}),
     lambda x, y, w, b: np.einsum("bi,oij,bj->bo", x, w, y) + b)
case("affine_channel",
     lambda: ((T(P((2, 3, 2, 2))), T(P((3,))), T(P((3,)))), {}),
     lambda x, s, b: x * s.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1))
case("fused_softmax_mask",
     lambda: ((T(P((2, 2, 3, 4))), T(P((2, 1, 3, 4)) * 0)), {}),
     None)
case("fused_softmax_mask_upper_triangle",
     lambda: ((T(P((2, 2, 4, 4))),), {}), None)
case("gather_tree",
     lambda: ((T(rng.randint(0, 9, (3, 2, 2))),
               T(rng.randint(0, 2, (3, 2, 2)))), {}),
     None, grad=False)
case("pool2d", lambda: ((T(P((1, 2, 4, 4))),),
                        {"kernel_size": 2, "pooling_type": "avg"}),
     None)
case("pool3d", lambda: ((T(P((1, 2, 4, 4, 4))),),
                        {"kernel_size": 2, "pooling_type": "max"}),
     None)
case("lp_pool2d", lambda: ((T(PP((1, 2, 4, 4))),), {"kernel_size": 2}),
     None)
case("max_pool2d_with_index", lambda: ((T(P((1, 2, 4, 4))),),
                                       {"kernel_size": 2}),
     None, grad=False)
case("max_pool3d_with_index", lambda: ((T(P((1, 2, 4, 4, 4))),),
                                       {"kernel_size": 2}),
     None, grad=False)


def _unpool_args():
    x = T(P((1, 1, 4, 4)))
    import paddle_tpu.ops as ops

    v, idx = ops.max_pool2d_with_index(x, kernel_size=2)
    return (v, idx), {"kernel_size": 2}


case("unpool", _unpool_args, None, grad=False)
case("unpool3d", lambda: ((T(P((1, 1, 2, 2, 2))),
                           T(np.arange(8).reshape(1, 1, 2, 2, 2) * 8)),
                          {"kernel_size": 2}),
     None, grad=False)
case("fractional_max_pool2d", lambda: ((T(P((1, 2, 8, 8))),),
                                       {"output_size": 4}),
     None, grad=False)
case("fractional_max_pool3d", lambda: ((T(P((1, 2, 8, 8, 8))),),
                                       {"output_size": 4}),
     None, grad=False)
case("depthwise_conv2d",
     lambda: ((T(P((1, 3, 5, 5))), T(P((3, 1, 3, 3)))), {"padding": 1}),
     None)
case("conv3d_transpose",
     lambda: ((T(P((1, 2, 3, 3, 3))), T(P((2, 2, 2, 2, 2)))),
              {"stride": 2}),
     None, grad=False)
case("depthwise_conv2d_transpose",
     lambda: ((T(P((1, 3, 4, 4))), T(P((3, 1, 2, 2)))), {"stride": 2}),
     None, grad=False)
case("bilinear_interp", lambda: ((T(P((1, 2, 4, 4))),), {"size": (8, 8)}),
     None)
case("nearest_interp", lambda: ((T(P((1, 2, 4, 4))),), {"size": (8, 8)}),
     None)
case("bicubic_interp", lambda: ((T(P((1, 2, 4, 4))),), {"size": (8, 8)}),
     None, grad=False)
case("linear_interp", lambda: ((T(P((1, 2, 8))),), {"size": (16,)}),
     None, grad=False)
case("trilinear_interp", lambda: ((T(P((1, 2, 4, 4, 4))),),
                                  {"size": (8, 8, 8)}),
     None, grad=False)


def _fold_ref(x):
    # inverse of unfold for non-overlapping 2x2 patches on 4x4
    out = np.zeros((1, 1, 4, 4), np.float32)
    cols = x.reshape(1, 1, 2, 2, 2, 2)
    for i in range(2):
        for j in range(2):
            out[:, :, i::2, j::2] += cols[:, :, i, j]
    return out


case("fold", lambda: ((T(P((1, 4, 4))),),
                      {"output_sizes": (4, 4), "kernel_sizes": 2,
                       "strides": 2}),
     _fold_ref)
case("pad3d", lambda: ((T(P((1, 1, 2, 2, 2))),),
                       {"paddings": [1, 1, 0, 0, 0, 0]}),
     lambda x: np.pad(x, [(0, 0), (0, 0), (0, 0), (0, 0), (1, 1)]))
case("frame", lambda: ((T(P((2, 16))),),
                       {"frame_length": 4, "hop_length": 2}),
     None)
case("overlap_add", lambda: ((T(P((2, 4, 7))),), {"hop_length": 4}),
     None)
case("stft", lambda: ((T(P((2, 32))),), {"n_fft": 8}), None, grad=False)
case("fft_c2c",
     lambda: ((T((rng.rand(4, 8) + 1j * rng.rand(4, 8)).astype(
         np.complex64)),), {"axes": [-1]}),
     lambda x: np.fft.fft(x, axis=-1), grad=False)
case("fft_r2c", lambda: ((T(P((4, 8))),), {"axes": [-1]}),
     lambda x: np.fft.rfft(x, axis=-1), grad=False)
case("fft_c2r",
     lambda: ((T((rng.rand(4, 5) + 1j * rng.rand(4, 5)).astype(
         np.complex64)),), {"axes": [-1]}),
     lambda x: np.fft.irfft(x, axis=-1), grad=False)


def _edit_ref(h, r, hl, rl):
    import difflib

    out = []
    for i in range(h.shape[0]):
        a = list(h[i][: hl[i]])
        b = list(r[i][: rl[i]])
        # classic DP
        d = np.zeros((len(a) + 1, len(b) + 1))
        d[:, 0] = np.arange(len(a) + 1)
        d[0, :] = np.arange(len(b) + 1)
        for x in range(1, len(a) + 1):
            for y in range(1, len(b) + 1):
                d[x, y] = min(d[x - 1, y] + 1, d[x, y - 1] + 1,
                              d[x - 1, y - 1] + (a[x - 1] != b[y - 1]))
        out.append(d[-1, -1])
    return np.asarray(out, np.float32)


case("edit_distance",
     lambda: ((T(rng.randint(0, 5, (3, 6))), T(rng.randint(0, 5, (3, 7))),
               T(np.asarray([6, 4, 2])), T(np.asarray([7, 3, 1]))), {}),
     _edit_ref, grad=False)
case("box_coder",
     lambda: ((T(np.asarray([[0., 0., 10., 10.], [5., 5., 9., 9.]],
                            np.float32)),
               T(np.ones((1, 4), np.float32)),
               T(np.asarray([[1., 1., 5., 5.]], np.float32))), {}),
     None, grad=False)
case("prior_box",
     lambda: ((T(P((1, 8, 2, 2))), T(P((1, 3, 16, 16)))),
              {"min_sizes": [4.0], "aspect_ratios": [1.0, 2.0]}),
     None, grad=False)
case("yolo_box",
     lambda: ((T(P((1, 14, 2, 2))),
               T(np.asarray([[64, 64]], np.int32))),
              {"anchors": [10, 13, 16, 30], "class_num": 2}),
     None, grad=False)
case("matrix_rank", lambda: ((T(np.eye(4, dtype=np.float32) * 2),), {}),
     lambda x: np.int64(4), grad=False)


EXEMPT = {
    "_gru_scan": "internal RNN kernel (tests/test_nn_layers.py)",
    "_lstm_scan": "internal RNN kernel (tests/test_nn_layers.py)",
    "_rnn_scan": "internal RNN kernel (tests/test_nn_layers.py)",
    "moe_dispatch": "MoE kernel (tests/test_fleet.py)",
    "moe_combine": "MoE kernel (tests/test_fleet.py)",
    "moe_ep_forward": "shard_map EP exchange, needs a mesh "
                      "(tests/test_fleet.py ep==replicated + HLO audit)",
    "_moe_expert_mm": "MoE kernel (tests/test_fleet.py)",
}


# ---------------------------------------------------------------- the tests

def test_every_op_has_a_case():
    # user-registered custom ops (utils.cpp_extension in other test files)
    # are outside the built-in registry contract
    missing = [
        n for n, op in OPS.items()
        if n not in A and n not in EXEMPT
        and (op.kernel.__module__ or "").startswith(("paddle_tpu.ops",
                                                     "paddle_tpu.distributed"))
    ]
    assert not missing, f"ops without an OpTest case: {sorted(missing)}"


@pytest.mark.parametrize("name", sorted(A))
def test_op_executes(name):
    import paddle_tpu.ops as ops

    args_fn, ref, _ = A[name]
    args, kwargs = args_fn()
    fn = getattr(ops, name, None)
    if fn is None:
        from paddle_tpu.ops.registry import apply_op, get_op

        out = apply_op(get_op(name), *args, **kwargs)
    else:
        out = fn(*args, **kwargs)
    assert out is not None
    if ref is not None:
        np_args = [
            _np(a) for a in args
            if isinstance(a, Tensor)
        ]
        expect = ref(*np_args)
        got = _np(out[0] if isinstance(out, tuple) else out)
        np.testing.assert_allclose(got, expect, rtol=2e-4, atol=2e-5,
                                   err_msg=name)


GRAD_OPS = sorted(n for n, (af, r, g) in A.items()
                  if g and OPS[n].differentiable)


@pytest.mark.parametrize("name", GRAD_OPS)
def test_op_gradient_finite_difference(name):
    """Central finite differences vs the autograd gradient w.r.t. the first
    float tensor input (op_test.py check_grad analog)."""
    import paddle_tpu.ops as ops

    args_fn, _, _ = A[name]
    args, kwargs = args_fn()
    fn = getattr(ops, name)

    target_idx = None
    for i, a in enumerate(args):
        if isinstance(a, Tensor) and np.issubdtype(
                np.asarray(a._value).dtype, np.floating):
            target_idx = i
            break
    if target_idx is None:
        pytest.skip("no float tensor input")
    base = np.asarray(args[target_idx]._value).astype(np.float64)

    def run_loss(arr):
        call = list(args)
        call[target_idx] = T(arr.astype(np.float32))
        out = fn(*call, **kwargs)
        outs = out if isinstance(out, tuple) else (out,)
        total = 0.0
        for o in outs:
            if isinstance(o, Tensor) and np.issubdtype(
                    np.asarray(o._value).dtype, np.floating):
                total = total + float(np.asarray(o._value).sum())
        return total

    # autograd gradient
    call = list(args)
    t = T(base.astype(np.float32))
    t.stop_gradient = False
    call[target_idx] = t
    out = fn(*call, **kwargs)
    outs = out if isinstance(out, tuple) else (out,)
    loss = None
    for o in outs:
        if isinstance(o, Tensor) and np.issubdtype(
                np.asarray(o._value).dtype, np.floating):
            s = o.sum()
            loss = s if loss is None else loss + s
    loss.backward()
    assert t.grad is not None, f"{name}: no gradient"
    g = np.asarray(t.grad._value).astype(np.float64)

    # numeric gradient on a sample of elements
    eps = 1e-3
    flat = base.flatten()
    n_sample = min(flat.size, 6)
    idxs = rng.choice(flat.size, n_sample, replace=False)
    for i in idxs:
        plus = flat.copy()
        minus = flat.copy()
        plus[i] += eps
        minus[i] -= eps
        num = (run_loss(plus.reshape(base.shape))
               - run_loss(minus.reshape(base.shape))) / (2 * eps)
        got = g.flatten()[i]
        denom = max(abs(num), abs(got), 1.0)
        assert abs(num - got) / denom < 5e-2, (
            f"{name}: grad mismatch at {i}: numeric {num:.5f} vs "
            f"autograd {got:.5f}")


def test_tail_op_regressions():
    """Behaviors found by review: axis=0 frame/overlap_add layout,
    non-square yolo_box, conv3d_transpose output_padding/groups, default
    sequence_mask."""
    import paddle_tpu.ops as ops

    x = T(P((16, 2)))
    f = ops.frame(x, frame_length=4, hop_length=2, axis=0)
    assert f.shape == [7, 4, 2], f.shape
    back = ops.overlap_add(f, hop_length=4, axis=0)
    assert back.shape[0] == (7 - 1) * 4 + 4

    # non-square grid: width normalized by w, height by h
    z = T(np.zeros((1, 7, 1, 2), np.float32))  # logits 0 -> exp() = 1
    boxes, _ = ops.yolo_box(z, T(np.asarray([[32, 64]], np.int32)),
                            anchors=[16, 16], class_num=2,
                            downsample_ratio=32, clip_bbox=False)
    b = np.asarray(boxes._value).reshape(-1, 4)
    w_norm = (b[0, 2] - b[0, 0]) / 64.0   # img_w = 64
    h_norm = (b[0, 3] - b[0, 1]) / 32.0   # img_h = 32
    np.testing.assert_allclose(w_norm, 16 / (32 * 2), rtol=1e-5)
    np.testing.assert_allclose(h_norm, 16 / (32 * 1), rtol=1e-5)

    out = ops.conv3d_transpose(T(P((1, 2, 3, 3, 3))), T(P((2, 2, 2, 2, 2))),
                               stride=2, output_padding=1)
    assert out.shape[2:] == [7, 7, 7], out.shape
    g = ops.conv3d_transpose(T(P((1, 4, 3, 3, 3))), T(P((4, 1, 2, 2, 2))),
                             stride=2, groups=2)
    assert g.shape[1] == 2, g.shape

    m = ops.sequence_mask(T(np.asarray([2, 4])))  # default maxlen
    assert m.shape == [2, 4]
