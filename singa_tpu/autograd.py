"""Define-by-run autograd over jnp.

Reference parity: python/singa/autograd.py — `Operator` base (autograd.py:227)
records `(creator, x_id, y, stores_grad)` per input (:285-294);
`infer_dependency` counts consumer edges (:71-102); `backward()` is a
*generator* doing reverse BFS with multi-consumer grad accumulation, yielding
`(param, grad)` as soon as ready (:128-224) so the optimizer can overlap
gradient communication with the rest of backward; `Dummy` wraps leaves (:344).

TPU-native redesign: operator forwards are pure jnp/lax functions, so the
backward rule of almost every op is derived mechanically with `jax.vjp` at
record time instead of ~90 hand-written rules; fused/hand rules are kept only
where the math matters (softmax-CE). The whole tape runs under `jax.jit`
tracing unchanged — Model's graph mode simply traces one step (model.py).
"""

from __future__ import annotations

import contextlib
import math
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax._src import source_info_util   # the name stack has no public reader

from .tensor import Tensor
from . import observe
from . import tensor as tensor_module

#: global train/eval switch (ref autograd.py `training`)
training = False


def _raw(x):
    return x.data if isinstance(x, Tensor) else x


def _is_float0(a):
    return getattr(a, "dtype", None) == jax.dtypes.float0


# ---- program scopes -------------------------------------------------------
# Every instruction of a traced program carries, in its `op_name`, the path
# of the layers it was recorded under: `Layer.__call__` opens
# `jax.named_scope(<the attribute name get_params() keys the layer by>)`.
# An operator remembers jax's own name stack as it is recorded, so that
# `backward()` below can run its backward rule under the same path again,
# behind a leading `bwd`.

#: leading scope of a recomputed `Region`'s second forward
RECOMPUTE_SCOPE = "recompute"


def _current_scope():
    """jax's name stack here, as the `/`-joined path an `op_name` shows,
    less the leading `recompute` of a replayed region: an operator recorded
    in a replay runs its backward under the scope of the first forward."""
    path = str(source_info_util.current_name_stack())
    if path == RECOMPUTE_SCOPE:
        return ""
    return path.removeprefix(RECOMPUTE_SCOPE + "/")


def _in_replay():
    """Whether this is traced in a `Region`'s second forward."""
    path = str(source_info_util.current_name_stack())
    return (path + "/").startswith(RECOMPUTE_SCOPE + "/")


class Operator:
    """Base op. Subclasses implement `forward(self, *arrays) -> array|tuple`.

    Default backward is the vjp of `forward` captured at record time;
    override `backward(self, *dys)` for fused rules.
    """

    #: class-level: op can never produce gradients (comparisons, casts, ...)
    never_requires_grad = False
    #: class-level: recorded outside any scope, the operator's name is one
    scope_by_name = True

    def __init__(self, name: str | None = None):
        self.name = name or self.__class__.__name__
        self.src = []          # [(src_op, x_id, x_tensor, x_stores_grad)]
        self.y_id2idx = {}     # id(output tensor) -> output index
        self.requires_grad = True
        self._vjp = None
        self._n_out = 1

    def __call__(self, *xs):
        return self._do_forward(*xs)

    def _do_forward(self, *xs):
        assert all(isinstance(x, Tensor) for x in xs), \
            f"{self.name} inputs must be Tensor, got {[type(x) for x in xs]}"
        device = xs[0].device

        if training and not self.never_requires_grad:
            self.requires_grad = any(x.requires_grad for x in xs)
        else:
            self.requires_grad = False

        # the scope this operator is recorded under, for backward(): the
        # layer's, or outside any layer the operator's own name
        self._scope = _current_scope()
        outside = not self._scope and self.scope_by_name
        if outside:
            self._scope = self.name
        raw = [x.data for x in xs]
        with jax.named_scope(self.name) if outside \
                else contextlib.nullcontext():
            if self.requires_grad:
                for x in xs:
                    if x.creator is None:
                        x.creator = Dummy(x)
                    self.src.append((x.creator, id(x), x, x.stores_grad))
                if type(self).backward is Operator.backward:
                    ys, self._vjp = jax.vjp(self.forward, *raw)
                else:
                    ys = self.forward(*raw)
            else:
                ys = self.forward(*raw)

        single = not isinstance(ys, tuple)
        if single:
            ys = (ys,)
        self._n_out = len(ys)
        self._out_shapes = [(y.shape, y.dtype) for y in ys]
        outs = []
        for i, y in enumerate(ys):
            t = Tensor(data=y, device=device,
                       requires_grad=self.requires_grad,
                       creator=self if self.requires_grad else None)
            self.y_id2idx[id(t)] = i
            outs.append(t)
        return outs[0] if single else tuple(outs)

    def forward(self, *xs):
        raise NotImplementedError

    def backward(self, *dys):
        """Default: vjp-derived. dys are raw arrays aligned with outputs
        (missing cotangents already zero-filled by the engine)."""
        assert self._vjp is not None, f"{self.name} has no recorded vjp"
        dxs = self._vjp(dys[0] if self._n_out == 1 else tuple(dys))
        return dxs if len(dxs) > 1 else dxs[0]


class Dummy(Operator):
    """Leaf placeholder (ref autograd.py:344): wraps a parameter/input."""

    def __init__(self, tensor: Tensor, name=None):
        super().__init__(name or "Dummy")
        self.tensor = tensor
        self.y_id2idx = {id(tensor): 0}
        self.requires_grad = tensor.requires_grad
        self._n_out = 1


def infer_dependency(op: Operator):
    """Count pending consumer edges per op (ref autograd.py:71-102)."""
    counts = {op: 0}
    queue = deque([op])
    while queue:
        cur = queue.popleft()
        for src_op, _, _, _ in cur.src:
            if src_op.requires_grad:
                if src_op in counts:
                    counts[src_op] += 1
                else:
                    counts[src_op] = 1
                    queue.append(src_op)
    return counts


def backward(y: Tensor, dy=None):
    """Reverse-mode pass from scalar/tensor `y`; GENERATOR yielding
    `(param_tensor, grad_tensor)` as each param's grad is finalized
    (ref autograd.py:128-224). This incremental yield is what lets DistOpt
    start all-reducing late-layer grads while early-layer backward runs.
    """
    assert y.creator is not None, "call backward on a tape output in training mode"
    dependency = infer_dependency(y.creator)
    if dy is None:
        dy = jnp.ones(y.shape, dtype=y.dtype)
    else:
        dy = _raw(dy)

    not_ready = {}  # op -> [grad per output]
    # seed the cotangent into the slot of THIS output (a multi-output op's
    # backward may start from any of its outputs)
    seed = [None] * y.creator._n_out
    seed[y.creator.y_id2idx.get(id(y), 0)] = dy
    ready = deque([(y.creator, seed)])
    visited = {y.creator}

    while ready:
        op, dys = ready.popleft()
        if isinstance(op, Dummy):
            continue
        # the operator's backward goes under the scope it was recorded in,
        # behind `bwd`: a rule called from this loop would otherwise read
        # like whatever drives the generator (the optimizer's update), and
        # the vjp-derived one names itself `transpose(jvp())` and no more
        bwd_scope = "bwd/" + op._scope if op._scope else "bwd"
        with jax.named_scope(bwd_scope):
            # zero-fill output cotangents that never received a gradient
            filled = [dys[i] if i < len(dys) and dys[i] is not None
                      else jnp.zeros(s, d)
                      for i, (s, d) in enumerate(op._out_shapes)]
            dxs = op.backward(*filled)
        if not isinstance(dxs, (tuple, list)):
            dxs = (dxs,)
        assert len(dxs) == len(op.src), \
            f"{op.name}: {len(dxs)} grads for {len(op.src)} inputs"

        for (src_op, x_id, x_tensor, x_stores_grad), dx in zip(op.src, dxs):
            if not src_op.requires_grad:
                continue
            if dx is not None and not _is_float0(dx):
                y_idx = src_op.y_id2idx[x_id]
                slots = not_ready.setdefault(src_op, [None] * src_op._n_out)
                if slots[y_idx] is None:
                    slots[y_idx] = dx
                else:
                    with jax.named_scope(bwd_scope):
                        slots[y_idx] = slots[y_idx] + dx
            dependency[src_op] -= 1
            if dependency[src_op] == 0:
                # Completion is uniform regardless of whether the LAST edge
                # carried a real cotangent or a None/float0 one — a Dummy
                # param still yields the grads accumulated from its other
                # consumers, and an op queued with partial slots zero-fills
                # the rest (so upstream params never stall).
                slots = not_ready.pop(src_op, None)
                if isinstance(src_op, Dummy):
                    if x_stores_grad and slots is not None \
                            and slots[0] is not None:
                        yield (x_tensor,
                               Tensor(data=slots[0], device=x_tensor.device,
                                      requires_grad=False))
                elif src_op not in visited:
                    visited.add(src_op)
                    ready.append((src_op,
                                  slots if slots is not None else []))


def gradients(y: Tensor, dy=None):
    """Run full backward; return {param_tensor: grad_tensor} (ref :105)."""
    grads = {}
    for p, g in backward(y, dy):
        grads[p] = g
    return grads


# ======================= operator zoo =====================================
# Class names and functional wrappers match the reference inventory
# (SURVEY.md §2.8, python/singa/autograd.py). Forwards are jnp; backward is
# vjp-derived unless overridden.


def _functional(op_cls):
    def f(*xs, **kwargs):
        return op_cls(**kwargs)(*xs)
    f.__name__ = op_cls.__name__.lower()
    return f


# ---- arithmetic / logic --------------------------------------------------

class Add(Operator):
    def forward(self, a, b):
        return a + b


class Sub(Operator):
    def forward(self, a, b):
        return a - b


class Mul(Operator):
    def forward(self, a, b):
        return a * b


class Div(Operator):
    def forward(self, a, b):
        return a / b


class Pow(Operator):
    def forward(self, a, b):
        return jnp.power(a, b)


class Negative(Operator):
    def forward(self, x):
        return -x


class Reciprocal(Operator):
    def forward(self, x):
        return 1.0 / x


class Abs(Operator):
    def forward(self, x):
        return jnp.abs(x)


class Sign(Operator):
    never_requires_grad = True

    def forward(self, x):
        return jnp.sign(x)


class Exp(Operator):
    def forward(self, x):
        return jnp.exp(x)


class Log(Operator):
    def forward(self, x):
        return jnp.log(x)


class Sqrt(Operator):
    def forward(self, x):
        return jnp.sqrt(x)


class _BoolBinary(Operator):
    never_requires_grad = True
    _fn = None

    def forward(self, a, b):
        return type(self)._fn(a.astype(bool), b.astype(bool)).astype(jnp.float32)


class And(_BoolBinary):
    _fn = staticmethod(jnp.logical_and)


class Or(_BoolBinary):
    _fn = staticmethod(jnp.logical_or)


class Xor(_BoolBinary):
    _fn = staticmethod(jnp.logical_xor)


class Not(Operator):
    never_requires_grad = True

    def forward(self, x):
        return jnp.logical_not(x.astype(bool)).astype(jnp.float32)


class _CmpBinary(Operator):
    never_requires_grad = True
    _fn = None

    def forward(self, a, b):
        return type(self)._fn(a, b).astype(jnp.float32)


class Less(_CmpBinary):
    _fn = staticmethod(jnp.less)


class Greater(_CmpBinary):
    _fn = staticmethod(jnp.greater)


class Equal(_CmpBinary):
    _fn = staticmethod(jnp.equal)


# ---- activations ---------------------------------------------------------

class ReLU(Operator):
    def forward(self, x):
        return jax.nn.relu(x)


class LeakyRelu(Operator):
    def __init__(self, a=0.01):
        super().__init__()
        self.a = a

    def forward(self, x):
        return jax.nn.leaky_relu(x, self.a)


class Elu(Operator):
    def __init__(self, alpha=1.0):
        super().__init__()
        self.alpha = alpha

    def forward(self, x):
        return jax.nn.elu(x, self.alpha)


class SeLU(Operator):
    def __init__(self, alpha=1.67326, gamma=1.0507):
        super().__init__()
        self.alpha, self.gamma = alpha, gamma

    def forward(self, x):
        return self.gamma * jnp.where(x > 0, x,
                                      self.alpha * (jnp.exp(x) - 1.0))


class PRelu(Operator):
    def forward(self, x, slope):
        return jnp.where(x > 0, x, slope * x)


class Sigmoid(Operator):
    def forward(self, x):
        return jax.nn.sigmoid(x)


class HardSigmoid(Operator):
    def __init__(self, alpha=0.2, gamma=0.5):
        super().__init__()
        self.alpha, self.gamma = alpha, gamma

    def forward(self, x):
        return jnp.clip(self.alpha * x + self.gamma, 0.0, 1.0)


class SoftMax(Operator):
    def __init__(self, axis=1):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        return jax.nn.softmax(x, axis=self.axis)


class SoftPlus(Operator):
    def forward(self, x):
        return jax.nn.softplus(x)


class SoftSign(Operator):
    def forward(self, x):
        return x / (1.0 + jnp.abs(x))


class Tanh(Operator):
    def forward(self, x):
        return jnp.tanh(x)


def _trig(name, fn):
    cls = type(name, (Operator,),
               {"forward": (lambda self, x, _f=fn: _f(x))})
    return cls


Cos = _trig("Cos", jnp.cos)
Cosh = _trig("Cosh", jnp.cosh)
Acos = _trig("Acos", jnp.arccos)
Acosh = _trig("Acosh", jnp.arccosh)
Sin = _trig("Sin", jnp.sin)
Sinh = _trig("Sinh", jnp.sinh)
Asin = _trig("Asin", jnp.arcsin)
Asinh = _trig("Asinh", jnp.arcsinh)
Tan = _trig("Tan", jnp.tan)
Atan = _trig("Atan", jnp.arctan)
Atanh = _trig("Atanh", jnp.arctanh)
Erf = _trig("Erf", jax.scipy.special.erf)


# ---- shape / indexing ----------------------------------------------------

class Reshape(Operator):
    def __init__(self, shape):
        super().__init__()
        self.shape = tuple(int(s) for s in shape)

    def forward(self, x):
        shape = self.shape
        if -1 in shape:
            known = -int(np.prod(shape))
            shape = tuple(int(x.size // known) if s == -1 else s for s in shape)
        return x.reshape(shape)


class Flatten(Operator):
    def __init__(self, axis=1):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        a = self.axis if self.axis >= 0 else x.ndim + self.axis
        lead = int(np.prod(x.shape[:a])) if a > 0 else 1
        return x.reshape(lead, -1)


class Squeeze(Operator):
    def __init__(self, axis=None):
        super().__init__()
        self.axis = tuple(axis) if isinstance(axis, (list, tuple)) else axis

    def forward(self, x):
        return jnp.squeeze(x, axis=self.axis)


class Unsqueeze(Operator):
    def __init__(self, axis):
        super().__init__()
        self.axis = axis if isinstance(axis, (list, tuple)) else [axis]

    def forward(self, x):
        for a in sorted(self.axis):
            x = jnp.expand_dims(x, a)
        return x


class Flip(Operator):
    def __init__(self, axis=0):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        return jnp.flip(x, axis=self.axis)


def flip(x, axis=0):
    return Flip(axis)(x)


class Transpose(Operator):
    def __init__(self, perm=None):
        super().__init__()
        self.perm = tuple(perm) if perm is not None else None

    def forward(self, x):
        return jnp.transpose(x, self.perm)


class Concat(Operator):
    def __init__(self, axis=0):
        super().__init__()
        self.axis = axis

    def forward(self, *xs):
        return jnp.concatenate(xs, axis=self.axis)


class Slice(Operator):
    def __init__(self, starts, ends, axes=None, steps=None):
        super().__init__()
        self.starts, self.ends = list(starts), list(ends)
        self.axes = list(axes) if axes is not None else list(range(len(starts)))
        self.steps = list(steps) if steps is not None else [1] * len(starts)

    def forward(self, x):
        import builtins
        idx = [builtins.slice(None)] * x.ndim
        for s, e, a, st in zip(self.starts, self.ends, self.axes, self.steps):
            dim = x.shape[a]
            e = builtins.min(e, dim) if e >= 0 else e
            idx[a] = builtins.slice(s, e, st)
        return x[tuple(idx)]


class Split(Operator):
    def __init__(self, axis, parts):
        super().__init__()
        self.axis, self.parts = axis, list(parts)

    def forward(self, x):
        offs = np.cumsum([0] + self.parts)
        return tuple(lax.slice_in_dim(x, int(offs[i]), int(offs[i + 1]),
                                      axis=self.axis)
                     for i in range(len(self.parts)))


class Gather(Operator):
    def __init__(self, axis, indices):
        super().__init__()
        self.axis = axis
        self.indices = jnp.asarray(indices, dtype=jnp.int32)

    def forward(self, x):
        return jnp.take(x, self.indices, axis=self.axis)


class Tile(Operator):
    def __init__(self, repeats):
        super().__init__()
        self.repeats = tuple(repeats)

    def forward(self, x):
        return jnp.tile(x, self.repeats)


class Expand(Operator):
    def __init__(self, shape):
        super().__init__()
        self.shape = tuple(shape)

    def forward(self, x):
        return jnp.broadcast_to(x, jnp.broadcast_shapes(x.shape, self.shape))


class Pad(Operator):
    def __init__(self, mode, pads, constant=0.0):
        super().__init__()
        self.mode = {"constant": "constant", "reflect": "reflect",
                     "edge": "edge"}[mode]
        self.pads = list(pads)
        self.constant = constant

    def forward(self, x):
        n = x.ndim
        width = [(int(self.pads[i]), int(self.pads[i + n])) for i in range(n)]
        if self.mode == "constant":
            return jnp.pad(x, width, mode="constant",
                           constant_values=self.constant)
        return jnp.pad(x, width, mode=self.mode)


class UpSample(Operator):
    def __init__(self, scales, mode="nearest"):
        super().__init__()
        self.scales = [float(s) for s in scales]
        assert mode == "nearest", "only nearest upsample supported"

    def forward(self, x):
        for a, s in enumerate(self.scales):
            if s != 1.0:
                x = jnp.repeat(x, int(s), axis=a)
        return x


class DepthToSpace(Operator):
    def __init__(self, blocksize, mode="DCR"):
        super().__init__()
        self.b, self.mode = blocksize, mode

    def forward(self, x):
        n, c, h, w = x.shape
        b = self.b
        if self.mode == "DCR":
            y = x.reshape(n, b, b, c // (b * b), h, w)
            y = jnp.transpose(y, (0, 3, 4, 1, 5, 2))
        else:  # CRD
            y = x.reshape(n, c // (b * b), b, b, h, w)
            y = jnp.transpose(y, (0, 1, 4, 2, 5, 3))
        return y.reshape(n, c // (b * b), h * b, w * b)


class SpaceToDepth(Operator):
    def __init__(self, blocksize):
        super().__init__()
        self.b = blocksize

    def forward(self, x):
        n, c, h, w = x.shape
        b = self.b
        y = x.reshape(n, c, h // b, b, w // b, b)
        y = jnp.transpose(y, (0, 3, 5, 1, 2, 4))
        return y.reshape(n, c * b * b, h // b, w // b)


class Shape(Operator):
    never_requires_grad = True

    def forward(self, x):
        return jnp.asarray(x.shape, dtype=jnp.int64)


class NonZero(Operator):
    never_requires_grad = True

    def forward(self, x):
        # NOTE: data-dependent shape -> host fallback; not jittable. Matches
        # reference which also computes this on concrete tensors.
        return jnp.asarray(np.array(np.nonzero(np.asarray(x))), dtype=jnp.int64)


class Cast(Operator):
    never_requires_grad = True

    def __init__(self, to):
        super().__init__()
        self.to = to

    def forward(self, x):
        from .tensor import _resolve_dtype
        return x.astype(_resolve_dtype(self.to))


class OneHot(Operator):
    never_requires_grad = True

    def __init__(self, depth, values=(0.0, 1.0), axis=-1):
        super().__init__()
        self.depth, self.values, self.axis = depth, values, axis

    def forward(self, idx):
        off, on = self.values
        oh = jax.nn.one_hot(idx.astype(jnp.int32), self.depth, axis=self.axis)
        return oh * (on - off) + off


class ConstantOfShape(Operator):
    never_requires_grad = True

    def __init__(self, value=0.0, dtype=jnp.float32):
        super().__init__()
        self.value, self.dtype = value, dtype

    def forward(self, shape):
        return jnp.full(tuple(int(s) for s in np.asarray(shape)), self.value,
                        dtype=self.dtype)


class ScatterElements(Operator):
    def __init__(self, indices, axis=0):
        super().__init__()
        self.indices = jnp.asarray(indices, dtype=jnp.int32)
        self.axis = axis

    def forward(self, x, updates):
        return jnp.put_along_axis(x, self.indices, updates, axis=self.axis,
                                  inplace=False)


class Where(Operator):
    def __init__(self, condition):
        super().__init__()
        self.condition = _raw(condition).astype(bool)

    def forward(self, a, b):
        return jnp.where(self.condition, a, b)


class Ceil(Operator):
    never_requires_grad = True

    def forward(self, x):
        return jnp.ceil(x)


class Floor(Operator):
    never_requires_grad = True

    def forward(self, x):
        return jnp.floor(x)


class Round(Operator):
    never_requires_grad = True

    def forward(self, x):
        return jnp.round(x)


class Rounde(Operator):
    """Round half to even (ref autograd.py:5620)."""
    never_requires_grad = True

    def forward(self, x):
        return jnp.round(x)  # numpy/jnp round IS half-to-even


class Clip(Operator):
    def __init__(self, min=None, max=None):  # noqa: A002
        super().__init__()
        self.min, self.max = min, max

    def forward(self, x):
        return jnp.clip(x, self.min, self.max)


class Identity(Operator):
    def forward(self, x):
        return x


# ---- reductions ----------------------------------------------------------

class Mean(Operator):
    def forward(self, *xs):
        import builtins
        return builtins.sum(xs) / len(xs)


class Sum(Operator):
    def forward(self, *xs):
        out = xs[0]
        for x in xs[1:]:
            out = out + x
        return out


class Min(Operator):
    def forward(self, a, b):
        return jnp.minimum(a, b)


class Max(Operator):
    def forward(self, a, b):
        return jnp.maximum(a, b)


class ReduceSum(Operator):
    def __init__(self, axes=None, keepdims=True):
        super().__init__()
        self.axes = tuple(axes) if axes is not None else None
        self.keepdims = bool(keepdims)

    def forward(self, x):
        return jnp.sum(x, axis=self.axes, keepdims=self.keepdims)


class ReduceMean(Operator):
    def __init__(self, axes=None, keepdims=True):
        super().__init__()
        self.axes = tuple(axes) if axes is not None else None
        self.keepdims = bool(keepdims)

    def forward(self, x):
        return jnp.mean(x, axis=self.axes, keepdims=self.keepdims)


# ---- linear algebra ------------------------------------------------------

class Matmul(Operator):
    def __init__(self, out_dtype=None):
        super().__init__()
        self.out_dtype = out_dtype

    def forward(self, a, b):
        # out_dtype="float32" with bf16 inputs: MXU accumulates fp32
        # anyway, so requesting a fp32 result is free and saves the
        # downstream upcast pass (loss heads under the amp policy)
        return jnp.matmul(a, b, preferred_element_type=self.out_dtype)


class Gemm(Operator):
    def __init__(self, alpha=1.0, beta=1.0, transA=0, transB=0):
        super().__init__()
        self.alpha, self.beta = alpha, beta
        self.transA, self.transB = transA, transB

    def forward(self, A, B, C=None):
        if self.transA:
            A = A.T
        if self.transB:
            B = B.T
        y = self.alpha * (A @ B)
        if C is not None:
            y = y + self.beta * C
        return y


class AddBias(Operator):
    def __init__(self, axis=0):
        super().__init__()
        self.axis = axis

    def forward(self, x, b):
        if self.axis == 0:
            return x + b  # per-column bias (broadcast over rows)
        return x + b[:, None]


class CosSim(Operator):
    def forward(self, a, b):
        num = jnp.sum(a * b, axis=-1)
        den = jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1)
        return num / den


# ---- losses --------------------------------------------------------------

class MeanSquareError(Operator):
    def forward(self, x, t):
        # ref autograd.py:1334: 0.5 * ||x-t||^2 / batch
        return 0.5 * jnp.sum(jnp.square(x - t)) / x.shape[0]


class CrossEntropy(Operator):
    """CE on probabilities (ref autograd.py:1212)."""

    def forward(self, p, t):
        eps = 1e-10
        return -jnp.sum(t * jnp.log(p + eps)) / p.shape[0]


class BinaryCrossEntropy(Operator):
    def forward(self, x, t):
        eps = 1e-10
        per = -(t * jnp.log(x + eps) + (1 - t) * jnp.log(1 - x + eps))
        return jnp.sum(per) / x.shape[0]


class RankingLoss(Operator):
    def __init__(self, M=0.2):
        super().__init__()
        self.M = M

    def forward(self, pos, neg):
        return jnp.mean(jnp.maximum(self.M - (pos - neg), 0.0))


def cross_entropy_path(logits, targets):
    """(the targets' kind, where the backward's log-sum-exp comes from) of a
    softmax cross-entropy recorded here, as `singa_cross_entropy` names
    them: with class indices the backward reads the forward's, which inside
    a `Region` is the second forward's; a distribution's takes the softmax
    anew."""
    if not tensor_module.targets_are_indices(logits, targets):
        return "dense", "rebuilt"
    return "integer", "rebuilt" if _in_replay() else "kept"


class SoftMaxCrossEntropy(Operator):
    """Fused stable softmax-CE with a HAND backward (ref: C++ fused
    CrossEntropyFwd/Bwd tensor.h:625-637 for exactly this reason). Logits of
    any rank; the mean is over all leading axes. With class-index targets
    the backward reads the forward's log-sum-exp. A third input `w`, one
    weight a row (the logits' leading axes), makes the loss
    sum(w * CE) / rows: still over ALL rows, a row of weight 0 among them
    (the objective of masked diffusion: 1 / rate on the masked positions,
    0 elsewhere). It takes no gradient."""

    def __init__(self):
        super().__init__()
        self._cache = None

    def forward(self, x, t, w=None):
        self._in_dtype = x.dtype
        x = x.astype(jnp.float32)  # fp32 island under bf16 compute policy
        lse = tensor_module.softmax_lse(x)
        self._cache = (x, t, lse, w)
        self._path = cross_entropy_path(x, t)
        ce = tensor_module.softmax_cross_entropy_fwd(x, t, lse)
        return jnp.mean(ce if w is None else ce * w.astype(jnp.float32))

    def backward(self, dy):
        x, t, lse, w = self._cache
        observe.record_cross_entropy(*self._path)
        # mean is over ALL leading dims (per-token for 3D logits), so the
        # scale is prod(x.shape[:-1]), not just the batch dim
        n = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
        dx = tensor_module.softmax_cross_entropy_bwd(x, t, lse) * (dy / n)
        if w is None:
            return dx.astype(self._in_dtype), None  # no grad for targets
        dx = dx * w.astype(jnp.float32)[..., None]
        return dx.astype(self._in_dtype), None, None


# ---- NN ops (handle-backed in the reference, §2.6) -----------------------

class _Conv2d(Operator):
    """Convolution; replaces CudnnConvHandle (convolution.h:105) with
    lax.conv_general_dilated which XLA tiles onto the MXU."""

    def __init__(self, stride=(1, 1), padding=(0, 0), group=1,
                 odd_padding=None, dilation=(1, 1)):
        super().__init__()
        self.stride = tuple(stride)
        self.padding = tuple(padding)
        self.group = group
        self.odd_padding = odd_padding  # (l, r, t, b) extra pad for "same"
        self.dilation = tuple(dilation)

    def forward(self, x, W, b=None):
        ph, pw = self.padding
        pad = [(ph, ph), (pw, pw)]
        if self.odd_padding is not None:
            l, r, t, bt = self.odd_padding
            pad = [(ph + t, ph + bt), (pw + l, pw + r)]
        y = lax.conv_general_dilated(
            x, W, window_strides=self.stride, padding=pad,
            rhs_dilation=self.dilation,
            feature_group_count=self.group,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            preferred_element_type=jnp.float32 if x.dtype == jnp.float32 else None)
        if b is not None:
            y = y + b[None, :, None, None]
        return y


class _BatchNorm2d(Operator):
    """Train-mode BN: normalizes with batch stats; grads flow through them.
    Replaces CudnnBatchNormHandle (batchnorm.cc). Running-stat updates are
    computed functionally by `batchnorm_2d` below (XLA CSEs the duplicate
    mean/var with the in-op ones under jit)."""

    def __init__(self, eps=1e-5):
        super().__init__()
        self.eps = eps

    def forward(self, x, gamma, beta):
        axes = (0, 2, 3) if x.ndim == 4 else (0,)
        xf = x.astype(jnp.float32)  # fp32 island under bf16 compute policy
        m = jnp.mean(xf, axis=axes)
        v = jnp.var(xf, axis=axes)
        shape = (1, -1, 1, 1) if x.ndim == 4 else (1, -1)
        xn = (xf - m.reshape(shape)) * lax.rsqrt(v.reshape(shape) + self.eps)
        return (xn * gamma.reshape(shape)
                + beta.reshape(shape)).astype(x.dtype)


class _BatchNorm2dInfer(Operator):
    def __init__(self, eps=1e-5):
        super().__init__()
        self.eps = eps

    def forward(self, x, gamma, beta, mean, var):
        shape = (1, -1, 1, 1) if x.ndim == 4 else (1, -1)
        xn = (x - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + self.eps)
        return xn * gamma.reshape(shape) + beta.reshape(shape)


class _Pooling2d(Operator):
    """Max/avg pooling via lax.reduce_window (replaces CudnnPoolingHandle)."""

    def __init__(self, kernel, stride, padding=(0, 0), is_max=True,
                 count_include_pad=False, odd_padding=None):
        super().__init__()
        self.kernel = tuple(kernel)
        self.stride = tuple(stride)
        self.padding = tuple(padding)
        self.is_max = is_max
        self.count_include_pad = count_include_pad
        self.odd_padding = odd_padding  # (l, r, t, b) extra for SAME modes

    def forward(self, x):
        kh, kw = self.kernel
        sh, sw = self.stride
        ph, pw = self.padding
        dims = (1, 1, kh, kw)
        strides = (1, 1, sh, sw)
        if self.odd_padding is not None:
            l, r, t, b = self.odd_padding
            pads = ((0, 0), (0, 0), (ph + t, ph + b), (pw + l, pw + r))
        else:
            pads = ((0, 0), (0, 0), (ph, ph), (pw, pw))
        if self.is_max:
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
                else jnp.iinfo(x.dtype).min
            return lax.reduce_window(x, init, lax.max, dims, strides, pads)
        s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
        if self.count_include_pad or all(p == (0, 0) for p in pads[2:]):
            return s / (kh * kw)
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pads)
        return s / cnt


class GlobalAveragePool(Operator):
    def forward(self, x):
        return jnp.mean(x, axis=tuple(range(2, x.ndim)), keepdims=True)


class Dropout(Operator):
    def __init__(self, ratio=0.5, key=None):
        super().__init__()
        self.ratio = ratio
        self.key = key

    def forward(self, x):
        if not training or self.ratio == 0.0:
            return x
        assert self.key is not None, "Dropout needs a PRNG key in training"
        keep = 1.0 - self.ratio
        mask = jax.random.bernoulli(self.key, keep, x.shape)
        return jnp.where(mask, x / keep, jnp.zeros_like(x))


class Embedding(Operator):
    """Row gather; vjp yields scatter-add grad for the table
    (ref autograd.py:5648).

    The ids are a REAL tape input (int32, never differentiated), not a
    captured constant — so ONNX export sees them as a graph edge and an
    exported model takes its token ids as input instead of replaying the
    trace batch."""

    def forward(self, ids, table):
        return jnp.take(table, ids, axis=0)


class LayerNorm(Operator):
    """Normalize over the last axis (no reference counterpart — SINGA has
    no transformer ops; required by the attention stack)."""

    def __init__(self, eps=1e-5):
        super().__init__()
        self.eps = eps

    def forward(self, x, gamma, beta):
        # fp32 island under the bf16 compute policy: variance in low
        # precision is catastrophically lossy
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, axis=-1, keepdims=True)
        v = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - m) * lax.rsqrt(v + self.eps) * gamma + beta
        return y.astype(x.dtype)


class Gelu(Operator):
    def forward(self, x):
        return jax.nn.gelu(x)


class RMSNorm(Operator):
    """x / rms(x) * gamma over the last axis, no mean and no shift."""

    def __init__(self, eps=1e-6):
        super().__init__()
        self.eps = eps

    def forward(self, x, gamma):
        xf = x.astype(jnp.float32)     # fp32 island, as LayerNorm
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return (xf * lax.rsqrt(ms + self.eps) * gamma).astype(x.dtype)


class SwiGLU(Operator):
    """silu(gate) * up: the product inside a gated feed-forward."""

    def forward(self, gate, up):
        return jax.nn.silu(gate) * up


def axis_bound(name: str) -> bool:
    """True iff mesh axis `name` is bound in the current trace (i.e. we
    are inside a shard_map over it)."""
    try:
        lax.axis_size(name)
        return True
    except Exception:
        return False


class _TPCopy(Operator):
    """Megatron's `f`: identity forward, psum backward over the TP axis.
    Applied to the replicated input of a column-parallel matmul so dL/dx
    sums each shard's contribution (tp.py docstring; no reference
    counterpart — SINGA is data-parallel only, SURVEY.md §2.3)."""

    def __init__(self, axis):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        return x

    def backward(self, dy):
        return lax.psum(dy, self.axis)


class _TPReduce(Operator):
    """Megatron's `g`: psum forward over the TP axis, identity backward.
    Applied to the partial output of a row-parallel matmul."""

    def __init__(self, axis):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        return lax.psum(x, self.axis)

    def backward(self, dy):
        return dy


def tp_copy(x, axis):
    return _TPCopy(axis)(x)


def tp_reduce(x, axis):
    return _TPReduce(axis)(x)


class _VocabParallelEmbedding(Operator):
    """Megatron vocab-parallel embedding (no reference counterpart — SINGA
    replicates every table, SURVEY.md §2.3): the (V, E) table is row-sharded
    over the TP axis (spec P(tp_axis, None)), each device gathers only the
    ids that land in its shard and a psum assembles the full activations.
    The vjp (auto-derived) scatter-adds each device's masked cotangent into
    ITS shard only — embedding grads never cross the TP axis."""

    def __init__(self, axis):
        super().__init__("VocabParallelEmbedding")
        self.axis = axis
        self._cache = None

    def forward(self, ids, table):
        vp = table.shape[0]                       # local rows = V / tp
        off = lax.axis_index(self.axis) * vp
        local = ids - off
        ok = (local >= 0) & (local < vp)
        safe = jnp.clip(local, 0, vp - 1)
        self._cache = (safe, ok, table.shape, table.dtype)
        out = jnp.take(table, safe, axis=0)
        out = jnp.where(ok[..., None], out, jnp.zeros((), out.dtype))
        return lax.psum(out, self.axis)

    def backward(self, dy):
        # HAND rule (like _TPCopy/_TPReduce): the activations' cotangent is
        # already replicated across the TP axis, so the psum's transpose is
        # identity here — the auto-vjp would psum it again, scaling the
        # table grad by tp_size. Scatter-add the masked rows locally.
        safe, ok, tshape, tdtype = self._cache
        dyv = jnp.where(ok[..., None], dy, jnp.zeros((), dy.dtype))
        flat_idx = safe.reshape(-1)
        flat_dy = dyv.reshape(-1, dy.shape[-1])
        dtable = jnp.zeros(tshape, dy.dtype).at[flat_idx].add(flat_dy)
        return None, dtable.astype(tdtype)


class _VocabParallelSCE(Operator):
    """Fused softmax-CE over VOCAB-SHARDED logits (Megatron's parallel
    cross-entropy): x is this device's (N, V/tp) logits slice, t the global
    target ids. Max/sum-exp/target-logit each need one scalar-per-row psum —
    the full (N, V) logits are never materialized on any device. Columns at
    global index >= valid_vocab (tying/padding rows) are masked out of the
    partition function. The math is shared with the 1F1B engine's
    custom_vjp version (parallel.tp.vp_ce_forward/backward) so the two
    loss paths cannot drift."""

    def __init__(self, axis, valid_vocab=None):
        super().__init__("VocabParallelSCE")
        self.axis = axis
        self.valid_vocab = valid_vocab
        self._cache = None

    def forward(self, x, t):
        from .parallel.tp import vp_ce_forward
        assert x.ndim == 2, "flatten logits to (N, V/tp) first"
        self._in_dtype = x.dtype
        loss, self._cache = vp_ce_forward(x, t, self.axis,
                                          self.valid_vocab)
        return loss

    def backward(self, dy):
        from .parallel.tp import vp_ce_backward
        dx = vp_ce_backward(self._cache, dy)
        return dx.astype(self._in_dtype), None  # no grad for targets


class _GatherLastDim(Operator):
    """all_gather shards over `axis` onto the last dim (tiled) — used to
    assemble full logits from a vocab-parallel head for the caller-facing
    output. Hand backward: each shard keeps its slice of the replicated
    cotangent."""

    def __init__(self, axis):
        super().__init__("GatherLastDim")
        self.axis = axis
        self._local = None

    def forward(self, x):
        self._local = x.shape[-1]
        return lax.all_gather(x, self.axis, axis=x.ndim - 1, tiled=True)

    def backward(self, dy):
        # replicated cotangent -> each shard keeps its own slice (hand
        # rule for the same reason as _VocabParallelEmbedding.backward)
        off = lax.axis_index(self.axis) * self._local
        return lax.dynamic_slice_in_dim(dy, off, self._local,
                                        axis=dy.ndim - 1)


class _VocabParallelArgmax(Operator):
    """Global argmax over vocab-sharded logits: each device reduces its
    (…, V/tp) slice, a tiny (tp, …) all_gather of the per-shard winners
    picks the global one — the cheap alternative to gathering full logits
    when the caller only wants predictions."""

    never_requires_grad = True

    def __init__(self, axis, valid_vocab=None):
        super().__init__("VocabParallelArgmax")
        self.axis = axis
        self.valid_vocab = valid_vocab

    def forward(self, x):
        vp = x.shape[-1]
        off = lax.axis_index(self.axis) * vp
        if self.valid_vocab is not None:
            gcol = off + jnp.arange(vp)
            x = jnp.where(gcol < self.valid_vocab, x, -jnp.inf)
        v = jnp.max(x, axis=-1)
        a = jnp.argmax(x, axis=-1).astype(jnp.int32) + off.astype(jnp.int32)
        vs = lax.all_gather(v, self.axis)            # (tp, ...)
        gs = lax.all_gather(a, self.axis)
        w = jnp.argmax(vs, axis=0)                   # (...)
        return jnp.take_along_axis(gs, w[None], axis=0)[0]


def vocab_parallel_embedding(ids, table, axis):
    return _VocabParallelEmbedding(axis)(ids, table)


def vocab_parallel_argmax(x, axis, valid_vocab=None):
    return _VocabParallelArgmax(axis, valid_vocab)(x)


def vocab_parallel_sce(x, t, axis, valid_vocab=None):
    return _VocabParallelSCE(axis, valid_vocab)(x, t)


def gather_last(x, axis):
    return _GatherLastDim(axis)(x)


class _FlashAttention(Operator):
    """Fused attention on the tape; forward is the Pallas flash kernel (or
    its reference fallback), backward is its custom_vjp (ops/attention.py)."""

    def __init__(self, causal=False, window=None, block_diffusion=None):
        super().__init__()
        self.causal, self.window = causal, window
        self.block_diffusion = block_diffusion

    def forward(self, q, k, v):
        from .ops.attention import flash_attention
        return flash_attention(q, k, v, self.causal, window=self.window,
                               block_diffusion=self.block_diffusion)


class _RingAttention(Operator):
    """Sequence-parallel attention over a mesh axis; only meaningful inside
    a shard_mapped step (Model graph mode with an 'sp' axis)."""

    def __init__(self, axis_name, causal=False):
        super().__init__()
        self.axis_name = axis_name
        self.causal = causal

    def forward(self, q, k, v):
        from .ops.attention import ring_attention, flash_attention
        try:
            return ring_attention(q, k, v, self.axis_name, self.causal)
        except NameError:
            # axis unbound: running outside the shard_mapped step (param
            # init, single-device eval) — full attention is equivalent
            return flash_attention(q, k, v, self.causal)


# ======================= functional wrappers ==============================

add = _functional(Add)
sub = _functional(Sub)
mul = _functional(Mul)
div = _functional(Div)
negative = _functional(Negative)
reciprocal = _functional(Reciprocal)
abs = _functional(Abs)  # noqa: A001
sign = _functional(Sign)
exp = _functional(Exp)
log = _functional(Log)
sqrt = _functional(Sqrt)
pow = _functional(Pow)  # noqa: A001
less = _functional(Less)
greater = _functional(Greater)
equal = _functional(Equal)

relu = _functional(ReLU)
sigmoid = _functional(Sigmoid)
tanh = _functional(Tanh)
softplus = _functional(SoftPlus)
softsign = _functional(SoftSign)
cos = _functional(Cos)
cosh = _functional(Cosh)
acos = _functional(Acos)
acosh = _functional(Acosh)
sin = _functional(Sin)
sinh = _functional(Sinh)
asin = _functional(Asin)
asinh = _functional(Asinh)
tan = _functional(Tan)
atan = _functional(Atan)
atanh = _functional(Atanh)
erf = _functional(Erf)
matmul = _functional(Matmul)
cossim = _functional(CosSim)
identity = _functional(Identity)
mean = _functional(Mean)


def elu(x, alpha=1.0):
    return Elu(alpha)(x)


def selu(x, alpha=1.67326, gamma=1.0507):
    return SeLU(alpha, gamma)(x)


def leakyrelu(x, a=0.01):
    return LeakyRelu(a)(x)


def prelu(x, slope):
    return PRelu()(x, slope)


def hardsigmoid(x, alpha=0.2, gamma=0.5):
    return HardSigmoid(alpha, gamma)(x)


def softmax(x, axis=1):
    return SoftMax(axis)(x)


def reshape(x, shape):
    return Reshape(shape)(x)


def flatten(x, axis=1):
    return Flatten(axis)(x)


def squeeze(x, axis=None):
    return Squeeze(axis)(x)


def unsqueeze(x, axis):
    return Unsqueeze(axis)(x)


def transpose(x, perm=None):
    return Transpose(perm)(x)


def cat(xs, axis=0):
    return Concat(axis)(*xs)


concat = cat


def slice(x, starts, ends, axes=None, steps=None):  # noqa: A001
    return Slice(starts, ends, axes, steps)(x)


def split(x, axis, parts):
    return Split(axis, parts)(x)


def gather(x, axis, indices):
    return Gather(axis, indices)(x)


def tile(x, repeats):
    return Tile(repeats)(x)


def expand(x, shape):
    return Expand(shape)(x)


def pad(x, mode, pads, constant=0.0):
    return Pad(mode, pads, constant)(x)


def upsample(x, mode="nearest", scales=None):
    return UpSample(scales, mode)(x)


def depth_to_space(x, blocksize, mode="DCR"):
    return DepthToSpace(blocksize, mode)(x)


def space_to_depth(x, blocksize):
    return SpaceToDepth(blocksize)(x)


def clip(x, min=None, max=None):  # noqa: A002
    return Clip(min, max)(x)


def cast(x, to):
    return Cast(to)(x)


def onehot(depth, indices, values=(0.0, 1.0), axis=-1):
    return OneHot(depth, values, axis)(indices)


def where(condition, a, b):
    return Where(condition)(a, b)


def min(a, b):  # noqa: A001
    return Min()(a, b)


def max(a, b):  # noqa: A001
    return Max()(a, b)


def reduce_sum(x, axes=None, keepdims=True):
    return ReduceSum(axes, keepdims)(x)


def reduce_mean(x, axes=None, keepdims=True):
    return ReduceMean(axes, keepdims)(x)


def gemm(A, B, C=None, alpha=1.0, beta=1.0, transA=0, transB=0):
    op = Gemm(alpha, beta, transA, transB)
    return op(A, B) if C is None else op(A, B, C)


def add_bias(x, b, axis=0):
    return AddBias(axis)(x, b)


def mse_loss(x, t):
    return MeanSquareError()(x, t)


def cross_entropy(p, t):
    return CrossEntropy()(p, t)


def binary_cross_entropy(x, t):
    return BinaryCrossEntropy()(x, t)


def ranking_loss(pos, neg, M=0.2):
    return RankingLoss(M)(pos, neg)


def softmax_cross_entropy(x, t, weight=None):
    """Mean softmax cross-entropy over the rows; `weight` (one a row): the
    mean of weight x cross-entropy over ALL rows."""
    if weight is None:
        return SoftMaxCrossEntropy()(x, t)
    return SoftMaxCrossEntropy()(x, t, weight)


def conv2d(handle, x, W, b=None):
    """handle: a layer-owned _Conv2d op-factory carrying geometry (parity
    with GpuConvForward(handle, ...), model_operation.i)."""
    op = _Conv2d(handle.stride, handle.padding, handle.group,
                 handle.odd_padding, getattr(handle, "dilation", (1, 1)))
    return op(x, W, b) if b is not None else op(x, W)


def batchnorm_2d(x, gamma, beta, running_mean, running_var, momentum=0.9,
                 eps=1e-5, train: bool = True):
    """Returns (y, new_running_mean, new_running_var) — running stats are
    returned functionally; the Layer assigns them back (TPU-native stand-in
    for the reference's in-place handle mutation)."""
    if train:
        op = _BatchNorm2d(eps)
        # stash running-stat refs + hyperparams for ONNX export (the ONNX
        # BatchNormalization node needs all five inputs)
        op._bn_extras = (running_mean, running_var)
        op._bn_momentum = momentum
        y = op(x, gamma, beta)
        xd = lax.stop_gradient(x.data).astype(running_mean.data.dtype)
        axes = (0, 2, 3) if xd.ndim == 4 else (0,)
        bm = jnp.mean(xd, axis=axes)
        bv = jnp.var(xd, axis=axes)
        new_m = momentum * running_mean.data + (1 - momentum) * bm
        new_v = momentum * running_var.data + (1 - momentum) * bv
        return y, new_m, new_v
    y = _BatchNorm2dInfer(eps)(x, gamma, beta, running_mean, running_var)
    return y, running_mean.data, running_var.data


def pooling_2d(x, kernel, stride, padding=(0, 0), is_max=True,
               odd_padding=None):
    return _Pooling2d(kernel, stride, padding, is_max,
                      odd_padding=odd_padding)(x)


def globalaveragepool(x):
    return GlobalAveragePool()(x)


def dropout(x, ratio=0.5):
    key = x.device.rand_key() if (training and ratio > 0.0) else None
    return Dropout(ratio, key)(x)


def embedding(indices, table):
    if not isinstance(indices, Tensor):
        indices = Tensor(data=jnp.asarray(_raw(indices), jnp.int32),
                         device=table.device, requires_grad=False)
    elif not jnp.issubdtype(indices.data.dtype, jnp.integer):
        indices = Tensor(data=indices.data.astype(jnp.int32),
                         device=indices.device, requires_grad=False)
    return Embedding()(indices, table)


def layernorm(x, gamma, beta, eps=1e-5):
    return LayerNorm(eps)(x, gamma, beta)


def gelu(x):
    return Gelu()(x)


def rmsnorm(x, gamma, eps=1e-6):
    return RMSNorm(eps)(x, gamma)


def swiglu(gate, up):
    return SwiGLU()(gate, up)


def attention_mask(causal=False, window=None, block_diffusion=None):
    """(kind, size) of the ONE mask an attention's arguments name: (None,
    None) every key; ("causal", None) the keys at or before the query;
    ("window", W) of those the last W, the query's own among them;
    ("block_diffusion", b) the mask of block-diffusion training over a
    doubled sequence [noised ; clean] in blocks of b
    (ops.attention.block_diffusion_visible). A window goes with `causal`
    and the block mask with neither: any other pair is refused here, not
    computed."""
    assert window is None or block_diffusion is None, \
        "one mask an attention: a sliding window and the block-diffusion " \
        f"mask exclude each other (window={window}, " \
        f"block_diffusion={block_diffusion})"
    if block_diffusion is not None:
        assert not causal and int(block_diffusion) >= 1, \
            "the block-diffusion mask is not causal over the doubled " \
            f"sequence: causal={causal} block_diffusion={block_diffusion}"
        return "block_diffusion", int(block_diffusion)
    if window is not None:
        assert causal and int(window) >= 1, \
            f"a sliding window needs the causal mask: causal={causal} " \
            f"window={window}"
        return "window", int(window)
    return ("causal" if causal else None), None


def attention(q, k, v, causal=False, seq_axis=None, window=None,
              block_diffusion=None):
    """Fused attention (B,H,S,D); seq_axis names a mesh axis for ring
    (sequence-parallel) execution. The mask is `attention_mask`'s of
    (`causal`, `window`, `block_diffusion`); the ring takes the causal
    mask or none."""
    kind, _ = attention_mask(causal, window, block_diffusion)
    if seq_axis is not None:
        assert kind in (None, "causal"), \
            f"ring attention takes the causal mask or none, not {kind}"
        return _RingAttention(seq_axis, causal)(q, k, v)
    return _FlashAttention(causal, window, block_diffusion)(q, k, v)


def yarn_frequencies(dim, theta, factor, original_max, beta_fast=32.0,
                     beta_slow=1.0):
    """(dim/2,) rotary frequencies under YaRN ("NTK by parts"): a pair that
    turns more than `beta_fast` times over the `original_max` positions
    keeps theta^(-2i/dim), one that turns less than `beta_slow` times takes
    it divided by `factor`, the pairs between the two blend linearly in i."""
    half = dim // 2
    plain = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    # the pair index at which a frequency makes `turns` turns over the
    # original context
    at = lambda turns: dim * math.log(
        original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))
    # (this module's own `max` and `min` are tape operators)
    lo, hi = (int(np.clip(f(at(b)), 0, dim - 1)) for f, b in (
        (math.floor, beta_fast), (math.ceil, beta_slow)))
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo)
                    / (hi - lo or 1e-3), 0.0, 1.0)
    return ramp * plain / factor + (1.0 - ramp) * plain


def rope_tables(positions, dim, theta=10000.0, scaling=None):
    """(cos, sin) tables for NeoX-style rotary embeddings: positions (S,)
    -> (S, dim) with the two half-blocks duplicated (cos = [c | c]).
    `scaling`: None, or YaRN's {"factor", "original_max_position_embeddings",
    "beta_fast", "beta_slow", "attention_factor"}: blended frequencies
    (yarn_frequencies) and `attention_factor` on cos and sin."""
    if scaling is None:
        inv, scale = theta ** (-jnp.arange(0, dim // 2, dtype=jnp.float32)
                               / (dim // 2)), None
    else:
        inv = yarn_frequencies(
            dim, theta, scaling["factor"],
            scaling["original_max_position_embeddings"],
            scaling.get("beta_fast", 32.0), scaling.get("beta_slow", 1.0))
        scale = scaling.get("attention_factor")
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # (S,D/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    return cos, sin


def apply_rope(x, cos, sin):
    """Rotate (.., S, D) by per-position tables (S, D) — NeoX halves:
    out = x*cos + rotate_half(x)*sin, rotate_half = [-x2 | x1]."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * cos + rot.astype(jnp.float32) * sin) \
        .astype(x.dtype)


class Rope(Operator):
    """Rotary position embedding on (B, H, S, D) q/k (RoFormer/NeoX
    convention; no reference counterpart — SINGA has no transformer).
    `seq_axis` offsets positions by axis_index * S_local under sequence
    parallelism, the same pattern as _PosSlice for the learned table."""

    def __init__(self, theta=10000.0, seq_axis=None, scaling=None,
                 period=None):
        super().__init__("Rope")
        self.theta = float(theta)
        self.seq_axis = seq_axis
        self.scaling = scaling      # rope_tables' `scaling` (YaRN)
        # position i is i mod `period`: the halves of a doubled sequence
        # carry the same positions (None: positions run on)
        self.period = period

    def forward(self, x):
        from jax import lax
        S = x.shape[-2]
        off = 0
        if self.seq_axis is not None:
            try:
                off = lax.axis_index(self.seq_axis) * S
            except NameError:
                off = 0
        pos = jnp.arange(S) + off
        if self.period is not None:
            pos = pos % self.period
        cos, sin = rope_tables(pos, x.shape[-1], self.theta, self.scaling)
        return apply_rope(x, cos, sin)


# ======================= extended ONNX op set ==============================
# Ops beyond the reference's _rename_operators table (sonnx.py:1046-1133),
# needed to import real-world exported models (torch/tf2onnx graphs use
# ConvTranspose, InstanceNorm, ArgMax, the full Reduce* family, LSTM/GRU,
# TopK, LRN, ...). Forwards are jnp/lax; backward vjp-derived unless noted.


class _ArgReduce(Operator):
    never_requires_grad = True
    _fn = None

    def __init__(self, axis=0, keepdims=True, select_last_index=False):
        super().__init__()
        self.axis, self.keepdims = int(axis), bool(keepdims)
        self.last = bool(select_last_index)

    def forward(self, x):
        if self.last:
            # ONNX select_last_index: ties resolve to the LAST occurrence
            n = x.shape[self.axis]
            y = n - 1 - type(self)._fn(jnp.flip(x, self.axis),
                                       axis=self.axis)
        else:
            y = type(self)._fn(x, axis=self.axis)
        y = y.astype(jnp.int64)
        return jnp.expand_dims(y, self.axis) if self.keepdims else y


class ArgMax(_ArgReduce):
    _fn = staticmethod(jnp.argmax)


class ArgMin(_ArgReduce):
    _fn = staticmethod(jnp.argmin)


class _Reduce(Operator):
    """Shared shell for the ONNX Reduce* family."""
    _fn = None

    def __init__(self, axes=None, keepdims=True):
        super().__init__()
        self.axes = tuple(int(a) for a in axes) if axes is not None else None
        self.keepdims = bool(keepdims)

    def forward(self, x):
        return type(self)._fn(x, self.axes, self.keepdims)


class ReduceMax(_Reduce):
    _fn = staticmethod(lambda x, a, k: jnp.max(x, axis=a, keepdims=k))


class ReduceMin(_Reduce):
    _fn = staticmethod(lambda x, a, k: jnp.min(x, axis=a, keepdims=k))


class ReduceProd(_Reduce):
    _fn = staticmethod(lambda x, a, k: jnp.prod(x, axis=a, keepdims=k))


class ReduceL1(_Reduce):
    _fn = staticmethod(
        lambda x, a, k: jnp.sum(jnp.abs(x), axis=a, keepdims=k))


class ReduceL2(_Reduce):
    _fn = staticmethod(
        lambda x, a, k: jnp.sqrt(jnp.sum(x * x, axis=a, keepdims=k)))


class ReduceLogSum(_Reduce):
    _fn = staticmethod(
        lambda x, a, k: jnp.log(jnp.sum(x, axis=a, keepdims=k)))


class ReduceLogSumExp(_Reduce):
    _fn = staticmethod(
        lambda x, a, k: jax.scipy.special.logsumexp(x, axis=a, keepdims=k))


class ReduceSumSquare(_Reduce):
    _fn = staticmethod(lambda x, a, k: jnp.sum(x * x, axis=a, keepdims=k))


class LogSoftmax(Operator):
    def __init__(self, axis=-1):
        super().__init__()
        self.axis = int(axis)

    def forward(self, x):
        return jax.nn.log_softmax(x, axis=self.axis)


class Hardmax(Operator):
    never_requires_grad = True

    def __init__(self, axis=-1):
        super().__init__()
        self.axis = int(axis)

    def forward(self, x):
        idx = jnp.argmax(x, axis=self.axis)
        return jax.nn.one_hot(idx, x.shape[self.axis], axis=self.axis,
                              dtype=x.dtype)


class HardSwish(Operator):
    def forward(self, x):
        return x * jnp.clip(x / 6.0 + 0.5, 0.0, 1.0)


class Celu(Operator):
    def __init__(self, alpha=1.0):
        super().__init__()
        self.alpha = float(alpha)

    def forward(self, x):
        a = self.alpha
        return jnp.maximum(x, 0.0) + jnp.minimum(
            0.0, a * (jnp.exp(x / a) - 1.0))


class ThresholdedRelu(Operator):
    def __init__(self, alpha=1.0):
        super().__init__()
        self.alpha = float(alpha)

    def forward(self, x):
        return jnp.where(x > self.alpha, x, 0.0)


class Shrink(Operator):
    def __init__(self, bias=0.0, lambd=0.5):
        super().__init__()
        self.bias, self.lambd = float(bias), float(lambd)

    def forward(self, x):
        return jnp.where(x < -self.lambd, x + self.bias,
                         jnp.where(x > self.lambd, x - self.bias, 0.0))


class Mod(Operator):
    # differentiable a.e. for float operands (d/da fmod(a,b) = 1); int
    # tensors never carry requires_grad, so no flag is needed

    def __init__(self, fmod=0):
        super().__init__()
        self.fmod = int(fmod)

    def forward(self, a, b):
        return jnp.fmod(a, b) if self.fmod else jnp.mod(a, b)


class CumSum(Operator):
    def __init__(self, axis=0, exclusive=0, reverse=0):
        super().__init__()
        self.axis = int(axis)
        self.exclusive, self.reverse = int(exclusive), int(reverse)

    def forward(self, x):
        ax = self.axis
        if self.reverse:
            x = jnp.flip(x, ax)
        y = jnp.cumsum(x, axis=ax)
        if self.exclusive:
            y = jnp.roll(y, 1, axis=ax)
            y = y.at[(slice(None),) * (ax % y.ndim) + (0,)].set(0)
        if self.reverse:
            y = jnp.flip(y, ax)
        return y


class EyeLike(Operator):
    never_requires_grad = True

    def __init__(self, k=0, dtype=None):
        super().__init__()
        self.k = int(k)
        self.dtype = dtype

    def forward(self, x):
        return jnp.eye(x.shape[-2], x.shape[-1], k=self.k,
                       dtype=self.dtype or x.dtype)


class Size(Operator):
    never_requires_grad = True

    def forward(self, x):
        return jnp.asarray(x.size, jnp.int64)


class IsNaN(Operator):
    never_requires_grad = True

    def forward(self, x):
        return jnp.isnan(x).astype(jnp.float32)


class IsInf(Operator):
    never_requires_grad = True

    def __init__(self, detect_negative=1, detect_positive=1):
        super().__init__()
        self.neg, self.pos = bool(detect_negative), bool(detect_positive)

    def forward(self, x):
        hit = jnp.zeros(x.shape, bool)
        if self.pos:
            hit |= jnp.isposinf(x)
        if self.neg:
            hit |= jnp.isneginf(x)
        return hit.astype(jnp.float32)


class Trilu(Operator):
    def __init__(self, upper=1, k=0):
        super().__init__()
        self.upper, self.k = int(upper), int(k)

    def forward(self, x):
        return jnp.triu(x, self.k) if self.upper else jnp.tril(x, self.k)


class GatherElements(Operator):
    """jnp.take_along_axis; ONNX GatherElements / torch.gather."""

    def __init__(self, axis, indices):
        super().__init__()
        self.axis = int(axis)
        self.indices = jnp.asarray(indices, jnp.int32)

    def forward(self, x):
        return jnp.take_along_axis(x, self.indices, axis=self.axis)


class TopK(Operator):
    """(values, indices) of the k largest along `axis`. Values carry
    gradient (scatter back through the selected slots); indices are int."""

    def __init__(self, k, axis=-1, largest=True):
        super().__init__()
        self.k, self.axis, self.largest = int(k), int(axis), bool(largest)

    def forward(self, x):
        ax = self.axis % x.ndim
        xs = jnp.moveaxis(x, ax, -1)
        xs = xs if self.largest else -xs
        v, i = jax.lax.top_k(xs, self.k)
        v = v if self.largest else -v
        self._x_shape, self._ax = x.shape, ax
        self._idx = i
        return (jnp.moveaxis(v, -1, ax),
                jnp.moveaxis(i, -1, ax).astype(jnp.int64))

    def backward(self, dv, di):
        dv = jnp.moveaxis(dv, self._ax, -1)
        zero = jnp.zeros(jnp.moveaxis(
            jnp.empty(self._x_shape), self._ax, -1).shape, dv.dtype)
        dx = jnp.put_along_axis(zero, self._idx, dv, axis=-1,
                                inplace=False)
        return jnp.moveaxis(dx, -1, self._ax)


class LRN(Operator):
    """Local response normalization (AlexNet-era ONNX zoo models)."""

    def __init__(self, size, alpha=1e-4, beta=0.75, bias=1.0):
        super().__init__()
        self.size = int(size)
        self.alpha, self.beta, self.bias = float(alpha), float(beta), \
            float(bias)

    def forward(self, x):
        # ONNX window: [c - floor((size-1)/2), c + ceil((size-1)/2)]
        half = (self.size - 1) // 2
        sq = x * x
        pad = [(0, 0), (half, self.size - 1 - half), (0, 0), (0, 0)]
        sq = jnp.pad(sq, pad)
        import builtins
        acc = builtins.sum(sq[:, i:i + x.shape[1]]
                           for i in range(self.size))
        return x / jnp.power(self.bias + self.alpha / self.size * acc,
                             self.beta)


class MeanVarianceNormalization(Operator):
    def __init__(self, axes=(0, 2, 3)):
        super().__init__()
        self.axes = tuple(int(a) for a in axes)

    def forward(self, x):
        m = jnp.mean(x, axis=self.axes, keepdims=True)
        v = jnp.var(x, axis=self.axes, keepdims=True)
        return (x - m) / jnp.sqrt(v + 1e-9)


class LpNormalization(Operator):
    def __init__(self, axis=-1, p=2):
        super().__init__()
        self.axis, self.p = int(axis), int(p)

    def forward(self, x):
        if self.p == 1:
            n = jnp.sum(jnp.abs(x), axis=self.axis, keepdims=True)
        else:
            n = jnp.sqrt(jnp.sum(x * x, axis=self.axis, keepdims=True))
        return x / jnp.maximum(n, 1e-12)


class InstanceNorm2d(Operator):
    """Per-sample per-channel spatial normalization (NCHW)."""

    def __init__(self, eps=1e-5):
        super().__init__()
        self.eps = float(eps)

    def forward(self, x, gamma, beta):
        m = jnp.mean(x, axis=(2, 3), keepdims=True)
        v = jnp.var(x, axis=(2, 3), keepdims=True)
        xhat = (x - m) * jax.lax.rsqrt(v + self.eps)
        return xhat * gamma.reshape(1, -1, 1, 1) + beta.reshape(1, -1, 1, 1)


class _ConvTranspose2d(Operator):
    """Gradient-of-conv transposed convolution (NCHW, OIHW-transposed
    weights as ONNX lays them out: (C_in, C_out/group, kH, kW))."""

    def __init__(self, stride=(1, 1), padding=(0, 0), output_padding=(0, 0),
                 dilation=(1, 1), group=1):
        super().__init__()
        self.stride = tuple(int(s) for s in stride)
        self.padding = tuple(int(p) for p in padding)
        self.output_padding = tuple(int(p) for p in output_padding)
        self.dilation = tuple(int(d) for d in dilation)
        self.group = int(group)

    def forward(self, x, W, b=None):
        kh, kw = W.shape[2], W.shape[3]
        ph, pw = self.padding
        oph, opw = self.output_padding
        dh, dw = self.dilation
        # lax.conv_transpose pads the *output*; ONNX semantics: out =
        # (in-1)*stride - 2*pad + dilation*(k-1) + output_padding + 1
        pads = ((dh * (kh - 1) - ph, dh * (kh - 1) - ph + oph),
                (dw * (kw - 1) - pw, dw * (kw - 1) - pw + opw))
        y = jax.lax.conv_general_dilated(
            x, jnp.flip(W, (2, 3)).transpose(1, 0, 2, 3)
            if self.group == 1 else self._grouped_kernel(W),
            window_strides=(1, 1),
            padding=pads,
            lhs_dilation=self.stride,
            rhs_dilation=self.dilation,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=self.group)
        if b is not None:
            y = y + b.reshape(1, -1, 1, 1)
        return y

    def _grouped_kernel(self, W):
        # (C_in, C_out/g, kH, kW) -> per-group OIHW stacked on O
        g = self.group
        ci, cog, kh, kw = W.shape
        Wg = W.reshape(g, ci // g, cog, kh, kw)
        Wg = jnp.flip(Wg, (3, 4)).transpose(0, 2, 1, 3, 4)
        return Wg.reshape(g * cog, ci // g, kh, kw)


class GlobalMaxPool(Operator):
    def forward(self, x):
        return jnp.max(x, axis=(2, 3), keepdims=True)


class Einsum(Operator):
    def __init__(self, equation):
        super().__init__()
        self.equation = equation

    def forward(self, *xs):
        return jnp.einsum(self.equation, *xs)


class GreaterOrEqual(_CmpBinary):
    _fn = staticmethod(jnp.greater_equal)


class LessOrEqual(_CmpBinary):
    _fn = staticmethod(jnp.less_equal)


argmax = _functional(ArgMax)
argmin = _functional(ArgMin)
reduce_max = _functional(ReduceMax)
reduce_min = _functional(ReduceMin)
reduce_prod = _functional(ReduceProd)
log_softmax = _functional(LogSoftmax)
hardswish = _functional(HardSwish)
celu = _functional(Celu)
cumsum = _functional(CumSum)
trilu = _functional(Trilu)
topk = _functional(TopK)
lrn = _functional(LRN)
einsum = _functional(Einsum)
global_max_pool = _functional(GlobalMaxPool)


def instance_norm(x, gamma, beta, eps=1e-5):
    return InstanceNorm2d(eps)(x, gamma, beta)


def conv_transpose2d(x, W, b=None, stride=(1, 1), padding=(0, 0),
                     output_padding=(0, 0), dilation=(1, 1), group=1):
    op = _ConvTranspose2d(stride, padding, output_padding, dilation, group)
    return op(x, W, b) if b is not None else op(x, W)


# ======================= mixed-precision policy ============================
# bf16 compute + fp32 master weights. Parameters stay
# fp32 (optimizer updates, checkpoints); layers cast activations/weights to
# `compute_dtype` at matmul/conv boundaries through a DIFFERENTIABLE cast,
# so the cotangent is cast back on the way up and the master weight's grad
# arrives fp32. Normalizations/losses upcast internally (see LayerNorm /
# _BatchNorm2d / SoftMaxCrossEntropy). Enable via Model.compile(amp=...).

compute_dtype = None


class ComputeCast(Operator):
    """Float->float cast that participates in the tape (unlike Cast, which
    is for ONNX integer casts and never carries grad)."""

    def __init__(self, to):
        super().__init__()
        self.to = to

    # both ways under the device scope `amp_cast`, a leaf of the layer's
    def forward(self, x):
        self._orig = x.dtype
        with jax.named_scope("amp_cast"):
            return x.astype(self.to)

    def backward(self, dy):
        with jax.named_scope("amp_cast"):
            return dy.astype(self._orig)


class _CastUse(Operator):
    """One use of a parameter's `cast_once` copy: the copy itself on the
    way in; on the way back this use's gradient in the parameter's own
    dtype, so that the uses are summed there and not in the compute
    dtype."""

    def __init__(self, orig):
        super().__init__()
        self._orig = orig

    def forward(self, x):
        return x

    def backward(self, dy):
        with jax.named_scope("amp_cast"):
            return dy.astype(self._orig)


#: {id(parameter): (the parameter, its cast)} inside a `cast_once` block
_casts = {}


@contextlib.contextmanager
def cast_once(params):
    """Cast each of `params` to the compute dtype here, once, and within
    the block hand that copy to every `compute_cast` of it: a weight that a
    model applies T times (and T times more in rebuilt regions) is read and
    rounded once a step. Each use's gradient goes back to the parameter's
    dtype before the uses are summed. Put the backward pass inside the
    block too: a `Region` rebuilt there has to find the same copies."""
    global _casts
    prev, _casts = _casts, {}
    try:
        for p in params:
            cast = compute_cast(p)
            if cast is not p:
                # the entry holds the parameter too: an id is only unique
                # while its object lives
                _casts[id(p)] = (p, cast)
        yield
    finally:
        _casts = prev


def compute_cast(*xs):
    """Cast float Tensors to the active compute dtype (no-op when the
    policy is off or dtypes already match)."""
    if compute_dtype is None:
        return xs if len(xs) > 1 else xs[0]
    tgt = jnp.dtype(compute_dtype)
    out = []
    for x in xs:
        if x is not None and jnp.issubdtype(x.data.dtype, jnp.floating) \
                and x.data.dtype != tgt:
            kept = _casts.get(id(x))
            x = ComputeCast(tgt)(x) if kept is None \
                else _CastUse(x.data.dtype)(kept[1])
        out.append(x)
    return tuple(out) if len(out) > 1 else out[0]


class Region(Operator):
    """A part of the tape that keeps its inputs and nothing else, and is
    rebuilt in the backward pass: `fn(*xs[:n_args])`, a function of Tensors
    that may also read the Tensors `xs[n_args:]` (the parameters of the
    layers it calls, and their `cast_once` copies). The forward runs `fn`
    off the tape, so none of its residuals outlive it. The backward puts
    the saved inputs and the cotangents behind one
    `lax.optimization_barrier` (what `jax.checkpoint` lowers to: the
    compiler may neither merge the second forward with the first nor start
    it early), runs `fn` again on a tape of its own under the leading scope
    `recompute`, and walks that tape back: every inner operator runs its
    own backward rule under its own `bwd/<scope>`, as on the ordinary
    tape. (`jax.checkpoint` itself would hand the region to
    jax's differentiation: no tape rule, no `bwd` scope.)

    The inner tape's leaves have to be the very Tensors `fn` reads (the
    layers hold them), so a run lends them its arrays and takes them back."""

    scope_by_name = False    # its operators name themselves

    def __init__(self, fn, n_args):
        super().__init__("Region")
        self.fn, self.n_args = fn, n_args

    def __call__(self, *xs):
        self._leaves = xs
        return self._do_forward(*xs)

    def _run(self, arrays, record):
        """`fn` on the leaves holding `arrays`: off the tape, or (`record`)
        on a tape of its own whose leaves they are. A tuple of Tensors."""
        global training
        leaves = self._leaves
        held = [(t.data, t.creator, t.stores_grad) for t in leaves]
        prev, training = training, record
        try:
            for t, a in zip(leaves, arrays):
                t.data, t.creator, t.stores_grad = a, None, t.requires_grad
            outs = self.fn(*leaves[:self.n_args])
        finally:
            for t, (a, creator, stores) in zip(leaves, held):
                t.data, t.creator, t.stores_grad = a, creator, stores
            training = prev
        return outs if isinstance(outs, tuple) else (outs,)

    def forward(self, *raw):
        # what the second forward has to find again
        self._saved = raw
        self._rng = self._leaves[0].device.rng_state
        outs = tuple(y.data for y in self._run(raw, False))
        return outs if len(outs) > 1 else outs[0]

    def backward(self, *dys):
        saved, dys = lax.optimization_barrier((self._saved, tuple(dys)))
        dev = self._leaves[0].device
        now, dev.rng_state = dev.rng_state, self._rng
        # out of the outer `bwd/<scope>`: the replay is named like the
        # first forward behind `recompute`, the backward like any other
        with source_info_util.reset_name_stack():
            try:
                with jax.named_scope("/".join(filter(
                        None, (RECOMPUTE_SCOPE, self._scope)))):
                    outs = self._run(saved, True)
            finally:
                dev.rng_state = now
            # an output off the tape (a sample, an index) has no cotangent
            # to take; two outputs that share inner work walk it once each
            grads = {}
            for y, dy in zip(outs, dys):
                if y.creator is None:
                    continue
                for p, g in backward(y, dy):
                    grads[id(p)] = g.data if id(p) not in grads \
                        else grads[id(p)] + g.data
        return tuple(grads.get(id(t)) for t in self._leaves)


def region(fn, *xs, reads=()):
    """`fn(*xs)` as a `Region`: kept as its inputs, rebuilt in the backward
    pass. `reads`: the parameter Tensors `fn` reads besides its arguments
    (`layer.get_params().values()`); where `cast_once` holds a parameter's
    copy, the region reads that as well."""
    seen = {id(x) for x in xs}
    reads = [t for p in reads
             for t in (p, *(_casts[id(p)][1:] if id(p) in _casts else ()))]
    extra = [t for t in reads if not (id(t) in seen or seen.add(id(t)))]
    return Region(fn, len(xs))(*xs, *extra)


# ---- reference-name functional parity (python/singa/autograd.py) --------
# Snake-case wrappers and helpers whose class-level ops already exist, so
# a reference user's `autograd.<name>(...)` calls resolve here too.

def axis_helper(y_shape, x_shape):
    """Axes along which x was broadcast to produce y (ref autograd.py:34)."""
    res = []
    j = len(x_shape) - 1
    for i in range(len(y_shape) - 1, -1, -1):
        if j < 0 or x_shape[j] != y_shape[i]:
            res.append(i)
        j -= 1
    return tuple(res[::-1])


def back_broadcast(y_shape, x_shape, x):
    """Reduce a broadcast result back to x_shape (ref autograd.py:52)."""
    if tuple(y_shape) == tuple(x_shape):
        return x
    y = reduce_sum(x, axes=axis_helper(y_shape, x_shape), keepdims=False)
    return reshape(y, x_shape)


def sum(*xs):  # noqa: A001  (name mandated by reference parity)
    """Element-wise sum of the input tensors (ref autograd.py:1144)."""
    return Sum()(*xs)


def add_all(*xs):
    assert len(xs) > 2
    y = add(xs[0], xs[1])
    for x in xs[2:]:
        y = add(y, x)
    return y


def ctensor2numpy(x):
    """Raw backing array -> numpy (ref autograd.py:1363; the 'ctensor'
    here is a jax.Array)."""
    import numpy as np
    return np.asarray(x)


def scatter_elements(x, indices, updates, axis=0):
    idx = indices.numpy() if hasattr(indices, "numpy") else indices
    return ScatterElements(idx, axis)(x, updates)


def shape(x):
    return Shape()(x)


def constant_of_shape(x, value=0):
    return ConstantOfShape(value)(x)


def ceil(x):
    return Ceil()(x)


def floor(x):
    return Floor()(x)


def round(x):  # noqa: A001  (name mandated by reference parity)
    return Round()(x)


def rounde(x):
    return Rounde()(x)


def nonzero(x):
    return NonZero()(x)
