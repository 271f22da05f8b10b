"""Tensor facade over jax.Array.

Reference parity: SINGA's Python `Tensor` (python/singa/tensor.py:73) wraps a
C++ `CTensor` in `.data`, carries `creator/requires_grad/stores_grad` for
autograd (tensor.py:121-125), and a ~150-function module API mirroring the
C++ free functions (include/singa/core/tensor.h:334-663).

TPU-native redesign: `.data` holds a `jax.Array`. There is no Block/stride
machinery — XLA owns layout; views (transpose/broadcast) are plain jnp ops.
The module-level functions here are NOT autograd-tracked (same as the
reference, where the tape lives in autograd.py); they are the raw math layer.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import device as device_module
from . import observe
from .device import Device, get_default_device

# ---- dtypes (parity with core.proto:26-34 + singa tensor.py) -------------
float16 = jnp.float16
float32 = jnp.float32
float64 = jnp.float64
bfloat16 = jnp.bfloat16  # TPU-native addition
int8 = jnp.int8
int32 = jnp.int32
int64 = jnp.int64
uint8 = jnp.uint8
bool_ = jnp.bool_

# singa string names -> jnp dtype (ref tensor.py int2dtype tables)
_DT = {
    "float16": float16, "float32": float32, "float64": float64,
    "bfloat16": bfloat16, "int8": int8, "int32": int32, "int64": int64,
    "uint8": uint8, "char": int8, "float": float32, "double": float64,
    "int": int32, "bool": jnp.bool_,
}


def _resolve_dtype(dt):
    if dt is None:
        return None
    if isinstance(dt, str):
        return _DT[dt]
    return jnp.dtype(dt)


def _dev(device: Device | None) -> Device:
    return device if device is not None else get_default_device()


def _put(arr, dev: Device):
    return jax.device_put(arr, dev.jax_device)


class Tensor:
    """nd-array living on a Device, with autograd hooks.

    Mirrors python/singa/tensor.py:73: `.data` (the backing array),
    `.creator` (the autograd Operator that produced it, tensor.py:121-125),
    `.requires_grad`, `.stores_grad`.
    """

    __slots__ = ("data", "device", "creator", "requires_grad", "stores_grad",
                 "name", "spec")

    def __init__(self, shape=None, device: Device | None = None, dtype=None,
                 data=None, requires_grad: bool = True, stores_grad: bool = False,
                 creator=None, name: str | None = None):
        self.device = _dev(device)
        dtype = _resolve_dtype(dtype)  # None = no explicit request
        if data is None:
            if shape is None:
                shape = ()
            self.data = _put(jnp.zeros(tuple(shape), dtype=dtype or float32),
                             self.device)
        elif isinstance(data, Tensor):
            arr = data.data
            if dtype is not None and arr.dtype != dtype:
                arr = arr.astype(dtype)
            self.data = _put(arr, self.device)
        elif isinstance(data, np.ndarray):
            if dtype is None and data.dtype == np.float64:
                dtype = float32  # never silently carry f64 onto the chip
            self.data = _put(jnp.asarray(data, dtype=dtype), self.device)
        else:
            self.data = data  # jax.Array (possibly a tracer): trust placement
        self.creator = creator
        self.requires_grad = requires_grad
        self.stores_grad = stores_grad
        self.name = name
        # Optional jax.sharding.PartitionSpec: how this tensor (typically a
        # TP-sharded param) is partitioned over the mesh inside Model's
        # shard_mapped step. None = replicated.
        self.spec = None

    # ---- metadata -------------------------------------------------------
    @property
    def shape(self):
        return tuple(self.data.shape)

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def ndim(self):
        return self.data.ndim

    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    def memsize(self):
        return self.size() * self.data.dtype.itemsize

    def is_empty(self):
        return self.size() == 0

    def is_transpose(self):
        return False  # views are materialized by XLA; kept for API parity

    # ---- conversions ----------------------------------------------------
    def numpy(self) -> np.ndarray:
        a = self.data
        # the read that fences: it returns when the device has run every
        # program the value depends on, so a training loop's wait for its
        # steps shows under this span, which names the device's idle gaps
        # beside it in a profiler trace. Not timed into the registry: the
        # device is idle until the loop's next dispatch (trace_span)
        with observe.trace_span("tensor.fetch"):
            if not getattr(a, "is_fully_addressable", True):
                # a global array whose rows live on other processes'
                # devices (a data-parallel step's batch output under
                # jax.distributed): the read gathers, every process gets
                # the whole value
                from jax.experimental import multihost_utils
                return np.asarray(
                    multihost_utils.process_allgather(a, tiled=True))
            return np.asarray(a)

    def item(self):
        return self.numpy().item()

    def as_type(self, dtype) -> "Tensor":
        return Tensor(data=self.data.astype(_resolve_dtype(dtype)),
                      device=self.device, requires_grad=self.requires_grad,
                      stores_grad=self.stores_grad)

    def to_device(self, device: Device) -> "Tensor":
        self.data = _put(self.data, device)
        self.device = device
        return self

    def to_host(self) -> "Tensor":
        return self.to_device(get_default_device())

    def clone(self) -> "Tensor":
        return Tensor(data=jnp.array(self.data), device=self.device,
                      requires_grad=self.requires_grad,
                      stores_grad=self.stores_grad, name=self.name)

    def copy(self) -> "Tensor":
        return self.clone()

    def deepcopy(self) -> "Tensor":
        """Same as clone() (ref tensor.py:488)."""
        return self.clone()

    def contiguous(self) -> "Tensor":
        """jax.Arrays are always contiguous; a copy for parity (ref :227)."""
        return self.clone()

    def is_dummy(self) -> bool:
        """True iff this tensor is a tape leaf placeholder (ref :159)."""
        from . import autograd
        return isinstance(self.creator, autograd.Dummy)

    def to_type(self, dtype):
        """In-place dtype change (ref tensor.py:286)."""
        self.data = self.data.astype(_resolve_dtype(dtype))
        return self

    def copy_data(self, t: "Tensor"):
        """Copy data from another Tensor (ref tensor.py:380)."""
        assert t.size() == self.size(), "tensor shape should be the same"
        self.data = _put(t.data.reshape(self.shape).astype(self.dtype),
                         self.device)

    # (DEPRECATED in the reference too — broadcast helpers, ref :550-595)
    def add_column(self, v: "Tensor"):
        self.data = self.data + v.data[:, None]

    def add_row(self, v: "Tensor"):
        self.data = self.data + v.data[None, :]

    def div_column(self, v: "Tensor"):
        self.data = self.data / v.data[:, None]

    def div_row(self, v: "Tensor"):
        self.data = self.data / v.data[None, :]

    def mult_column(self, v: "Tensor"):
        self.data = self.data * v.data[:, None]

    def mult_row(self, v: "Tensor"):
        self.data = self.data * v.data[None, :]

    def copy_from(self, t: "Tensor"):
        self.data = _put(t.data, self.device)

    def copy_from_numpy(self, arr: np.ndarray):
        self.data = _put(jnp.asarray(arr, dtype=self.dtype).reshape(self.shape),
                         self.device)

    def reset_like(self, t: "Tensor"):
        self.data = jnp.zeros(t.shape, dtype=t.dtype)

    # ---- in-place init (parity with Tensor::SetValue / Gaussian / ...) ---
    def set_value(self, x):
        self.data = _put(jnp.full(self.shape, x, dtype=self.dtype), self.device)
        return self

    def gaussian(self, mean=0.0, std=1.0):
        k = self.device.rand_key()
        self.data = mean + std * jax.random.normal(k, self.shape, dtype=self.dtype)
        return self

    def uniform(self, low=0.0, high=1.0):
        k = self.device.rand_key()
        self.data = jax.random.uniform(k, self.shape, dtype=self.dtype,
                                       minval=low, maxval=high)
        return self

    def bernoulli(self, p):
        k = self.device.rand_key()
        self.data = jax.random.bernoulli(k, p, self.shape).astype(self.dtype)
        return self

    # ---- shape ops ------------------------------------------------------
    def reshape(self, shape) -> "Tensor":
        return Tensor(data=self.data.reshape(tuple(shape)), device=self.device,
                      requires_grad=self.requires_grad)

    def transpose(self, axes=None) -> "Tensor":
        return Tensor(data=jnp.transpose(self.data, axes), device=self.device,
                      requires_grad=self.requires_grad)

    @property
    def T(self):
        return self.transpose()

    def repeat(self, repeats, axis=None) -> "Tensor":
        return Tensor(data=jnp.repeat(self.data, repeats, axis=axis),
                      device=self.device)

    # ---- reductions -----------------------------------------------------
    def sum(self, axis=None):
        return Tensor(data=jnp.sum(self.data, axis=axis), device=self.device)

    def l1(self):
        return float(jnp.mean(jnp.abs(self.data)))

    def l2(self):
        # Reference Tensor::L2 returns ||x||_2 / sqrt(n) (nrm2 over size).
        return float(jnp.linalg.norm(self.data.ravel()) /
                     np.sqrt(np.maximum(self.size(), 1)))

    # ---- operators ------------------------------------------------------
    def _rhs(self, x):
        return x.data if isinstance(x, Tensor) else x

    def __add__(self, x):
        return Tensor(data=self.data + self._rhs(x), device=self.device)

    __radd__ = __add__

    def __sub__(self, x):
        return Tensor(data=self.data - self._rhs(x), device=self.device)

    def __rsub__(self, x):
        return Tensor(data=self._rhs(x) - self.data, device=self.device)

    def __mul__(self, x):
        return Tensor(data=self.data * self._rhs(x), device=self.device)

    __rmul__ = __mul__

    def __truediv__(self, x):
        return Tensor(data=self.data / self._rhs(x), device=self.device)

    def __rtruediv__(self, x):
        return Tensor(data=self._rhs(x) / self.data, device=self.device)

    def __pow__(self, x):
        return Tensor(data=self.data ** self._rhs(x), device=self.device)

    def __neg__(self):
        return Tensor(data=-self.data, device=self.device)

    def __matmul__(self, x):
        return Tensor(data=self.data @ self._rhs(x), device=self.device)

    def __lt__(self, x):
        return Tensor(data=(self.data < self._rhs(x)).astype(float32),
                      device=self.device, requires_grad=False)

    def __le__(self, x):
        return Tensor(data=(self.data <= self._rhs(x)).astype(float32),
                      device=self.device, requires_grad=False)

    def __gt__(self, x):
        return Tensor(data=(self.data > self._rhs(x)).astype(float32),
                      device=self.device, requires_grad=False)

    def __ge__(self, x):
        return Tensor(data=(self.data >= self._rhs(x)).astype(float32),
                      device=self.device, requires_grad=False)

    def __iadd__(self, x):
        self.data = self.data + self._rhs(x)
        return self

    def __isub__(self, x):
        self.data = self.data - self._rhs(x)
        return self

    def __imul__(self, x):
        self.data = self.data * self._rhs(x)
        return self

    def __itruediv__(self, x):
        self.data = self.data / self._rhs(x)
        return self

    def __getitem__(self, idx):
        return Tensor(data=self.data[idx], device=self.device)

    def __len__(self):
        return self.shape[0]

    def __repr__(self):
        return (f"Tensor(shape={self.shape}, dtype={self.dtype}, "
                f"device={self.device.lang})")


# ======================= module-level functions ===========================
# Parity with the free-function API in include/singa/core/tensor.h:334-663
# and python/singa/tensor.py module functions.

def from_numpy(arr, device: Device | None = None, dtype=None,
               requires_grad: bool = False) -> Tensor:
    """Data tensors default to requires_grad=False (params are created by
    layers with explicit flags), so backward never wastes FLOPs on inputs."""
    arr = np.asarray(arr)
    if dtype is None:
        # match reference from_numpy: float64 -> float32 promotion is caller's
        # job, but ints stay ints
        dtype = arr.dtype if arr.dtype != np.float64 else np.float32
    return Tensor(data=jnp.asarray(arr, dtype=_resolve_dtype(dtype)),
                  device=_dev(device), requires_grad=requires_grad)


def to_numpy(t: Tensor) -> np.ndarray:
    return t.numpy()


def from_raw(arr: "jax.Array", device: Device | None = None) -> Tensor:
    return Tensor(data=arr, device=_dev(device))


def zeros(shape, device=None, dtype=float32) -> Tensor:
    return Tensor(shape=shape, device=device, dtype=dtype)


def ones(shape, device=None, dtype=float32) -> Tensor:
    d = _dev(device)
    return Tensor(data=_put(jnp.ones(tuple(shape), _resolve_dtype(dtype)), d),
                  device=d)


def zeros_like(t: Tensor) -> Tensor:
    return Tensor(data=jnp.zeros_like(t.data), device=t.device)


def ones_like(t: Tensor) -> Tensor:
    return Tensor(data=jnp.ones_like(t.data), device=t.device)


def sizeof(dtype) -> int:
    return jnp.dtype(_resolve_dtype(dtype)).itemsize


def reshape(t: Tensor, shape) -> Tensor:
    return t.reshape(shape)


def transpose(t: Tensor, axes=None) -> Tensor:
    return t.transpose(axes)


def copy_data_to_from(dst: Tensor, src: Tensor, size=None):
    if size is None:
        dst.copy_from(src)
    else:
        flat = jnp.concatenate(
            [src.data.ravel()[:size], dst.data.ravel()[size:]])
        dst.data = flat.reshape(dst.shape)


def concatenate(tensors, axis=0) -> Tensor:
    return Tensor(data=jnp.concatenate([t.data for t in tensors], axis=axis),
                  device=tensors[0].device)


def repeat(t: Tensor, repeats, axis=None) -> Tensor:
    return t.repeat(repeats, axis)


# ---- elementwise unary (tensor.h:366-437) --------------------------------

def _unary(fn):
    def wrapped(t: Tensor) -> Tensor:
        return Tensor(data=fn(t.data), device=t.device)
    return wrapped


abs = _unary(jnp.abs)  # noqa: A001 - parity with reference module name
exp = _unary(jnp.exp)
log = _unary(jnp.log)
sign = _unary(jnp.sign)
sqrt = _unary(jnp.sqrt)
square = _unary(jnp.square)
tanh = _unary(jnp.tanh)
sigmoid = _unary(jax.nn.sigmoid)
relu = _unary(jax.nn.relu)
sin = _unary(jnp.sin)
cos = _unary(jnp.cos)
ceil = _unary(jnp.ceil)
floor = _unary(jnp.floor)
round = _unary(jnp.round)  # noqa: A001


def softmax(t: Tensor, axis: int = -1) -> Tensor:
    return Tensor(data=jax.nn.softmax(t.data, axis=axis), device=t.device)


def pow(base, exponent) -> Tensor:  # noqa: A001
    b = base.data if isinstance(base, Tensor) else base
    e = exponent.data if isinstance(exponent, Tensor) else exponent
    dev = base.device if isinstance(base, Tensor) else exponent.device
    return Tensor(data=jnp.power(b, e), device=dev)


def clip(t: Tensor, lo, hi) -> Tensor:
    return Tensor(data=jnp.clip(t.data, lo, hi), device=t.device)


# ---- arithmetic (tensor.h:489-528) ---------------------------------------

def add(lhs, rhs) -> Tensor:
    return lhs + rhs


def sub(lhs, rhs) -> Tensor:
    return lhs - rhs


def eltwise_mult(lhs: Tensor, rhs) -> Tensor:
    return lhs * rhs


def div(lhs, rhs) -> Tensor:
    if not isinstance(lhs, Tensor):
        return Tensor(data=lhs / rhs.data, device=rhs.device)
    return lhs / rhs


def mult(A: Tensor, B: Tensor) -> Tensor:
    """Matrix multiply (reference Mult/GEMM, tensor.h:600-611)."""
    return Tensor(data=A.data @ B.data, device=A.device)


def axpy(alpha, x: Tensor, y: Tensor):
    """y += alpha * x, in place on y (BLAS Axpy, tensor.h:596)."""
    y.data = y.data + alpha * x.data
    return y


def einsum(subscripts: str, *operands: Tensor) -> Tensor:
    return Tensor(data=jnp.einsum(subscripts, *[o.data for o in operands]),
                  device=operands[0].device)


def tensordot(a: Tensor, b: Tensor, axes=2) -> Tensor:
    return Tensor(data=jnp.tensordot(a.data, b.data, axes=axes), device=a.device)


# ---- comparison (tensor.h:440-487); results are float masks like the ref --

def lt(t: Tensor, x): return t < x
def le(t: Tensor, x): return t <= x
def gt(t: Tensor, x): return t > x
def ge(t: Tensor, x): return t >= x


def eq(t: Tensor, x) -> Tensor:
    rhs = x.data if isinstance(x, Tensor) else x
    return Tensor(data=(t.data == rhs).astype(float32), device=t.device,
                  requires_grad=False)


# ---- reductions ----------------------------------------------------------

def sum(t: Tensor, axis=None) -> Tensor:  # noqa: A001
    return Tensor(data=jnp.sum(t.data, axis=axis), device=t.device)


def mean(t: Tensor, axis=None) -> Tensor:
    return Tensor(data=jnp.mean(t.data, axis=axis), device=t.device)


def max(t: Tensor, axis=None) -> Tensor:  # noqa: A001
    return Tensor(data=jnp.max(t.data, axis=axis), device=t.device)


def min(t: Tensor, axis=None) -> Tensor:  # noqa: A001
    return Tensor(data=jnp.min(t.data, axis=axis), device=t.device)


def argmax(t: Tensor, axis=-1) -> Tensor:
    return Tensor(data=jnp.argmax(t.data, axis=axis), device=t.device,
                  requires_grad=False)


# ---- row/col ops for 2-D matrices (tensor.h:531-579) ---------------------

def _colwise(op):
    def f(m: Tensor, v: Tensor) -> Tensor:  # v length = nrows
        return Tensor(data=op(m.data, v.data[:, None]), device=m.device)
    return f


def _rowwise(op):
    def f(m: Tensor, v: Tensor) -> Tensor:  # v length = ncols
        return Tensor(data=op(m.data, v.data[None, :]), device=m.device)
    return f


import operator as _op  # noqa: E402

add_column = _colwise(_op.add)
sub_column = _colwise(_op.sub)
mult_column = _colwise(_op.mul)
div_column = _colwise(_op.truediv)
add_row = _rowwise(_op.add)
sub_row = _rowwise(_op.sub)
mult_row = _rowwise(_op.mul)
div_row = _rowwise(_op.truediv)


def sum_columns(m: Tensor) -> Tensor:
    return Tensor(data=jnp.sum(m.data, axis=1), device=m.device)


def sum_rows(m: Tensor) -> Tensor:
    return Tensor(data=jnp.sum(m.data, axis=0), device=m.device)


# ---- random (tensor.h:581-590) -------------------------------------------

def gaussian(mean, std, shape, device=None, dtype=float32) -> Tensor:
    d = _dev(device)
    k = d.rand_key()
    return Tensor(data=mean + std * jax.random.normal(
        k, tuple(shape), dtype=_resolve_dtype(dtype)), device=d)


def uniform(low, high, shape, device=None, dtype=float32) -> Tensor:
    d = _dev(device)
    k = d.rand_key()
    return Tensor(data=jax.random.uniform(
        k, tuple(shape), dtype=_resolve_dtype(dtype), minval=low, maxval=high),
        device=d)


def bernoulli(p, shape, device=None, dtype=float32) -> Tensor:
    d = _dev(device)
    k = d.rand_key()
    return Tensor(data=jax.random.bernoulli(k, p, tuple(shape)).astype(
        _resolve_dtype(dtype)), device=d)


# ---- fused softmax cross-entropy (tensor.h:625-637) ----------------------

def targets_are_indices(logits, targets):
    """Whether `targets` name one class a row (integers, a rank below the
    logits) and not a distribution over the classes (one-hot or soft)."""
    return targets.ndim == logits.ndim - 1 \
        or targets.dtype in (jnp.int32, jnp.int64)


def softmax_lse(logits):
    """log(sum(exp(logits))) over the last axis, a number a row."""
    return jax.scipy.special.logsumexp(logits, axis=-1)


def _at_target(logits, targets):
    """True where the last axis' index is the row's target. A compare of an
    iota, so that a consumer folds it into its own pass over the logits in
    whatever layout they lie: no gather, no one-hot array."""
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
    return classes == targets.astype(jnp.int32)[..., None]


def softmax_cross_entropy_fwd(logits, targets, lse=None):
    """Fused stable softmax CE of every row; targets may be class indices
    or a distribution (one-hot, soft). `lse`: `softmax_lse(logits)` where
    the caller has it.

    Reference: CrossEntropyFwd (tensor.h:636) fuses softmax+CE on device; on
    TPU the fusion is done by XLA from this logsumexp formulation. With
    class indices the loss is `lse - logits[target]`, the picked logit a
    masked sum over the classes beside the sum of exponentials: nothing of
    the logits' size is formed, and the logits are read as they lie.
    """
    if lse is None:
        lse = softmax_lse(logits)
    if targets_are_indices(logits, targets):
        picked = jnp.sum(jnp.where(_at_target(logits, targets), logits, 0),
                         axis=-1)
        return lse - picked
    logp = logits - lse[..., None]
    return -jnp.sum(targets * logp, axis=-1)


def softmax_cross_entropy_bwd(logits, targets, lse=None):
    """d(CE)/d(logits) = softmax(logits) - onehot(targets). With class
    indices the softmax is `exp(logits - lse)`, from the `lse` the forward
    made where the caller kept it."""
    if not targets_are_indices(logits, targets):
        return jax.nn.softmax(logits, axis=-1) - targets
    if lse is None:
        lse = softmax_lse(logits)
    return jnp.exp(logits - lse[..., None]) \
        - _at_target(logits, targets).astype(logits.dtype)


# ---- reference-name module-fn parity (python/singa/tensor.py) -----------

def from_raw_tensor(t):
    """Wrap a raw backing array (jax.Array / numpy) as a Tensor in place —
    zero-copy, placement preserved (ref tensor.py:789; the 'raw tensor'
    here is a jax.Array)."""
    if isinstance(t, np.ndarray):
        return from_numpy(t)
    return from_raw(t)


def from_raw_tensors(tt):
    return [from_raw_tensor(t) for t in list(tt)]


def product(shape):
    """Number of elements for a shape (ref tensor.py:814)."""
    out = 1
    for s in shape:
        out *= int(s)
    return out


def contiguous(t: Tensor) -> Tensor:
    """jax.Arrays are always contiguous; returns a device-side copy for
    parity with the reference's new-tensor semantics (ref :830)."""
    return t.clone()


def to_host(t: Tensor) -> Tensor:
    """Copy to a host (CPU) tensor (ref tensor.py:910)."""
    from . import device as device_module
    return from_numpy(t.numpy(), device=device_module.create_cpu_device())


def average(t: Tensor, axis=None):
    """Mean of all elements (float) or along `axis` (Tensor)
    (ref tensor.py:1128)."""
    if axis is None or t.data.ndim <= 1:
        return float(jnp.mean(t.data))
    return Tensor(data=jnp.mean(t.data, axis=axis), device=t.device)


def copy_from_numpy(data, np_array):
    """Static-method-style copy into an existing Tensor (ref :1777)."""
    data.copy_from_numpy(np.asarray(np_array).reshape(data.shape))


def random(shape, device: "Device | None" = None) -> Tensor:
    """Uniform [0,1) tensor of `shape` (ref tensor.py:1817)."""
    t = Tensor(shape, device=device)
    t.uniform(0.0, 1.0)
    return t
