"""Layer API with deferred shape-inferring initialization.

Reference parity: python/singa/layer.py — `LayerMeta` wraps `initialize`
(run lazily on first forward with concrete input shapes, layer.py:31-64);
`Layer` base gives name scoping, `get/set_params`, `get/set_states`, and a
sublayer registry populated through `__setattr__` (layer.py:75-284). The
layer zoo below matches §2.7 of SURVEY.md name-for-name.

TPU-native redesign: layers own `Tensor` params and call autograd ops whose
forwards are jnp — under Model's graph mode the whole stack traces into one
XLA executable, so there is no per-layer kernel dispatch cost to hide.
"""

from __future__ import annotations

import dataclasses
import math
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd
from . import initializer
from .tensor import Tensor
from . import tensor as tensor_module


class LayerMeta(type):
    """Wraps forward so initialize() runs once with real input shapes."""

    def __new__(mcs, name, bases, attrs):
        if "forward" in attrs:
            inner = attrs["forward"]

            def forward(self, *args, **kwargs):
                if not self._initialized:
                    self.initialize(*args, **kwargs)
                    self._initialized = True
                return inner(self, *args, **kwargs)

            forward.__wrapped__ = inner
            attrs["forward"] = forward
        return super().__new__(mcs, name, bases, attrs)


class Layer(metaclass=LayerMeta):
    sep = "."  # param-name scoping separator (ref layer.py:77)

    def __init__(self, name: str | None = None):
        # use object.__setattr__ to avoid registry recursion
        object.__setattr__(self, "_layers", OrderedDict())
        object.__setattr__(self, "_initialized", False)
        # program scope of what forward records: the attribute name the
        # parent registered this layer under (set there), else `name`
        object.__setattr__(self, "_scope", None)
        self.name = name or self.__class__.__name__
        self._param_names = []   # attribute names holding trainable Tensors
        self._state_names = []   # attribute names holding non-trainable state

    # ---- registry -------------------------------------------------------
    def __setattr__(self, key, value):
        if isinstance(value, Layer):
            self._layers[key] = value
            object.__setattr__(value, "_scope", key)
        object.__setattr__(self, key, value)

    def _register_param(self, attr: str, t: Tensor):
        t.requires_grad = True
        t.stores_grad = True
        t.name = attr
        object.__setattr__(self, attr, t)
        if attr not in self._param_names:
            self._param_names.append(attr)

    def _register_state(self, attr: str, t: Tensor):
        t.requires_grad = False
        t.stores_grad = False
        t.name = attr
        object.__setattr__(self, attr, t)
        if attr not in self._state_names:
            self._state_names.append(attr)

    # ---- lifecycle ------------------------------------------------------
    def initialize(self, *args, **kwargs):
        pass

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        # the scope is the key get_params() gives this layer's parameters,
        # so an instruction's scope and the parameter it reads agree
        with jax.named_scope(self._scope or self.name):
            return self.forward(*args, **kwargs)

    # ---- params / states (ref layer.py:140-220) --------------------------
    # Names are scoped by *attribute path* (e.g. "conv1.W"), which is what
    # the reference's __setattr__-based registration produces (layer.py:241)
    # and what the checkpoint format keys on.
    def dtype_check(self, *inputs):
        """Coerce all inputs to the first input's dtype, in place
        (ref layer.py:171)."""
        x_dtype = inputs[0].dtype
        for inp in inputs[1:]:
            if inp.dtype != x_dtype:
                inp.to_type(x_dtype)

    def get_params(self) -> "OrderedDict[str, Tensor]":
        out = OrderedDict()
        for attr in self._param_names:
            out[attr] = getattr(self, attr)
        for key, sub in self._layers.items():
            for n, t in sub.get_params().items():
                out[f"{key}{self.sep}{n}"] = t
        return out

    def set_params(self, params: dict):
        own = self.get_params()
        for n, v in params.items():
            assert n in own, f"unknown param {n}; have {list(own)}"
            if isinstance(v, Tensor):
                own[n].copy_from(v)
            else:
                own[n].copy_from_numpy(np.asarray(v))

    def get_states(self) -> "OrderedDict[str, Tensor]":
        out = self.get_params()
        for attr in self._state_names:
            out[attr] = getattr(self, attr)
        for key, sub in self._layers.items():
            for n, t in sub.get_states().items():
                out.setdefault(f"{key}{self.sep}{n}", t)
        return out

    def set_states(self, states: dict):
        own = self.get_states()
        for n, v in states.items():
            if n in own:
                if isinstance(v, Tensor):
                    own[n].copy_from(v)
                else:
                    own[n].copy_from_numpy(np.asarray(v))

    def register_layers(self, *args):
        """Register sublayers held in lists/closures rather than attributes
        (ref layer.py:265-284; used by resnet's _make_layer blocks)."""
        if len(args) == 1 and isinstance(args[0], OrderedDict):
            items = list(args[0].items())
        else:
            items = [(f"{v.__class__.__name__}_{i}", v)
                     for i, v in enumerate(args)]
        for name, value in items:
            if isinstance(value, Layer):
                # unlike the reference, survive repeated register_layers
                # calls (resnet registers one stage at a time)
                while name in self._layers:
                    name += "_"
                self._layers[name] = value
                value.name = name
                object.__setattr__(value, "_scope", name)

    def sublayers(self):
        return dict(self._layers)

    # device of params follows input tensors; kept for API parity
    def device_check(self, *xs):
        pass


# ======================= core layers ======================================


class Linear(Layer):
    """y = x W + b (ref layer.py:287).

    Tensor parallelism (no reference counterpart — SINGA is data-parallel
    only, SURVEY.md §2.3): `tp_axis` names a mesh axis to shard the weight
    over. `tp_mode="column"` splits the OUTPUT features (activations leave
    sharded, zero comm, Megatron f on the input); `tp_mode="row"` splits
    the INPUT features (one psum on the output, Megatron g). Params carry
    their PartitionSpec in `.spec`, which Model's shard_mapped step uses
    as the in/out sharding. Outside a mesh (eval / single device) the same
    layer runs the dense math on the full weight."""

    def __init__(self, out_features: int, *args, bias: bool = True, name=None,
                 tp_axis: str | None = None, tp_mode: str = "column",
                 out_dtype: str | None = None, **kwargs):
        super().__init__(name)
        # legacy call style Linear(in_features, out_features) (ref layer.py:294)
        if len(args) > 0 and isinstance(args[0], int):
            out_features = args[0]
        self.out_features = out_features
        self.bias = bias
        assert tp_mode in ("column", "row"), tp_mode
        self.tp_axis = tp_axis
        self.tp_mode = tp_mode
        # out_dtype="float32": fp32-accumulated output even under the bf16
        # amp policy (use on loss heads so the CE never upcasts logits)
        self.out_dtype = out_dtype

    def initialize(self, x):
        in_features = x.shape[-1]
        W = Tensor((in_features, self.out_features), device=x.device,
                   dtype=x.dtype)
        initializer.he_uniform(W)
        if self.tp_axis is not None:
            from jax.sharding import PartitionSpec as P
            W.spec = P(None, self.tp_axis) if self.tp_mode == "column" \
                else P(self.tp_axis, None)
        self._register_param("W", W)
        if self.bias:
            b = Tensor((self.out_features,), device=x.device, dtype=x.dtype)
            b.set_value(0.0)
            if self.tp_axis is not None and self.tp_mode == "column":
                from jax.sharding import PartitionSpec as P
                b.spec = P(self.tp_axis)
            self._register_param("b", b)

    def forward(self, x):
        tp = self.tp_axis is not None and autograd.axis_bound(self.tp_axis)
        if tp and self.tp_mode == "column":
            x = autograd.tp_copy(x, self.tp_axis)
        b = self.b if self.bias else None
        x, W, b = autograd.compute_cast(x, self.W, b)
        y = autograd.matmul(x, W, out_dtype=self.out_dtype)
        if tp and self.tp_mode == "row":
            y = autograd.tp_reduce(y, self.tp_axis)
        if b is not None:
            y = autograd.add_bias(y, b, axis=0)
        return y


class Gemm(Layer):
    """alpha*A'B' + beta*C with optional transposes (ref layer.py:364)."""

    def __init__(self, nb_kernels, alpha=1.0, beta=1.0, transA=False,
                 transB=True, bias=True, bias_shape=None, name=None):
        super().__init__(name)
        self.nb_kernels = nb_kernels
        self.alpha, self.beta = alpha, beta
        self.transA, self.transB = int(transA), int(transB)
        self.bias = bias
        self.bias_shape = bias_shape

    def initialize(self, x):
        fan_in = x.shape[-1] if not self.transA else x.shape[0]
        # init in (in, out) layout so he_uniform sees the true fan_in, then
        # lay out as (out, in) when transB
        W = Tensor((fan_in, self.nb_kernels), device=x.device, dtype=x.dtype)
        initializer.he_uniform(W)
        if self.transB:
            W.data = W.data.T
        self._register_param("W", W)
        if self.bias:
            shape = self.bias_shape or (1, self.nb_kernels)
            b = Tensor(shape, device=x.device, dtype=x.dtype)
            b.set_value(0.0)
            self._register_param("b", b)

    def forward(self, x):
        if self.bias:
            return autograd.gemm(x, self.W, self.b, self.alpha, self.beta,
                                 self.transA, self.transB)
        return autograd.gemm(x, self.W, None, self.alpha, self.beta,
                             self.transA, self.transB)


class Embedding(Layer):
    """Token-id -> vector table lookup (ref layer.py:466).

    `tp_axis` row-shards the (V, E) table over that mesh axis
    (Megatron vocab-parallel embedding): each device gathers only ids in
    its vocab range and one psum assembles the activations — the model's
    largest tensor stops being replicated. V must divide by the axis size
    (pad the vocab, e.g. to a multiple of 128, as GPT(vocab_tp=) does).
    `out_dtype="float32"` hands the looked-up rows on as they are under
    `amp` too (a residual stream kept in fp32)."""

    def __init__(self, input_dim, output_dim, initializer_fn=None, name=None,
                 tp_axis: "str | None" = None,
                 out_dtype: "str | None" = None):
        super().__init__(name)
        assert out_dtype in (None, "float32"), out_dtype
        self.input_dim = input_dim
        self.output_dim = output_dim
        self.initializer_fn = initializer_fn
        self.tp_axis = tp_axis
        self.out_dtype = out_dtype

    def initialize(self, x):
        W = Tensor((self.input_dim, self.output_dim), device=x.device,
                   dtype=tensor_module.float32)
        (self.initializer_fn or initializer.glorot_uniform)(W)
        if self.tp_axis is not None:
            from jax.sharding import PartitionSpec as P
            W.spec = P(self.tp_axis, None)
        self._register_param("W", W)

    def forward(self, x):
        # cast AFTER the lookup: (B,S,D) activations, not the (V,D) table
        if self.tp_axis is not None and autograd.axis_bound(self.tp_axis):
            y = autograd.vocab_parallel_embedding(x, self.W, self.tp_axis)
        else:
            y = autograd.embedding(x, self.W)
        return y if self.out_dtype else autograd.compute_cast(y)


class _ConvGeometry:
    """Carries conv geometry; plays the role of ConvHandle
    (src/model/operation/convolution.h:43) minus the cuDNN descriptors."""

    def __init__(self, stride, padding, group, odd_padding=None,
                 dilation=(1, 1)):
        self.stride = stride
        self.padding = padding
        self.group = group
        self.odd_padding = odd_padding
        self.dilation = dilation


def _pair(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v)


class Conv2d(Layer):
    """NCHW convolution, optional fused activation (ref layer.py:508; fused
    relu used by examples/cnn/model/cnn.py:31)."""

    def __init__(self, nb_kernels, kernel_size, *args, stride=1, padding=0,
                 dilation=1, group=1, bias=True, pad_mode="NOTSET",
                 activation="NONE", name=None, **kwargs):
        super().__init__(name)
        # legacy call style Conv2d(in_ch, out_ch, k[, stride[, padding]])
        # (ref layer.py:551-560); in_ch is re-derived from the input anyway
        if len(args) > 0:
            nb_kernels = kernel_size
            kernel_size = args[0]
        if len(args) > 1:
            stride = args[1]
        if len(args) > 2:
            padding = args[2]
        self.nb_kernels = nb_kernels
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride)
        self.padding = _pair(padding)
        self.dilation = _pair(dilation)  # rhs_dilation (atrous conv),
        # parity with ConvHandle dilation (convolution.h:43)
        self.group = group
        self.bias = bias
        self.pad_mode = pad_mode
        self.activation = activation

    def _same_odd_padding(self, x):
        # ONNX SAME_UPPER/SAME_LOWER: compute per-side pads (l, r, t, b)
        # from the EFFECTIVE (dilated) kernel extent
        ih, iw = x.shape[2], x.shape[3]
        dh, dw = self.dilation
        kh = (self.kernel_size[0] - 1) * dh + 1
        kw = (self.kernel_size[1] - 1) * dw + 1
        sh, sw = self.stride
        oh, ow = -(-ih // sh), -(-iw // sw)
        ph = max((oh - 1) * sh + kh - ih, 0)
        pw = max((ow - 1) * sw + kw - iw, 0)
        if self.pad_mode == "SAME_UPPER":
            return (pw // 2, pw - pw // 2, ph // 2, ph - ph // 2)
        return (pw - pw // 2, pw // 2, ph - ph // 2, ph // 2)

    def initialize(self, x):
        in_channels = x.shape[1]
        assert in_channels % self.group == 0
        w_shape = (self.nb_kernels, in_channels // self.group,
                   *self.kernel_size)
        W = Tensor(w_shape, device=x.device, dtype=x.dtype)
        initializer.he_normal(W)
        self._register_param("W", W)
        if self.bias:
            b = Tensor((self.nb_kernels,), device=x.device, dtype=x.dtype)
            b.set_value(0.0)
            self._register_param("b", b)
        odd = None
        if self.pad_mode in ("SAME_UPPER", "SAME_LOWER"):
            odd = self._same_odd_padding(x)
        self.handle = _ConvGeometry(self.stride, self.padding, self.group,
                                    odd, self.dilation)
        self.handle.kernel = self.kernel_size  # for same_pad_shape_check

    def forward(self, x):
        b = self.b if self.bias else None
        x, W, b = autograd.compute_cast(x, self.W, b)
        y = autograd.conv2d(self.handle, x, W, b)
        if self.activation in ("RELU", "relu"):
            y = autograd.relu(y)
        return y


class SeparableConv2d(Layer):
    """Depthwise + pointwise conv (ref layer.py:740)."""

    def __init__(self, nb_kernels, kernel_size, *args, stride=1, padding=0,
                 bias=False, name=None, **kwargs):
        super().__init__(name)
        # legacy call style SeparableConv2d(in_ch, out_ch, k[, stride[, pad]])
        if len(args) > 0:
            nb_kernels = kernel_size
            kernel_size = args[0]
        if len(args) > 1:
            stride = args[1]
        if len(args) > 2:
            padding = args[2]
        self.nb_kernels = nb_kernels
        self.kernel_size = kernel_size
        self.stride = stride
        self.padding = padding
        self.bias = bias

    def initialize(self, x):
        in_channels = x.shape[1]
        # nb_kernels None = keep channel count (used by blocks whose input
        # width is only known at first call, e.g. xception middle reps)
        nb = self.nb_kernels if self.nb_kernels is not None else in_channels
        self.depthwise = Conv2d(in_channels, self.kernel_size,
                                stride=self.stride, padding=self.padding,
                                group=in_channels, bias=self.bias)
        self.pointwise = Conv2d(nb, 1, bias=self.bias)

    def forward(self, x):
        return self.pointwise(self.depthwise(x))


class BatchNorm2d(Layer):
    """BN over NCHW channel dim; running stats are layer states
    (ref layer.py:802)."""

    def __init__(self, *args, momentum=0.9, eps=1e-5, name=None, **kwargs):
        super().__init__(name)
        # legacy call style BatchNorm2d(num_features[, momentum]); channel
        # count is re-derived from the input at initialize()
        if len(args) > 1:
            momentum = args[1]
        self.momentum = momentum
        self.eps = eps

    def initialize(self, x):
        c = x.shape[1]
        scale = Tensor((c,), device=x.device, dtype=x.dtype)
        scale.set_value(1.0)
        self._register_param("scale", scale)
        bias = Tensor((c,), device=x.device, dtype=x.dtype)
        bias.set_value(0.0)
        self._register_param("bias", bias)
        rm = Tensor((c,), device=x.device, dtype=x.dtype)
        rm.set_value(0.0)
        self._register_state("running_mean", rm)
        rv = Tensor((c,), device=x.device, dtype=x.dtype)
        rv.set_value(1.0)
        self._register_state("running_var", rv)

    def forward(self, x):
        y, new_m, new_v = autograd.batchnorm_2d(
            x, self.scale, self.bias, self.running_mean, self.running_var,
            self.momentum, self.eps, train=autograd.training)
        self.running_mean.data = new_m
        self.running_var.data = new_v
        return y


class Pooling2d(Layer):
    """(ref layer.py:891)"""

    def __init__(self, kernel_size, stride=None, padding=0, is_max=True,
                 pad_mode="NOTSET", name=None):
        super().__init__(name)
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride) if stride is not None else self.kernel_size
        self.padding = _pair(padding)
        self.is_max = is_max
        self.pad_mode = pad_mode

    def forward(self, x):
        odd = None
        if self.pad_mode in ("SAME_UPPER", "SAME_LOWER"):
            ih, iw = x.shape[2], x.shape[3]
            kh, kw = self.kernel_size
            sh, sw = self.stride
            ph = np.maximum((-(-ih // sh) - 1) * sh + kh - ih, 0)
            pw = np.maximum((-(-iw // sw) - 1) * sw + kw - iw, 0)
            if self.pad_mode == "SAME_UPPER":
                odd = (pw // 2, pw - pw // 2, ph // 2, ph - ph // 2)
            else:
                odd = (pw - pw // 2, pw // 2, ph - ph // 2, ph // 2)
        return autograd.pooling_2d(x, self.kernel_size, self.stride,
                                   self.padding, self.is_max, odd_padding=odd)


class MaxPool2d(Pooling2d):
    def __init__(self, kernel_size, stride=None, padding=0, name=None):
        super().__init__(kernel_size, stride, padding, True, name=name)


class AvgPool2d(Pooling2d):
    def __init__(self, kernel_size, stride=None, padding=0, name=None):
        super().__init__(kernel_size, stride, padding, False, name=name)


class _Pool1dMixin:
    def forward(self, x):  # N, C, L -> unsqueeze W
        x4 = autograd.unsqueeze(x, [3])
        y = autograd.pooling_2d(x4, (self.kernel_size[0], 1),
                                (self.stride[0], 1), (self.padding[0], 0),
                                self.is_max)
        return autograd.squeeze(y, 3)


class MaxPool1d(_Pool1dMixin, Pooling2d):
    def __init__(self, kernel_size, stride=None, padding=0, name=None):
        Pooling2d.__init__(self, (kernel_size, 1),
                           (stride, 1) if stride else (kernel_size, 1),
                           (padding, 0), True, name=name)


class AvgPool1d(_Pool1dMixin, Pooling2d):
    def __init__(self, kernel_size, stride=None, padding=0, name=None):
        Pooling2d.__init__(self, (kernel_size, 1),
                           (stride, 1) if stride else (kernel_size, 1),
                           (padding, 0), False, name=name)


class GlobalAvgPool2d(Layer):
    def forward(self, x):
        y = autograd.globalaveragepool(x)
        return autograd.flatten(y, 1)


# ---- stateless wrappers (ref layer.py:1403-1548) -------------------------


class ReLU(Layer):
    def forward(self, x):
        return autograd.relu(x)


class Sigmoid(Layer):
    def forward(self, x):
        return autograd.sigmoid(x)


class Tanh(Layer):
    def forward(self, x):
        return autograd.tanh(x)


class Add(Layer):
    def forward(self, a, b):
        return autograd.add(a, b)


class Flatten(Layer):
    def __init__(self, axis=1, name=None):
        super().__init__(name)
        self.axis = axis

    def forward(self, x):
        return autograd.flatten(x, self.axis)


class Reshape(Layer):
    def __init__(self, shape, name=None):
        super().__init__(name)
        self.shape = shape

    def forward(self, x):
        return autograd.reshape(x, self.shape)


class Cat(Layer):
    def __init__(self, axis=0, name=None):
        super().__init__(name)
        self.axis = axis

    def forward(self, xs):
        return autograd.cat(xs, self.axis)


class Dropout(Layer):
    def __init__(self, ratio=0.5, name=None):
        super().__init__(name)
        self.ratio = ratio

    def forward(self, x):
        return autograd.dropout(x, self.ratio)


class SoftMax(Layer):
    def __init__(self, axis=1, name=None):
        super().__init__(name)
        self.axis = axis

    def forward(self, x):
        return autograd.softmax(x, self.axis)


class SoftMaxCrossEntropy(Layer):
    """Mean softmax cross-entropy; with `weight` (one a row) the mean of
    weight x cross-entropy over all rows."""

    def forward(self, x, t, weight=None):
        return autograd.softmax_cross_entropy(x, t, weight)


class MeanSquareError(Layer):
    def forward(self, x, t):
        return autograd.mse_loss(x, t)


class CrossEntropy(Layer):
    def forward(self, p, t):
        return autograd.cross_entropy(p, t)


class BinaryCrossEntropy(Layer):
    def forward(self, x, t):
        return autograd.binary_cross_entropy(x, t)


# ---- transformer stack (no reference counterpart; long-context is
# first-class in this framework — SURVEY.md §5 notes the reference has no
# attention op at all) ------------------------------------------------------


class LayerNorm(Layer):
    def __init__(self, eps=1e-5, name=None):
        super().__init__(name)
        self.eps = eps

    def initialize(self, x):
        d = x.shape[-1]
        g = Tensor((d,), device=x.device, dtype=x.dtype)
        g.set_value(1.0)
        self._register_param("gamma", g)
        b = Tensor((d,), device=x.device, dtype=x.dtype)
        b.set_value(0.0)
        self._register_param("beta", b)

    def forward(self, x):
        return autograd.layernorm(x, self.gamma, self.beta, self.eps)


class RMSNorm(Layer):
    """x / rms(x) * gamma (no mean, no shift)."""

    def __init__(self, eps=1e-6, name=None):
        super().__init__(name)
        self.eps = eps

    def initialize(self, x):
        g = Tensor((x.shape[-1],), device=x.device, dtype=x.dtype)
        g.set_value(1.0)
        self._register_param("gamma", g)

    def forward(self, x):
        return autograd.rmsnorm(x, self.gamma, self.eps)


class MultiHeadAttention(Layer):
    """Self-attention over (B, S, E); the core runs as ONE fused tape op
    (flash attention / ring attention when seq_axis is a mesh axis).

    `tp_axis` shards the heads Megatron-style: Wq/Wk/Wv column-parallel
    (each device computes num_heads/tp local heads, zero comm), Wo
    row-parallel (one psum). Composes with `seq_axis` ring attention.

    `num_kv_heads` (grouped-query attention, GQA; = num_heads is MHA,
    = 1 is MQA): Wk/Wv project to num_kv_heads*D and each KV head
    serves num_heads/num_kv_heads query heads. This shrinks the KV
    params AND — the real point — the serving KV cache, which is the
    binding term of the decode roofline.

    `head_dim`: the width of a head where it is not `E / num_heads` (q is
    then num_heads x head_dim wide, Wo maps that back to E).

    The mask is ONE of `autograd.attention_mask`'s kinds, named by
    (`causal`, `window`, `block_diffusion`) and checked here: `causal`
    alone; `window` with `causal`: a query sees its last `window` keys,
    itself among them; `block_diffusion` (without `causal` or a window):
    the input is a doubled sequence [noised ; clean] in blocks of that
    length under the mask of block-diffusion training, and both halves
    carry the rotary positions 0..S/2-1. The flash kernels visit no tile
    outside the mask. `rope_scaling`: YaRN's parameters for the rotary
    tables (autograd.rope_tables). `qk_norm` (None: no such norm): an RMS
    norm with a learned gain and the eps `qk_norm_eps` (None: RMSNorm's)
    over q and over k (`q_norm`, `k_norm`), before the rotary: over each
    head ("head") or over the whole projection before it is split into
    heads ("projection", OLMo 2's; not under `tp_axis`, where a device
    holds a slice of it)."""

    def __init__(self, num_heads, causal=False, seq_axis=None, tp_axis=None,
                 bias=False, num_kv_heads=None, rope=False,
                 rope_theta=10000.0, head_dim=None, window=None,
                 rope_scaling=None, block_diffusion=None, qk_norm=None,
                 qk_norm_eps=None, name=None):
        super().__init__(name)
        self.num_heads = num_heads
        self.head_dim, self.window = head_dim, window
        self.block_diffusion = block_diffusion
        # refuses, here and not at the first forward, what is not ONE mask
        autograd.attention_mask(causal, window, block_diffusion)
        assert qk_norm in (None, "head", "projection"), qk_norm
        assert qk_norm != "projection" or tp_axis is None, \
            "a whole-projection qk norm needs the whole projection"
        self.qk_norm = qk_norm
        if qk_norm:
            eps = {} if qk_norm_eps is None else dict(eps=qk_norm_eps)
            self.q_norm, self.k_norm = RMSNorm(**eps), RMSNorm(**eps)
        self.rope_scaling = rope_scaling
        self.rope = bool(rope)          # rotary q/k (RoFormer/NeoX)
        self.rope_theta = rope_theta
        self.num_kv_heads = num_kv_heads or num_heads
        assert num_heads % self.num_kv_heads == 0, \
            f"num_heads {num_heads} not divisible by " \
            f"num_kv_heads {self.num_kv_heads}"
        self.causal = causal
        self.seq_axis = seq_axis
        self.tp_axis = tp_axis
        self.use_bias = bias  # GPT-2-style projection biases

    def initialize(self, x):
        e = x.shape[-1]
        assert self.head_dim or e % self.num_heads == 0
        d = self.head_dim or e // self.num_heads
        q_e, kv_e = self.num_heads * d, self.num_kv_heads * d
        spec_col = spec_row = spec_colb = None
        if self.tp_axis is not None:
            from jax.sharding import PartitionSpec as P
            spec_col = P(None, self.tp_axis)
            spec_row = P(self.tp_axis, None)
            spec_colb = P(self.tp_axis)
        for attr in ("Wq", "Wk", "Wv", "Wo"):
            out_e = kv_e if attr in ("Wk", "Wv") else q_e
            W = Tensor((q_e, e) if attr == "Wo" else (e, out_e),
                       device=x.device, dtype=x.dtype)
            initializer.glorot_uniform(W)
            W.spec = spec_row if attr == "Wo" else spec_col
            self._register_param(attr, W)
            if self.use_bias:
                b = Tensor((e if attr == "Wo" else out_e,), device=x.device,
                           dtype=x.dtype)
                b.set_value(0.0)
                # q/k/v biases shard with the heads (column); the output
                # bias is added after the row-parallel psum: replicated
                b.spec = None if attr == "Wo" else spec_colb
                self._register_param("b" + attr[1].lower(), b)

    def _split(self, t, B, S, heads):
        t = autograd.reshape(t, (B, S, heads, -1))
        return autograd.transpose(t, (0, 2, 1, 3))  # (B,H,S,D)

    def forward(self, x):
        B, S, E = x.shape
        tp = self.tp_axis is not None and autograd.axis_bound(self.tp_axis)
        heads = self.num_heads
        if tp:
            import jax
            tp_size = jax.lax.axis_size(self.tp_axis)
            assert heads % tp_size == 0, \
                f"{heads} heads not divisible by tp={tp_size}"
            heads //= tp_size
            x = autograd.tp_copy(x, self.tp_axis)
        x, Wq, Wk, Wv, Wo = autograd.compute_cast(
            x, self.Wq, self.Wk, self.Wv, self.Wo)

        def proj(W, b):
            y = autograd.matmul(x, W)
            if b is not None:
                y = autograd.add_bias(y, autograd.compute_cast(b), axis=0)
            return y

        bq = bk = bv = bo = None
        if self.use_bias:
            bq, bk, bv, bo = self.bq, self.bk, self.bv, self.bo
        kv_heads = self.num_kv_heads
        grp = self.num_heads // self.num_kv_heads
        if tp:
            assert kv_heads % tp_size == 0, \
                f"{kv_heads} kv heads not divisible by tp={tp_size}"
            kv_heads //= tp_size
        q = proj(Wq, bq)
        whole = self.qk_norm == "projection"   # normed before the split
        if whole:
            q = self.q_norm(q)
        q = self._split(q, B, S, heads)
        k = proj(Wk, bk)
        if whole:
            k = self.k_norm(k)
        k = self._split(k, B, S, kv_heads)
        v = self._split(proj(Wv, bv), B, S, kv_heads)
        if self.qk_norm == "head":
            q, k = self.q_norm(q), self.k_norm(k)
        if self.rope:
            # rotate q/k before the kv-head repeat (rotation is per-head
            # identical, so rotating the Hkv heads is cheaper)
            rope_args = (self.rope_theta, self.seq_axis, self.rope_scaling,
                         S // 2 if self.block_diffusion else None)
            q, k = autograd.Rope(*rope_args)(q), autograd.Rope(*rope_args)(k)
        if grp > 1:
            # GQA: each kv head serves `grp` consecutive query heads
            # (repeat on the head axis; XLA folds the broadcast)
            k = autograd.UpSample([1, grp, 1, 1])(k)
            v = autograd.UpSample([1, grp, 1, 1])(v)
        o = autograd.attention(q, k, v, causal=self.causal,
                               seq_axis=self.seq_axis, window=self.window,
                               block_diffusion=self.block_diffusion)
        o = autograd.transpose(o, (0, 2, 1, 3))
        o = autograd.reshape(o, (B, S, -1))
        y = autograd.matmul(o, Wo)
        if tp:
            y = autograd.tp_reduce(y, self.tp_axis)
        if bo is not None:
            y = autograd.add_bias(y, autograd.compute_cast(bo), axis=0)
        return y


class ShortConv(Layer):
    """The gated short convolution over (B, S, E) (ops/shortconv.py): the
    input projected to three streams (B, C, u) = split3(x W_in), z = B * u,
    a depthwise causal convolution of `taps` taps over z along the
    sequence (zeros before its start), the result gated by C and projected
    by W_out. No bias. ONE tape operator, its device time under
    `in_proj`, `mix` and `out_proj`. The state a decoder would carry for
    it is the last `taps - 1` rows of z a sequence; nothing here serves it
    (serving.py and engine.py hold keys and values only), packs documents
    through it (the taps cross a boundary) or exports it (sonnx refuses
    it by name)."""

    def __init__(self, taps=3, name=None):
        super().__init__(name)
        self.taps = taps

    def initialize(self, x):
        e = x.shape[-1]
        for attr, shape in (("W_in", (e, 3 * e)), ("W_out", (e, e))):
            W = Tensor(shape, device=x.device, dtype=x.dtype)
            initializer.glorot_uniform(W)
            self._register_param(attr, W)
        # a depthwise filter's fan-in is its taps
        w = Tensor((e, self.taps), device=x.device, dtype=x.dtype)
        w.uniform(-self.taps ** -0.5, self.taps ** -0.5)
        self._register_param("w", w)

    def forward(self, x):
        # the taps stay as they are (fp32 under `amp`): the chain between
        # the products computes in fp32
        x, W_in, W_out = autograd.compute_cast(x, self.W_in, self.W_out)
        return _ShortConvOp()(x, W_in, self.w, W_out)


class _ShortConvOp(autograd.Operator):
    def __init__(self):
        super().__init__("ShortConv")

    def forward(self, x, W_in, w, W_out):
        from .ops.shortconv import short_conv
        return short_conv(x, W_in, w, W_out)


class GatedDeltaNet(Layer):
    """Gated delta-rule linear attention over (B, S, E)
    (ops/gated_delta.py): `heads` heads of `key_dim` (q, k) and
    `value_dim` (v, o), each carrying a (value_dim x key_dim) state through
    the sequence, updated by the delta rule under a decay alpha = exp(g)
    and a write strength beta (in (0, 2) with `neg_eigval`, else (0, 1));
    q, k and v through causal depthwise taps of `conv_taps` and SiLU, q
    and k L2-normalised; the output RMS-normalised a head (`norm_eps`) and
    gated by SiLU(x W_g). The recurrence runs in chunks of `chunk` tokens,
    in fp32. No bias. ONE tape operator, its device time under `proj`,
    `conv`, `delta`, `norm_gate` and `out_proj`. Nothing here serves it:
    a decoder would carry the state and the taps' last rows a sequence
    (ROADMAP B-M6)."""

    def __init__(self, heads, key_dim, value_dim, conv_taps=4,
                 neg_eigval=True, chunk=64, norm_eps=1e-6, name=None):
        super().__init__(name)
        self.heads, self.key_dim, self.value_dim = heads, key_dim, value_dim
        self.taps, self.neg_eigval = conv_taps, neg_eigval
        self.chunk, self.norm_eps = chunk, norm_eps

    def initialize(self, x):
        e, h = x.shape[-1], self.heads
        dk, dv = h * self.key_dim, h * self.value_dim
        for attr, shape in (("W_q", (e, dk)), ("W_k", (e, dk)),
                            ("W_v", (e, dv)), ("W_a", (e, h)),
                            ("W_b", (e, h)), ("W_g", (e, dv)),
                            ("W_o", (dv, e))):
            W = Tensor(shape, device=x.device, dtype=x.dtype)
            initializer.glorot_uniform(W)
            self._register_param(attr, W)
        # a depthwise filter's fan-in is its taps
        for attr, width in (("conv_q", dk), ("conv_k", dk), ("conv_v", dv)):
            w = Tensor((width, self.taps), device=x.device, dtype=x.dtype)
            w.uniform(-self.taps ** -0.5, self.taps ** -0.5)
            self._register_param(attr, w)
        # the decay's rate A in [1, 16] and its step dt in [1e-3, 0.1]
        # log-uniform, dt_bias its inverse softplus (the Mamba-2 / Gated
        # DeltaNet initialisers)
        A = Tensor((h,), device=x.device, dtype=x.dtype).uniform(1.0, 16.0)
        A.data = jnp.log(A.data)
        self._register_param("A_log", A)
        dt = Tensor((h,), device=x.device, dtype=x.dtype).uniform(
            math.log(1e-3), math.log(0.1))
        dt.data = jnp.exp(dt.data)
        dt.data = dt.data + jnp.log(-jnp.expm1(-dt.data))
        self._register_param("dt_bias", dt)
        g = Tensor((self.value_dim,), device=x.device, dtype=x.dtype)
        g.set_value(1.0)
        self._register_param("o_norm", g)

    def forward(self, x):
        # the taps, gates and gain stay as they are (fp32 under `amp`): the
        # recurrence computes in fp32
        x, W_q, W_k, W_v, W_a, W_b, W_g, W_o = autograd.compute_cast(
            x, self.W_q, self.W_k, self.W_v, self.W_a, self.W_b, self.W_g,
            self.W_o)
        return _GatedDeltaOp(self)(
            x, W_q, W_k, W_v, W_a, W_b, W_g, W_o, self.conv_q, self.conv_k,
            self.conv_v, self.A_log, self.dt_bias, self.o_norm)


class _GatedDeltaOp(autograd.Operator):
    def __init__(self, layer):
        super().__init__("GatedDeltaNet")
        self.kw = dict(heads=layer.heads, neg_eigval=layer.neg_eigval,
                       chunk=layer.chunk, norm_eps=layer.norm_eps)

    def forward(self, *xs):
        from .ops.gated_delta import gated_delta_net
        return gated_delta_net(*xs, **self.kw)


class Mixer:
    """A block's token mixer, described: `build(tp_axis, norm_eps)` makes
    its layer (under the block's `tp_axis` and its norms' eps), which the
    block holds under the attribute `attr`."""

    attr = None

    def build(self, tp_axis, norm_eps):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class Attention(Mixer):
    """Softmax attention (`MultiHeadAttention`, `attn`): its arguments, but
    for the block's `tp_axis` and its norms' eps, which the q and k norms
    take. A window beside `block_diffusion`, or any other pair that is not
    ONE mask, is refused when the block is made."""

    num_heads: int
    causal: bool = True
    num_kv_heads: int | None = None
    head_dim: int | None = None
    window: int | None = None
    block_diffusion: int | None = None
    rope: bool = False
    rope_theta: float = 10000.0
    rope_scaling: dict | None = None
    qk_norm: str | None = None
    bias: bool = False
    seq_axis: str | None = None

    attr = "attn"

    def build(self, tp_axis, norm_eps):
        return MultiHeadAttention(tp_axis=tp_axis, qk_norm_eps=norm_eps,
                                  **vars(self))


@dataclasses.dataclass(frozen=True)
class Convolution(Mixer):
    """The gated short convolution of `taps` taps (`ShortConv`, `conv`)."""

    taps: int = 3

    attr = "conv"

    def build(self, tp_axis, norm_eps):
        return ShortConv(self.taps)


@dataclasses.dataclass(frozen=True)
class DeltaRule(Mixer):
    """Gated delta-rule linear attention (`GatedDeltaNet`, `linear_attn`):
    its arguments."""

    heads: int
    key_dim: int
    value_dim: int
    conv_taps: int = 4
    neg_eigval: bool = True
    chunk: int = 64
    norm_eps: float = 1e-6

    attr = "linear_attn"

    def build(self, tp_axis, norm_eps):
        return GatedDeltaNet(**vars(self))


@dataclasses.dataclass(frozen=True)
class FeedForward:
    """A block's dense feed-forward: "gelu", gelu(x W1) W2 (`fc1`, `fc2`),
    or "swiglu", (silu(x W_gate) * (x W_up)) W_down (`fc_gate`, `fc1`,
    `fc2`); `dim` wide (None: `ratio` x the block's width)."""

    kind: str = "gelu"
    dim: int | None = None
    ratio: int = 4
    bias: bool = True

    def __post_init__(self):
        assert self.kind in ("gelu", "swiglu"), self.kind


@dataclasses.dataclass(frozen=True)
class Experts:
    """A block's mixture-of-experts feed-forward (`moe`): `num` experts of
    width `dim` (None: `ratio` x the block's), top-`k`. `dropless`: a
    `DroplessMoE`, of which this device holds `held` experts from
    `offset` on (None: all), routed by `router` (its `score`, `bias`,
    `scale`, `gate_eps`); else a capacity-bound `MoE` (`capacity_factor`,
    expert-parallel over `ep_axis`), whose router losses surface on
    `moe.{aux_loss,z_loss}` after forward."""

    num: int
    k: int = 1
    dim: int | None = None
    ratio: int = 4
    dropless: bool = False
    held: int | None = None
    offset: int = 0
    router: dict | None = None
    capacity_factor: float = 1.25
    ep_axis: str | None = None

    def build(self):
        if self.dropless:
            return DroplessMoE(self.num, k=self.k, held=self.held,
                               offset=self.offset, **(self.router or {}))
        return MoE(self.num, capacity_factor=self.capacity_factor,
                   ep_axis=self.ep_axis, k=self.k)


@dataclasses.dataclass(frozen=True)
class Norms:
    """A block's norms: `kind` "layer" or "rms" with `eps` (None: the
    class's own); `pre`: before each branch (`ln1`, `ln2`); `post`: on
    each branch's output before it joins the residual (`ln1_post`,
    `ln2_post`)."""

    kind: str = "layer"
    eps: float | None = None
    pre: bool = True
    post: bool = False

    def __post_init__(self):
        assert self.kind in ("layer", "rms"), self.kind

    def make(self):
        cls = LayerNorm if self.kind == "layer" else RMSNorm
        return cls() if self.eps is None else cls(self.eps)


class TransformerBlock(Layer):
    """x + Mix(N1(x)); x + FFN(N2(x)), from three descriptions: the mixer
    (`Attention`, `Convolution`, `DeltaRule`), the feed-forward
    (`FeedForward`, `Experts`) and the norms (`Norms`; with `pre=False,
    post=True` OLMo 2's block, x + N(Mix(x)); x + N(FFN(x))). The defaults
    are the GPT-2 block's: LayerNorms before each branch, a biased GELU
    feed-forward 4x wide. `tp_axis` makes the attention head-parallel and
    the dense feed-forward column -> row parallel (two psums a block, the
    Megatron layout)."""

    def __init__(self, mixer, ffn=FeedForward(), norms=Norms(),
                 tp_axis=None, name=None):
        super().__init__(name)
        assert isinstance(mixer, Mixer), mixer
        self.mixer, self.ffn, self.norms = mixer, ffn, norms
        self.tp_axis = tp_axis
        if norms.pre:
            self.ln1 = norms.make()
        setattr(self, mixer.attr, mixer.build(tp_axis, norms.eps))
        if norms.pre:
            self.ln2 = norms.make()
        if norms.post:
            self.ln1_post, self.ln2_post = norms.make(), norms.make()
        if isinstance(ffn, Experts):
            self.moe = ffn.build()

    def initialize(self, x):
        e, ffn = x.shape[-1], self.ffn
        width = ffn.dim or e * ffn.ratio
        if isinstance(ffn, Experts):
            self.moe.hidden = width
            return
        wide = dict(bias=ffn.bias, tp_axis=self.tp_axis, tp_mode="column")
        if ffn.kind == "swiglu":
            self.fc_gate = Linear(width, **wide)
        self.fc1 = Linear(width, **wide)
        self.fc2 = Linear(e, bias=ffn.bias, tp_axis=self.tp_axis,
                          tp_mode="row")

    def forward(self, x):
        pre, post = self.norms.pre, self.norms.post
        a = getattr(self, self.mixer.attr)(self.ln1(x) if pre else x)
        x = autograd.add(x, self.ln1_post(a) if post else a)
        h = self.ln2(x) if pre else x
        if isinstance(self.ffn, Experts):
            m = self.moe(h)
        elif self.ffn.kind == "swiglu":
            m = self.fc2(autograd.swiglu(self.fc_gate(h), self.fc1(h)))
        else:
            m = self.fc2(autograd.gelu(self.fc1(h)))
        return autograd.add(x, self.ln2_post(m) if post else m)


class DroplessMoE(Layer):
    """Mixture-of-experts feed-forward that drops nothing: every token goes
    to its top-`k` of `num_experts` experts (router and softmax in fp32
    over all of them, the k gates renormalised to sum to 1), the (token,
    choice) pairs are sorted by expert and the SiLU-gated experts
    (silu(x Wg_e) * (x Wu_e)) Wd_e run as grouped matrix products over the
    rows really routed (parallel/moe.py `dropless_moe`). No capacity, no
    (tokens, experts, capacity) tensor, no auxiliary loss. The sorted
    buffer is sized for every pair at a held expert; the backward's passes
    over its rows (the cotangent gathered row by row, the gate's backward,
    the sum of the two input gradients) work on a rung, the least of 1/8,
    1/4, 1/2 and the whole of it that holds the step's routed rows, chosen
    on the device from the count the layer already has. The top rung is
    the whole buffer, so no count overflows a rung and there is nothing to
    configure.

    `held`, `offset`: this device holds experts offset .. offset + held - 1
    of `num_experts` (None: all). The router still scores all of them; the
    layer computes its own experts' part of the sum and hands that partial
    sum on: summed over the devices that share the layer it is the whole
    layer. Nothing here stands in for the other devices or the exchange.
    After forward `self.rows` holds the rows routed to each held expert
    (float32, off the tape).

    The routing (parallel/moe.py `route_topk`): `score` "softmax" over all
    experts or "sigmoid" of each; the k gates divided by their sum +
    `gate_eps`, times `scale`. `bias=True` adds a selection bias `b`
    (num_experts,): added to the scores for the top-k alone, never in the
    gates. It is a STATE, not a parameter (`_register_state`, as
    BatchNorm2d's running statistics: saved, restored and carried by the
    graph step, with no gradient and in no optimizer's state), zero on a
    fresh layer; `update_bias(rate)` moves it by the load of the latest
    forward, `self.load` (num_experts,): the pairs sent to each of ALL the
    experts, held or not (float32, off the tape; None without a bias)."""

    def __init__(self, num_experts, hidden=None, k=1, held=None, offset=0,
                 score="softmax", bias=False, scale=1.0, gate_eps=0.0,
                 name=None):
        super().__init__(name)
        self.num_experts, self.hidden, self.k = num_experts, hidden, k
        self.held = num_experts if held is None else held
        self.offset = offset
        assert 0 <= offset and offset + self.held <= num_experts, \
            (num_experts, held, offset)
        self.use_bias = bias
        self.route = dict(score=score, scale=scale, eps=gate_eps)
        self.rows = self.load = None

    def initialize(self, x):
        d, h, H = x.shape[-1], self.hidden or 4 * x.shape[-1], self.held
        Wr = Tensor((d, self.num_experts), device=x.device, dtype=x.dtype)
        initializer.glorot_uniform(Wr)
        self._register_param("Wr", Wr)
        for attr, shape, fan in (("Wg", (H, d, h), d), ("Wu", (H, d, h), d),
                                 ("Wd", (H, h, d), h)):
            W = Tensor(shape, device=x.device, dtype=x.dtype)
            W.gaussian(0.0, (2.0 / fan) ** 0.5)
            self._register_param(attr, W)
        if self.use_bias:
            b = Tensor((self.num_experts,), device=x.device, dtype=x.dtype)
            b.set_value(0.0)
            self._register_state("b", b)

    def forward(self, x):
        # the router reads x and its own weight as they are (fp32 under
        # `amp`); the experts take the compute dtype
        Wg, Wu, Wd = autograd.compute_cast(self.Wg, self.Wu, self.Wd)
        y, *counts = _DroplessMoEOp(self.k, self.offset, **self.route)(
            x, self.Wr, Wg, Wu, Wd, *([self.b] if self.use_bias else []))
        # off the tape: a region that hands the rows out must not walk the
        # layer's backward a second time for them
        off = lambda c: Tensor(data=c.data, device=x.device,
                               requires_grad=False)
        self.rows = off(counts[0])
        self.load = off(counts[1]) if self.use_bias else None
        return y

    def update_bias(self, rate, load=None):
        """b_e += rate x sign(mean(load) - load_e) over ALL the experts:
        one that took more than its share is chosen less often from the
        next step on, at the same gates (auxiliary-loss-free balancing,
        arXiv:2408.15664). `load`: a forward's `self.load` (the latest
        one's when None). Call it after the optimizer's step."""
        import jax.numpy as jnp
        load = (self.load if load is None else load).data
        self.b.data = self.b.data + rate * jnp.sign(
            jnp.mean(load) - load).astype(self.b.data.dtype)


class _DroplessMoEOp(autograd.Operator):
    def __init__(self, k, offset, **route):
        super().__init__("DroplessMoE")
        self.k, self.offset, self.route = k, offset, route

    def forward(self, x, Wr, Wg, Wu, Wd, bias=None):
        from .parallel.moe import dropless_moe
        y, *counts = dropless_moe(x.reshape(-1, x.shape[-1]), Wr, Wg, Wu, Wd,
                                  self.k, self.offset, bias, **self.route)
        return (y.reshape(x.shape).astype(x.dtype), *counts)


class MoE(Layer):
    """Switch-style mixture-of-experts FFN over (..., D) activations.

    THIS layer's routing has a capacity and DROPS on overflow: a (token,
    choice) pair that finds its expert's queue full passes through with no
    expert output, on one device as under `ep_axis` (`overflow` says how
    many). `DroplessMoE` is the routing that drops nothing (sorted
    dispatch, grouped products; one device's share of the experts, no
    exchange across devices yet). The ONNX exporter refuses both
    (sonnx/frontend.py: a model holding either layer has no ONNX form).

    `ep_axis` shards experts over that mesh axis (all_to_all dispatch,
    parallel/moe.py); out of mesh scope it falls back to the dense path.
    `k` routes each token to its top-k experts with renormalized gates
    (k=1: Switch; k=2: GShard/ST-MoE default). After forward,
    `self.aux_loss` holds the load-balancing loss and `self.z_loss` the
    router z-loss as tape Tensors — add `autograd.mul(moe.aux_loss, w)`
    (and optionally the z-loss, ST-MoE weight ~1e-3) into the training
    loss INSIDE train_one_batch (they participate in the same trace;
    reading them outside a jitted step is undefined); `self.overflow` is
    the dropped-route fraction for monitoring. To TRAIN under ep_axis on a
    {data, ep} mesh, the gradient reduction must cover BOTH axes:
    `DistOpt(axis=(data_axis, ep_axis), mesh=mesh)` — reducing over data
    alone leaves expert grads (and every replicated param) diverging
    across the ep axis.
    """

    def __init__(self, num_experts, hidden=None, capacity_factor=1.25,
                 ep_axis=None, k=1, name=None):
        super().__init__(name)
        self.num_experts = num_experts
        self.hidden = hidden
        self.capacity_factor = capacity_factor
        self.ep_axis = ep_axis
        self.k = k
        self.aux_loss = None
        self.z_loss = None
        self.overflow = None

    def initialize(self, x):
        d = x.shape[-1]
        h = self.hidden or 4 * d
        E = self.num_experts
        Wg = Tensor((d, E), device=x.device, dtype=x.dtype)
        initializer.glorot_uniform(Wg)
        self._register_param("Wg", Wg)
        W1 = Tensor((E, d, h), device=x.device, dtype=x.dtype)
        W1.gaussian(0.0, (2.0 / d) ** 0.5)
        self._register_param("W1", W1)
        b1 = Tensor((E, h), device=x.device, dtype=x.dtype)
        b1.set_value(0.0)
        self._register_param("b1", b1)
        W2 = Tensor((E, h, d), device=x.device, dtype=x.dtype)
        W2.gaussian(0.0, (2.0 / h) ** 0.5)
        self._register_param("W2", W2)
        b2 = Tensor((E, d), device=x.device, dtype=x.dtype)
        b2.set_value(0.0)
        self._register_param("b2", b2)

    def forward(self, x):
        op = _MoEOp(self)
        y, aux, z, ovf = op(x, self.Wg, self.W1, self.b1, self.W2, self.b2)
        self.aux_loss = aux  # tape Tensors; see class docstring
        self.z_loss = z
        self.overflow = ovf
        return y


class _MoEOp(autograd.Operator):
    def __init__(self, layer_ref):
        super().__init__("MoE")
        self.layer_ref = layer_ref

    def forward(self, x, Wg, W1, b1, W2, b2):
        from .parallel.moe import moe_ffn, moe_ffn_ep
        from jax import lax as _lax
        lyr = self.layer_ref
        shape = x.shape
        flat = x.reshape(-1, shape[-1])
        in_mesh = False
        if lyr.ep_axis is not None:
            try:
                n = _lax.axis_size(lyr.ep_axis)  # probes mesh scope only
                in_mesh = True
            except NameError:
                in_mesh = False
        if in_mesh:
            # params are replicated; each device computes only its expert
            # slice. No grad pre-scaling: under the required
            # DistOpt(axis=(data, ep)) tuple reduction, slice-e cotangents
            # exist on exactly the `data`-group devices (each covering a
            # disjoint token set via the all_to_all transpose), so the
            # psum/world_size mean already equals the serial token-mean
            # gradient (verified by test_moe_gpt_model_api).
            my = _lax.axis_index(lyr.ep_axis)
            el = W1.shape[0] // n
            sl = lambda a: _lax.dynamic_slice_in_dim(a, my * el, el, 0)
            y, aux, (z, ovf) = moe_ffn_ep(
                flat, Wg, sl(W1), sl(b1), sl(W2), sl(b2),
                lyr.ep_axis, lyr.capacity_factor, k=lyr.k)
        else:
            y, aux, (z, ovf) = moe_ffn(flat, Wg, W1, b1, W2, b2,
                                       lyr.capacity_factor, k=lyr.k)
        return y.reshape(shape), aux, z, ovf


# ---- recurrent (ref layer.py:1115-1347 + CudnnRNN:1550) ------------------


class RNN_Base(Layer):
    pass


class RNN(RNN_Base):
    """Vanilla elman RNN composed from autograd ops, time loop in Python
    (ref layer.py:1129). For long sequences prefer CudnnRNN (lax.scan)."""

    def __init__(self, hidden_size, activation="tanh", name=None):
        super().__init__(name)
        self.hidden_size = hidden_size
        self.activation = activation

    def initialize(self, x, hx=None):
        # x: (seq, batch, feature)
        in_size = x.shape[2]
        Wx = Tensor((in_size, self.hidden_size), device=x.device, dtype=x.dtype)
        initializer.glorot_uniform(Wx)
        self._register_param("Wx", Wx)
        Wh = Tensor((self.hidden_size, self.hidden_size), device=x.device,
                    dtype=x.dtype)
        initializer.orthogonal(Wh)
        self._register_param("Wh", Wh)
        b = Tensor((self.hidden_size,), device=x.device, dtype=x.dtype)
        b.set_value(0.0)
        self._register_param("b", b)

    def step(self, xt, h):
        z = autograd.add(autograd.matmul(xt, self.Wx),
                         autograd.matmul(h, self.Wh))
        z = autograd.add_bias(z, self.b, axis=0)
        return autograd.tanh(z) if self.activation == "tanh" \
            else autograd.relu(z)

    def forward(self, x, hx=None):
        seq = x.shape[0]
        if hx is None:
            hx = Tensor((x.shape[1], self.hidden_size), device=x.device,
                        dtype=x.dtype)
        ys = []
        h = hx
        for t in range(seq):
            h = self.step(x[t], h)
            ys.append(h)
        return ys, h


class LSTM(RNN_Base):
    """Autograd-composed LSTM (ref layer.py:1229), fused-gates formulation."""

    def __init__(self, hidden_size, name=None):
        super().__init__(name)
        self.hidden_size = hidden_size

    def initialize(self, x, hx_cx=None):
        in_size = x.shape[2]
        H = self.hidden_size
        Wx = Tensor((in_size, 4 * H), device=x.device, dtype=x.dtype)
        initializer.glorot_uniform(Wx)
        self._register_param("Wx", Wx)
        Wh = Tensor((H, 4 * H), device=x.device, dtype=x.dtype)
        initializer.glorot_uniform(Wh)
        self._register_param("Wh", Wh)
        b = Tensor((4 * H,), device=x.device, dtype=x.dtype)
        b.set_value(0.0)
        self._register_param("b", b)

    def step(self, xt, h, c):
        H = self.hidden_size
        z = autograd.add(autograd.matmul(xt, self.Wx),
                         autograd.matmul(h, self.Wh))
        z = autograd.add_bias(z, self.b, axis=0)
        zi = autograd.slice(z, [0], [H], axes=[1])
        zf = autograd.slice(z, [H], [2 * H], axes=[1])
        zg = autograd.slice(z, [2 * H], [3 * H], axes=[1])
        zo = autograd.slice(z, [3 * H], [4 * H], axes=[1])
        i = autograd.sigmoid(zi)
        f = autograd.sigmoid(zf)
        g = autograd.tanh(zg)
        o = autograd.sigmoid(zo)
        c_new = autograd.add(autograd.mul(f, c), autograd.mul(i, g))
        h_new = autograd.mul(o, autograd.tanh(c_new))
        return h_new, c_new

    def forward(self, x, hx_cx=None):
        seq, batch = x.shape[0], x.shape[1]
        if hx_cx is None:
            h = Tensor((batch, self.hidden_size), device=x.device, dtype=x.dtype)
            c = Tensor((batch, self.hidden_size), device=x.device, dtype=x.dtype)
        else:
            h, c = hx_cx
        ys = []
        for t in range(seq):
            h, c = self.step(x[t], h, c)
            ys.append(h)
        return ys, (h, c)


class CudnnRNN(Layer):
    """Fused multi-step LSTM: one autograd op whose forward is a lax.scan —
    the TPU-native replacement for CudnnRNNHandle (rnn.h:38). Name kept for
    API parity; `FusedRNN` is the honest alias."""

    def __init__(self, hidden_size, batch_first=False, name=None,
                 return_sequences=True, bidirectional=False):
        super().__init__(name)
        self.hidden_size = hidden_size
        self.batch_first = batch_first
        self.return_sequences = return_sequences
        self.bidirectional = bidirectional

    def initialize(self, x, hx=None, cx=None, **kwargs):
        from .ops.rnn import init_lstm_params
        in_size = x.shape[2]  # feature axis is 2 in both layouts
        Wx, Wh, b = init_lstm_params(in_size, self.hidden_size, x.device,
                                     x.dtype)
        self._register_param("Wx", Wx)
        self._register_param("Wh", Wh)
        self._register_param("b", b)
        if self.bidirectional:
            Wx2, Wh2, b2 = init_lstm_params(in_size, self.hidden_size,
                                            x.device, x.dtype)
            self._register_param("Wx_r", Wx2)
            self._register_param("Wh_r", Wh2)
            self._register_param("b_r", b2)

    def forward(self, x, hx=None, cx=None, seq_lengths=None):
        """seq_lengths (batch,) int32 enables the variable-length path
        (parity with GpuRNNForwardTrainingEx, rnn.h:117-131): hy/cy are
        each sample's state at its true last step, padded ys are zero."""
        from .ops.rnn import lstm_scan, lstm_scan_ex
        if self.batch_first:
            x = autograd.transpose(x, (1, 0, 2))
        batch = x.shape[1]
        dev = x.device
        if hx is None:
            hx = Tensor((batch, self.hidden_size), device=dev, dtype=x.dtype)
        if cx is None:
            cx = Tensor((batch, self.hidden_size), device=dev, dtype=x.dtype)
        if seq_lengths is not None and not isinstance(seq_lengths, Tensor):
            seq_lengths = tensor_module.from_numpy(
                np.asarray(seq_lengths, np.int32), dev)

        def run(xs, Wx, Wh, b):
            if seq_lengths is not None:
                return lstm_scan_ex(xs, seq_lengths, hx, cx, Wx, Wh, b)
            return lstm_scan(xs, hx, cx, Wx, Wh, b)

        ys, hy, cy = run(x, self.Wx, self.Wh, self.b)
        if self.bidirectional:
            from .ops.rnn import reverse_padded
            if seq_lengths is not None:
                xr = reverse_padded(x, seq_lengths)
            else:
                xr = autograd.flip(x, axis=0)
            ys_r, hy_r, cy_r = run(xr, self.Wx_r, self.Wh_r, self.b_r)
            if seq_lengths is not None:
                ys_r = reverse_padded(ys_r, seq_lengths)
            else:
                ys_r = autograd.flip(ys_r, axis=0)
            ys = autograd.cat((ys, ys_r), axis=2)
            hy = autograd.cat((hy, hy_r), axis=1)
            cy = autograd.cat((cy, cy_r), axis=1)
        if self.batch_first:
            ys = autograd.transpose(ys, (1, 0, 2))
        if self.return_sequences:
            return ys, hy, cy
        return hy, hy, cy


FusedRNN = CudnnRNN
