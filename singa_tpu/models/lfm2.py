"""A sparse language model whose layers mix tokens by a gated short
convolution three times in four and by attention in the fourth (the LFM2
family, `model_type: "lfm2_moe"`), with leading dense layers and experts
routed by a sigmoid under a selection bias that the training step moves.

    layer:  h = x + Op(N1(x));  y = h + FFN(N2(h))        RMS norms, the
            residual stream fp32 under `amp`
    Op:     by `layer_types`: "conv": `layer.ShortConv` ((B, C, u) =
            split3(n W_in); z = B * u; `conv_taps` causal depthwise taps
            over z; (C * c) W_out); "full_attention": grouped-query
            attention, RMS norms on q and k a head, rotary, causal
    FFN:    the first `num_dense_layers` layers: (silu(n W1) * (n W3)) W2
            of width `dense_ffn_dim`; the others: sigmoid of the router's
            logits over ALL experts in fp32, the top `experts_per_token`
            of score + bias, gates = the chosen scores / (their sum +
            1e-6) x `routed_scaling_factor` (the bias is not in them); sum
            over the chosen experts THIS DEVICE HOLDS of
            gate x (silu(n Wg_e) * (n Wu_e)) Wd_e, width `ffn_dim`
    loss:   mean cross-entropy of the untied head's logits over the
            vocabulary held
    after the optimizer's step, each sparse layer:
            b_e += bias_update_rate x sign(mean(load) - load_e), load_e =
            the (token, choice) pairs the step sent to expert e, over ALL
            experts (this device's own tokens: a deployment sums the
            count over its replicas, which nothing here does)

The layers are `layer.TransformerBlock` by arguments (`mixer`, `ffn`,
`moe_dropless`, `moe_router`). `experts_held` / `expert_offset` and
`recompute` as in models/mellum.py. The bias is a state of the expert
layer (`TransformerBlock_<i>.moe.b` in `get_states()`, not in
`get_params()`): zero on a fresh model, saved and restored with it.
"""

from __future__ import annotations

import jax
import numpy as np

from .. import autograd, layer, model, observe
from .mellum import _SampleLogits, _Stack, _moe_plan
from .mellum import record_rows as _record_rows

CONV, FULL = "conv", "full_attention"
GATE_EPS = 1e-6     # the family's modelling code; no config key


class _Zeros(autograd.Operator):
    """The row a layer without experts has in the step's counts."""

    never_requires_grad = True

    def __init__(self, n):
        super().__init__()
        self.n = n

    def forward(self, like):
        import jax.numpy as jnp
        return jnp.zeros((self.n,), jnp.float32)


class LFM2(model.Model):
    """`forward(ids)` -> logits (B, S, V); `train_one_batch(ids, targets)`
    -> (loss, the logits at `sample` fixed positions, rows (L, held): the
    rows routed to each held expert of each layer, load (L, experts): the
    pairs sent to each of ALL experts; a dense layer's row is zeros)."""

    def __init__(self, vocab_size, dim=256, num_heads=8, num_kv_heads=2,
                 head_dim=None, layer_types=(CONV, FULL), conv_taps=3,
                 num_dense_layers=1, dense_ffn_dim=512, ffn_dim=128,
                 num_experts=8, experts_per_token=2, experts_held=None,
                 expert_offset=0, routed_scaling_factor=1.0,
                 use_expert_bias=True, bias_update_rate=1e-3, rope_theta=1e6,
                 norm_eps=1e-5, sample=128, recompute=False, name=None):
        super().__init__(name)
        assert all(t in (CONV, FULL) for t in layer_types), layer_types
        self.vocab_size, self.dim, self.sample = vocab_size, dim, sample
        self.layer_types, self.conv_taps = tuple(layer_types), conv_taps
        self.num_dense = int(num_dense_layers)
        self.recompute = len(layer_types) if recompute is True \
            else int(recompute)
        self.num_experts, self.k = num_experts, experts_per_token
        self.held = num_experts if experts_held is None else experts_held
        self.use_bias, self.bias_rate = use_expert_bias, bias_update_rate
        # the residual stream stays fp32 under `amp`
        self.tok_embed = layer.Embedding(vocab_size, dim, out_dtype="float32")
        router = dict(score="sigmoid", bias=use_expert_bias,
                      scale=routed_scaling_factor, gate_eps=GATE_EPS)
        self.blocks = [layer.TransformerBlock(
            num_heads, mixer="conv" if kind == CONV else "attention",
            conv_taps=conv_taps, causal=True, num_kv_heads=num_kv_heads,
            head_dim=head_dim, rope=True, rope_theta=rope_theta,
            qk_norm=True, norm="rms", norm_eps=norm_eps, ffn="swiglu",
            ffn_bias=False,
            **(dict(ffn_dim=dense_ffn_dim) if i < self.num_dense else dict(
                ffn_dim=ffn_dim, moe_experts=num_experts,
                moe_k=experts_per_token, moe_dropless=True,
                moe_held=experts_held, moe_offset=expert_offset,
                moe_router=router)))
            for i, kind in enumerate(self.layer_types)]
        self.register_layers(*self.blocks)
        self.ln_f = layer.RMSNorm(norm_eps)
        self.head = layer.Linear(vocab_size, bias=False, out_dtype="float32")
        self.sce = layer.SoftMaxCrossEntropy()

    def sparse_layers(self):
        """The indices of the layers that hold experts."""
        return range(self.num_dense, len(self.blocks))

    def _counts(self, b, h):
        """(rows (held,), load (experts,)) of block `b` after its forward."""
        if not b.moe_experts:
            return _Zeros(self.held)(h), _Zeros(self.num_experts)(h)
        return b.moe.rows, b.moe.load if self.use_bias \
            else _Zeros(self.num_experts)(h)

    def _trunk(self, ids):
        """(the final norm's output, [rows a layer], [load a layer])."""
        h, rows, load = self.tok_embed(ids), [], []
        for i, b in enumerate(self.blocks):
            def fn(x, b=b):
                y = b(x)
                return (y, *self._counts(b, y))
            if autograd.training and i < self.recompute:
                h, r, ld = autograd.region(fn, h,
                                           reads=b.get_params().values())
            else:
                h, r, ld = fn(h)
            rows.append(r)
            load.append(ld)
        return self.ln_f(h), rows, load

    def forward(self, ids):
        return self.head(self._trunk(ids)[0])

    def train_one_batch(self, ids, targets):
        n = int(np.prod(ids.shape))
        at = np.linspace(0, n - 1, min(self.sample, n)).astype(np.int32)
        h, rows, load = self._trunk(ids)
        with jax.named_scope("head"):
            sampled = _SampleLogits(at)(h, self.head.W)
        loss = self.sce(self.head(h), targets)
        recomputed = min(self.recompute, len(self.blocks)) \
            if autograd.training else 0
        _moe_plan(experts=self.num_experts, held=self.held, k=self.k,
                  rows_worst=n * min(self.k, self.held),
                  recomputed_blocks=recomputed, dense_layers=self.num_dense,
                  sigmoid=1, bias=int(self.use_bias))
        _conv_plan(layers=self.layer_types.count(CONV), channels=self.dim,
                   taps=self.conv_taps,
                   recomputed_blocks=self.layer_types[:recomputed].count(CONV))
        self.optimizer(loss)
        if self.use_bias:
            # by the load of the step's own forward (a rebuilt block's
            # second forward counted the same pairs)
            with jax.named_scope("router_bias"):
                for i in self.sparse_layers():
                    self.blocks[i].moe.update_bias(self.bias_rate, load[i])
        return loss, sampled, _Stack()(*rows), _Stack()(*load)

    def router_bias(self):
        """(L, experts) host array: each layer's selection bias as it
        stands (a dense layer's row is zeros)."""
        out = np.zeros((len(self.blocks), self.num_experts), np.float32)
        if self.use_bias:
            for i in self.sparse_layers():
                out[i] = np.asarray(self.blocks[i].moe.b.data)
        return out


def _conv_plan(**kinds):
    """What the latest traced step's short convolutions work on, readable
    with no chip: `singa_conv_plan{kind}`."""
    g = observe.gauge(
        "singa_conv_plan",
        "the latest traced step's gated short convolutions, by kind: "
        "layers that run one, channels (the stream's width: each has its "
        "own filter), taps a filter, and how many of those layers sit in "
        "blocks rebuilt in the backward pass")
    for kind, v in kinds.items():
        g.set(v, kind=kind)


def record_rows(rows, load, bias, dense_layers=0):
    """A fetched step's third and fourth outputs and the model's
    `router_bias()`, each (L, .). Sets `singa_moe_rows` for the sparse
    layers (models/mellum.py `record_rows`), `singa_moe_load{layer,
    kind=max|mean|min}`: the pairs sent to the busiest, the average and
    the idlest of ALL the experts, and `singa_moe_bias{layer,
    kind=max|min}`: the ends of the selection bias."""
    rows, load, bias = (np.asarray(a)[dense_layers:]
                        for a in (rows, load, bias))
    _record_rows(rows, first=dense_layers)
    gl = observe.gauge(
        "singa_moe_load",
        "(token, choice) pairs the latest fetched step sent to an expert, "
        "over ALL experts of a layer (held here or not): the busiest's, "
        "the mean and the idlest's; what the selection bias acts on")
    gb = observe.gauge(
        "singa_moe_bias",
        "the router's selection bias of a layer after the latest fetched "
        "step: its largest and least entry (zero on a fresh model)")
    for i, (ld, b) in enumerate(zip(load, bias), dense_layers):
        for kind in ("max", "mean", "min"):
            gl.set(float(getattr(ld, kind)()), layer=str(i), kind=kind)
        for kind in ("max", "min"):
            gb.set(float(getattr(b, kind)()), layer=str(i), kind=kind)


def create_model(vocab_size=256, **kwargs):
    return LFM2(vocab_size, **kwargs)
