"""A sparse language model whose layers mix tokens by a gated short
convolution three times in four and by attention in the fourth (the LFM2
family, `model_type: "lfm2_moe"`), with leading dense layers and experts
routed by a sigmoid under a selection bias that the training step moves.

    layer:  h = x + Op(N1(x));  y = h + FFN(N2(h))        RMS norms
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
    after the optimizer's step, each sparse layer:
            b_e += bias_update_rate x sign(mean(load) - load_e), load_e =
            the (token, choice) pairs the step sent to expert e, over ALL
            experts (this device's own tokens: a deployment sums the
            count over its replicas, which nothing here does)

On models/lm.py's skeleton; `experts_held` / `expert_offset` as in
models/mellum.py. The bias is a state (`TransformerBlock_<i>.moe.b` in
`get_states()`, not `get_params()`): zero on a fresh model, saved with it.
"""

from __future__ import annotations

import jax
import numpy as np

from .. import layer, observe
from . import lm

CONV, FULL = "conv", "full_attention"
GATE_EPS = 1e-6     # the family's modelling code; no config key


class LFM2(lm.SparseLM):
    """`train_one_batch(ids, targets)` -> (loss, the sampled logits, rows
    routed (L, held), load (L, experts)): `lm.SparseLM`'s."""

    loads = True

    def __init__(self, vocab_size=256, dim=256, num_heads=8, num_kv_heads=2,
                 head_dim=None, layer_types=(CONV, FULL), conv_taps=3,
                 num_dense_layers=1, dense_ffn_dim=512, ffn_dim=128,
                 num_experts=8, experts_per_token=2, experts_held=None,
                 expert_offset=0, routed_scaling_factor=1.0,
                 use_expert_bias=True, bias_update_rate=1e-3, rope_theta=1e6,
                 norm_eps=1e-5, sample=128, recompute=False, name=None):
        assert all(t in (CONV, FULL) for t in layer_types), layer_types
        attn = layer.Attention(num_heads, num_kv_heads=num_kv_heads,
                               head_dim=head_dim, rope=True,
                               rope_theta=rope_theta, qk_norm="head")
        dense = layer.FeedForward("swiglu", dense_ffn_dim, bias=False)
        experts = layer.Experts(
            num_experts, experts_per_token, dim=ffn_dim, dropless=True,
            held=experts_held, offset=expert_offset,
            router=dict(score="sigmoid", bias=use_expert_bias,
                        scale=routed_scaling_factor, gate_eps=GATE_EPS))
        blocks = [layer.TransformerBlock(
            layer.Convolution(conv_taps) if kind == CONV else attn,
            dense if i < num_dense_layers else experts,
            layer.Norms("rms", norm_eps))
            for i, kind in enumerate(layer_types)]
        super().__init__(vocab_size, dim, blocks, norm_eps, sample, recompute,
                         name)
        self.layer_types, self.conv_taps = tuple(layer_types), conv_taps
        self.num_dense = int(num_dense_layers)
        self.use_bias, self.bias_rate = use_expert_bias, bias_update_rate

    def sparse_layers(self):
        """The indices of the layers that hold experts."""
        return range(self.num_dense, len(self.blocks))

    def plan(self, shape):
        self.moe_plan(int(np.prod(shape)), dense_layers=self.num_dense,
                      sigmoid=1, bias=int(self.use_bias))
        observe.record_plan(
            "singa_conv_plan", layers=self.layer_types.count(CONV),
            channels=self.dim, taps=self.conv_taps,
            recomputed_blocks=self.layer_types[:self.rebuilt()].count(CONV))

    def outputs(self, counts):
        if self.use_bias:
            # by the load of the step's own forward (a rebuilt block's
            # second forward counted the same pairs)
            with jax.named_scope("router_bias"):
                for i in self.sparse_layers():
                    self.blocks[i].moe.update_bias(self.bias_rate,
                                                   counts[i][1])
        return super().outputs(counts)

    def router_bias(self):
        """(L, experts) host array: each layer's selection bias as it
        stands (a dense layer's row is zeros)."""
        out = np.zeros((len(self.blocks), self.num_experts), np.float32)
        if self.use_bias:
            for i in self.sparse_layers():
                out[i] = np.asarray(self.blocks[i].moe.b.data)
        return out


def record_rows(rows, load, bias, dense_layers=0):
    """A fetched step's third and fourth outputs and the model's
    `router_bias()`, each (L, .): models/lm.py's `record_rows` for the
    sparse layers."""
    rows, load, bias = (np.asarray(a)[dense_layers:]
                        for a in (rows, load, bias))
    lm.record_rows(rows, dense_layers, load, bias)
