"""A dense language model whose layers mix tokens by gated delta-rule
linear attention three times in four and by full softmax attention in the
fourth (Olmo Hybrid, `model_type: "olmo_hybrid"`), with OLMo 2's norms
after each branch.

    layer:  h = x + N1(Op(x));  y = h + N2(FFN(h))       RMS norms, no norm
            before a branch
    Op:     by `layer_types`: "linear_attention": `layer.GatedDeltaNet`
            (ops/gated_delta.py: q, k, v through 4 causal taps and SiLU, q
            and k L2-normalised, beta = 2 sigmoid(x W_b), alpha =
            exp(-exp(A_log) softplus(x W_a + dt_bias)), the delta rule on a
            (dv x dk) state a head, the output RMS-normalised a head and
            gated by SiLU(x W_g)); "full_attention": multi-head attention
            with RMS norms over the whole q and k projections, no rotary
            (the config's `rope_theta` is null), causal
    FFN:    (silu(n W_gate) * (n W_up)) W_down

On models/lm.py's skeleton: blocks of `layer.DeltaRule` or
`layer.Attention`, and `layer.Norms(pre=False, post=True)`.
"""

from __future__ import annotations

import math

from .. import layer, observe
from . import lm

LINEAR, FULL = "linear_attention", "full_attention"


class OlmoHybrid(lm.LM):
    """`forward(ids)` -> logits (B, S, V); `train_one_batch(ids, targets)`
    -> (loss, the logits at `sample` fixed positions)."""

    def __init__(self, vocab_size=256, dim=256, num_heads=8, head_dim=None,
                 layer_types=(LINEAR, LINEAR, LINEAR, FULL), ffn_dim=512,
                 linear_heads=8, linear_key_dim=32, linear_value_dim=64,
                 linear_conv_taps=4, linear_neg_eigval=True, chunk=64,
                 norm_eps=1e-6, sample=128, recompute=False, name=None):
        assert all(t in (LINEAR, FULL) for t in layer_types), layer_types
        linear = layer.DeltaRule(
            linear_heads, linear_key_dim, linear_value_dim,
            conv_taps=linear_conv_taps, neg_eigval=linear_neg_eigval,
            chunk=chunk, norm_eps=norm_eps)
        attn = layer.Attention(num_heads, head_dim=head_dim,
                               qk_norm="projection")
        blocks = [layer.TransformerBlock(
            linear if kind == LINEAR else attn,
            layer.FeedForward("swiglu", ffn_dim, bias=False),
            layer.Norms("rms", norm_eps, pre=False, post=True))
            for kind in layer_types]
        super().__init__(vocab_size, dim, blocks, norm_eps, sample, recompute,
                         name)
        self.layer_types, self.linear = tuple(layer_types), linear

    def plan(self, shape):
        d, rebuilt = self.linear, self.rebuilt()
        observe.record_plan(
            "singa_linear_attn_plan", layers=self.layer_types.count(LINEAR),
            heads=d.heads, key_dim=d.key_dim, value_dim=d.value_dim,
            chunk=d.chunk, chunks=math.ceil(shape[-1] / d.chunk),
            recomputed_blocks=self.layer_types[:rebuilt].count(LINEAR))
