"""A sparse language model with two kinds of layer in one stack (the
Mellum2 family): every layer's feed-forward is a mixture of many small
SiLU-gated experts routed top-k with nothing dropped, and attention
alternates sliding-window layers with full ones.

    layer:  h = x + Attn_kind(N1(x));  y = h + MoE(N2(h))        (RMS norms)
    Attn:   grouped-query attention, heads of `head_dim` (not dim / heads),
            rotary on q and k; kind "sliding_attention": plain rotary, a
            query sees its last `window` keys; "full_attention": YaRN's
            rotary tables (`rope_scaling`), every key at or before it
    MoE:    softmax over all experts in fp32, top-k, gates renormalised;
            sum over the chosen experts THIS DEVICE HOLDS of
            gate x (silu(n Wg_e) * (n Wu_e)) Wd_e

On models/lm.py's skeleton. `experts_held` / `expert_offset` name this
device's share of the experts (the router still scores all of them; the
layer hands on its partial sum): one chip of an expert-parallel group runs
exactly this program, less the exchange.
"""

from __future__ import annotations

from .. import layer
from . import lm
from .lm import record_rows  # noqa: F401  (the benchmark's drivers call it)

SLIDING, FULL = "sliding_attention", "full_attention"


class Mellum(lm.SparseLM):
    """`train_one_batch(ids, targets)` -> (loss, the sampled logits, rows
    routed (L, held)): `lm.SparseLM`'s."""

    def __init__(self, vocab_size=256, dim=256, num_heads=8, num_kv_heads=2,
                 head_dim=64, layer_types=(SLIDING, FULL), window=128,
                 ffn_dim=128, num_experts=8, experts_per_token=2,
                 experts_held=None, expert_offset=0, rope_theta=5e5,
                 rope_scaling=None, norm_eps=1e-6, sample=128,
                 recompute=False, name=None):
        assert all(t in (SLIDING, FULL) for t in layer_types), layer_types
        experts = layer.Experts(num_experts, experts_per_token, dim=ffn_dim,
                                dropless=True, held=experts_held,
                                offset=expert_offset)
        blocks = [layer.TransformerBlock(layer.Attention(
            num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
            window=window if kind == SLIDING else None, rope=True,
            rope_theta=rope_theta,
            rope_scaling=rope_scaling if kind == FULL else None),
            experts, layer.Norms("rms", norm_eps)) for kind in layer_types]
        super().__init__(vocab_size, dim, blocks, norm_eps, sample, recompute,
                         name)
