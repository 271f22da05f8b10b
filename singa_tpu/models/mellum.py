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
    loss:   mean cross-entropy of the head's logits over the vocabulary held

The layers are `layer.TransformerBlock` by arguments (`window`, `head_dim`,
`rope_scaling`, `moe_dropless`). `experts_held` / `expert_offset` name this
device's share of the experts (the router still scores all of them; the
layer hands on its partial sum): one chip of an expert-parallel group runs
exactly this program, less the exchange. With `recompute` the first that
many blocks (True: all) are `autograd.Region`s: kept as their input and
rebuilt on the way back.
"""

from __future__ import annotations

import jax
import numpy as np

from .. import autograd, layer, model, observe
from ..parallel.moe import rung_of, rungs

SLIDING, FULL = "sliding_attention", "full_attention"


class _SampleLogits(autograd.Operator):
    """The head's logits at fixed flat positions, off the tape, in the
    step's compute dtype with fp32 sums: what the step hands back for the
    comparison with the reference (never the (tokens, vocabulary) logits,
    which a loop that runs ahead would pile up)."""

    never_requires_grad = True

    def __init__(self, rows):
        super().__init__()
        self.rows = rows

    def forward(self, h, W):
        import jax.numpy as jnp
        dt = autograd.compute_dtype or h.dtype
        hs = h.reshape(-1, h.shape[-1])[self.rows]
        return jnp.dot(hs.astype(dt), W.astype(dt),
                       preferred_element_type=jnp.float32)


class _Stack(autograd.Operator):
    """Rows of numbers, one a layer, stacked off the tape."""

    never_requires_grad = True

    def forward(self, *rows):
        import jax.numpy as jnp
        return jnp.stack(rows)


class Mellum(model.Model):
    """`forward(ids)` -> logits (B, S, V); `train_one_batch(ids, targets)`
    -> (loss, the logits at `sample` fixed positions, the rows routed to
    each held expert of each layer (L, held))."""

    def __init__(self, vocab_size, dim=256, num_heads=8, num_kv_heads=2,
                 head_dim=64, layer_types=(SLIDING, FULL), window=128,
                 ffn_dim=128, num_experts=8, experts_per_token=2,
                 experts_held=None, expert_offset=0, rope_theta=5e5,
                 rope_scaling=None, norm_eps=1e-6, sample=128,
                 recompute=False, name=None):
        super().__init__(name)
        assert all(t in (SLIDING, FULL) for t in layer_types), layer_types
        self.vocab_size, self.dim, self.sample = vocab_size, dim, sample
        self.layer_types = tuple(layer_types)
        self.recompute = len(layer_types) if recompute is True \
            else int(recompute)
        self.num_experts, self.k = num_experts, experts_per_token
        # the residual stream stays fp32 under `amp`
        self.tok_embed = layer.Embedding(vocab_size, dim, out_dtype="float32")
        self.blocks = [layer.TransformerBlock(
            num_heads, causal=True, num_kv_heads=num_kv_heads,
            head_dim=head_dim, rope=True, rope_theta=rope_theta,
            window=window if kind == SLIDING else None,
            rope_scaling=rope_scaling if kind == FULL else None,
            norm="rms", norm_eps=norm_eps, ffn_dim=ffn_dim,
            moe_experts=num_experts, moe_k=experts_per_token,
            moe_dropless=True, moe_held=experts_held,
            moe_offset=expert_offset) for kind in self.layer_types]
        self.register_layers(*self.blocks)
        self.ln_f = layer.RMSNorm(norm_eps)
        self.head = layer.Linear(vocab_size, bias=False, out_dtype="float32")
        self.sce = layer.SoftMaxCrossEntropy()

    def _trunk(self, ids):
        """(the final norm's output, [rows routed a held expert] a layer)."""
        h, rows = self.tok_embed(ids), []
        for i, b in enumerate(self.blocks):
            fn = lambda x, b=b: (b(x), b.moe.rows)
            if autograd.training and i < self.recompute:
                h, r = autograd.region(fn, h, reads=b.get_params().values())
            else:
                h, r = fn(h)
            rows.append(r)
        return self.ln_f(h), rows

    def forward(self, ids):
        return self.head(self._trunk(ids)[0])

    def train_one_batch(self, ids, targets):
        n = int(np.prod(ids.shape))
        at = np.linspace(0, n - 1, min(self.sample, n)).astype(np.int32)
        h, rows = self._trunk(ids)
        with jax.named_scope("head"):
            sampled = _SampleLogits(at)(h, self.head.W)
        loss = self.sce(self.head(h), targets)
        held = self.blocks[0].moe.held
        _moe_plan(experts=self.num_experts, held=held, k=self.k,
                  rows_worst=n * min(self.k, held),
                  recomputed_blocks=min(self.recompute, len(self.blocks))
                  if autograd.training else 0)
        self.optimizer(loss)
        return loss, sampled, _Stack()(*rows)


def _plan_gauge():
    return observe.gauge(
        "singa_moe_plan",
        "the latest traced step's expert layers, by kind: experts routed "
        "over, experts this device holds, choices a token (k), rows of the "
        "sorted buffer (tokens x min(k, held): the worst case; the grouped "
        "products follow the rows really routed, the passes over the buffer "
        "the rung that holds them), the least rung of the buffer's ladder, "
        "blocks recomputed in the backward pass; where the model says so, "
        "dense_layers (leading layers without experts), sigmoid (1: the "
        "router scores by a sigmoid, not a softmax), bias (1: a selection "
        "bias that the step moves)")


def _moe_plan(**kinds):
    """What the latest traced step of a sparse model routes, readable with
    no chip: `singa_moe_plan{kind}`."""
    g = _plan_gauge()
    for kind, v in {**kinds,
                    "rung_least": rungs(kinds["rows_worst"])[0]}.items():
        g.set(v, kind=kind)


def record_rows(rows, first=0):
    """`rows` (L, held): a step's third output, fetched (`first`: the
    index of its first layer in the model, where leading layers hold no
    experts). Sets
    `singa_moe_rows{layer, kind=routed|held_max|held_min|buffer}`: the rows
    routed to this device's experts in each layer, the largest and the
    least load among them, and the rung of the sorted buffer that the
    layer's passes over its rows worked on (`parallel.moe.rung_of`, the
    rule the step itself used, on the latest traced step's `rows_worst`)."""
    g = observe.gauge(
        "singa_moe_rows",
        "rows (token, choice pairs) routed to the experts this device "
        "holds in the latest fetched step, by layer: their sum, the largest "
        "and the least expert's, and the buffer length (a rung of the "
        "ladder) the layer's row passes worked on")
    worst = int(_plan_gauge().value(kind="rows_worst"))
    for i, r in enumerate(np.asarray(rows), first):
        for kind, v in (("routed", r.sum()), ("held_max", r.max()),
                        ("held_min", r.min()),
                        ("buffer", rung_of(r.sum(), worst))):
            g.set(float(v), layer=str(i), kind=kind)


def create_model(vocab_size=256, **kwargs):
    return Mellum(vocab_size, **kwargs)
