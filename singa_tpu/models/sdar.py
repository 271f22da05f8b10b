"""A sparse language model trained by diffusion over blocks (the SDAR
family): every layer's feed-forward is a mixture of many small SiLU-gated
experts routed top-k with nothing dropped, attention is grouped-query with
RMS norms on q and k, and a training step feeds the sequence TWICE, noised
and clean, under a mask that is not causal.

    layer:  h = x + Attn(N1(x));  y = h + MoE(N2(h))      RMS norms, the
            residual stream fp32 under `amp`
    Attn:   q = Nq(x Wq), k = Nk(x Wk) per head over `head_dim` (learned
            gain), then rotary (rotate-half, `rope_theta`, no scaling) at
            position p(i); v = x Wv; `num_heads` query heads on
            `num_kv_heads`; softmax(q k^T / sqrt(head_dim) + M) v; Wo; no
            bias anywhere
    MoE:    softmax over all experts in fp32, top-k, the k gates
            renormalised; sum over the chosen experts THIS DEVICE HOLDS of
            gate x (silu(n Wg_e) * (n Wu_e)) Wd_e; no shared expert, no
            router bias; nothing dropped
    train:  x0 (B, S) ids; blocks of b = `block_length` positions; for each
            block k a rate t_k; masked_i ~ Bernoulli(t_k(i));
            xt = where(masked, MASK, x0)
            input = [xt ; x0] of length 2S, p(i) = i mod S  (noised half
            first, clean half second)
            M, with blk(i) = (i mod S) // b:
              noised query i sees  noised key j  iff blk(j) == blk(i)
                                   clean  key j  iff blk(j) <  blk(i)
              clean  query i sees  clean  key j  iff blk(j) <= blk(i)
                                   and no noised key
            logits = head(Nf(y_L))[noised half]  (B, S, V)
            loss = sum_i masked_i / t_k(i) * CE(logits_i, x0_i) / (B * S)
            (no shift: position i predicts its own token)

`data.block_diffusion_noise` draws `masked` and the weight `masked / t`; the
step makes `xt` and the doubled input on the device (`noise`). MASK is the
last row of the vocabulary held (`vocab_size - 1`): data never draws it.
The layers are `layer.TransformerBlock` by arguments (`block_diffusion`,
`qk_norm`, `head_dim`, `moe_dropless`); the flash kernels schedule the
mask (ops/attention.py: no grid step outside it). `experts_held` /
`expert_offset` and `recompute` as in models/mellum.py; `sample`: the
step hands back the logits at that many positions of the noised half
(`sample_positions`).
"""

from __future__ import annotations

import jax
import numpy as np

from .. import autograd, layer, model, observe
from .mellum import _SampleLogits, _Stack, _moe_plan, record_rows  # noqa: F401


class _Noise(autograd.Operator):
    """[where(masked, MASK, ids) ; ids] along the sequence, off the tape,
    under the scope `noise`."""

    never_requires_grad = True

    def __init__(self, mask_id):
        super().__init__("noise")
        self.mask_id = mask_id

    def forward(self, ids, masked):
        import jax.numpy as jnp
        ids = ids.astype(jnp.int32)
        xt = jnp.where(masked != 0, jnp.int32(self.mask_id), ids)
        return jnp.concatenate([xt, ids], axis=1)


def sample_positions(batch, seq, sample):
    """The flat positions of the noised half (batch x seq) whose logits a
    step hands back: half of them the first positions of the first
    sequence, half spread evenly over the rest. A query of the first
    blocks sees the fewest keys, so what it must NOT see (its own block's
    clean keys: the answer) shows most there; the even half covers the
    long contexts."""
    n = batch * seq
    first = min(sample // 2, n)
    rest = np.linspace(first, n - 1, min(sample - first, n - first))
    return np.concatenate([np.arange(first), rest]).astype(np.int32)


class SDAR(model.Model):
    """`forward(doubled)` -> the noised half's logits (B, S, V) of a
    doubled input (B, 2S); `train_one_batch(ids, masked, weight)` -> (loss,
    the logits at `sample` fixed positions of the noised half, the rows
    routed to each held expert of each layer (L, held))."""

    def __init__(self, vocab_size, dim=256, num_heads=8, num_kv_heads=2,
                 head_dim=64, num_layers=2, ffn_dim=128, num_experts=8,
                 experts_per_token=2, experts_held=None, expert_offset=0,
                 rope_theta=1e6, norm_eps=1e-6, block_length=4, sample=128,
                 recompute=False, name=None):
        super().__init__(name)
        self.vocab_size, self.dim, self.sample = vocab_size, dim, sample
        self.mask_id, self.block_length = vocab_size - 1, int(block_length)
        self.recompute = num_layers if recompute is True else int(recompute)
        self.num_experts, self.k = num_experts, experts_per_token
        # the residual stream stays fp32 under `amp`
        self.tok_embed = layer.Embedding(vocab_size, dim, out_dtype="float32")
        self.blocks = [layer.TransformerBlock(
            num_heads, causal=False, block_diffusion=self.block_length,
            qk_norm=True, num_kv_heads=num_kv_heads, head_dim=head_dim,
            rope=True, rope_theta=rope_theta, norm="rms", norm_eps=norm_eps,
            ffn_dim=ffn_dim, moe_experts=num_experts,
            moe_k=experts_per_token, moe_dropless=True,
            moe_held=experts_held, moe_offset=expert_offset)
            for _ in range(num_layers)]
        self.register_layers(*self.blocks)
        self.ln_f = layer.RMSNorm(norm_eps)
        self.head = layer.Linear(vocab_size, bias=False, out_dtype="float32")
        self.sce = layer.SoftMaxCrossEntropy()

    def _trunk(self, doubled):
        """(the final norm's output on the noised half (B, S, d), [rows
        routed a held expert] a layer) of a doubled input (B, 2S)."""
        S = doubled.shape[1] // 2
        assert doubled.shape[1] == 2 * S and S % self.block_length == 0, \
            (doubled.shape, self.block_length)
        h, rows = self.tok_embed(doubled), []
        for i, b in enumerate(self.blocks):
            fn = lambda x, b=b: (b(x), b.moe.rows)
            if autograd.training and i < self.recompute:
                h, r = autograd.region(fn, h, reads=b.get_params().values())
            else:
                h, r = fn(h)
            rows.append(r)
        return self.ln_f(autograd.slice(h, [0], [S], [1])), rows

    def forward(self, doubled):
        return self.head(self._trunk(doubled)[0])

    def train_one_batch(self, ids, masked, weight):
        B, S = ids.shape
        n = B * S
        at = sample_positions(B, S, self.sample)
        h, rows = self._trunk(_Noise(self.mask_id)(ids, masked))
        with jax.named_scope("head"):
            sampled = _SampleLogits(at)(h, self.head.W)
        loss = self.sce(self.head(h), ids, weight)
        held, b = self.blocks[0].moe.held, self.block_length
        recomputed = min(self.recompute, len(self.blocks)) \
            if autograd.training else 0
        _moe_plan(experts=self.num_experts, held=held, k=self.k,
                  rows_worst=2 * n * min(self.k, held),
                  recomputed_blocks=recomputed)
        _blockdiff_plan(block=b, rows=2 * n, loss_rows=n,
                        pairs_inside=B * (S * S + S * b),
                        pairs_square=B * 4 * S * S,
                        recomputed_blocks=recomputed)
        self.optimizer(loss)
        return loss, sampled, _Stack()(*rows)


def _blockdiff_plan(**kinds):
    """What the latest traced block-diffusion step feeds its layers,
    readable with no chip: `singa_blockdiff_plan{kind}`."""
    g = observe.gauge(
        "singa_blockdiff_plan",
        "the latest traced block-diffusion training step, by kind: block "
        "(positions a block), rows (of the doubled input, through every "
        "layer), loss_rows (the noised half: the head's and the loss's), "
        "pairs_inside (query, key pairs a head inside the mask: S^2 + S b "
        "a sequence), pairs_square (the doubled sequence's square), "
        "recomputed_blocks (rebuilt in the backward pass)")
    for kind, v in kinds.items():
        g.set(v, kind=kind)


def create_model(vocab_size=256, **kwargs):
    return SDAR(vocab_size, **kwargs)
