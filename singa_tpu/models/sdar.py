"""A sparse language model trained by diffusion over blocks (the SDAR
family): every layer's feed-forward is a mixture of many small SiLU-gated
experts routed top-k with nothing dropped, attention is grouped-query with
RMS norms on q and k, and a training step feeds the sequence TWICE, noised
and clean, under a mask that is not causal.

    layer:  h = x + Attn(N1(x));  y = h + MoE(N2(h))      RMS norms
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
On models/lm.py's skeleton; the flash kernels schedule the mask
(ops/attention.py: no grid step outside it). `experts_held` /
`expert_offset` as in models/mellum.py.
"""

from __future__ import annotations

import numpy as np

from .. import autograd, layer, observe
from . import lm
from .lm import record_rows  # noqa: F401  (the benchmark's drivers call it)


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


class SDAR(lm.SparseLM):
    """`forward(doubled)` -> the noised half's logits (B, S, V) of a
    doubled input (B, 2S); `train_one_batch(ids, masked, weight)` -> (loss,
    the logits at `sample` fixed positions of the noised half, the rows
    routed to each held expert of each layer (L, held))."""

    def __init__(self, vocab_size=256, dim=256, num_heads=8, num_kv_heads=2,
                 head_dim=64, num_layers=2, ffn_dim=128, num_experts=8,
                 experts_per_token=2, experts_held=None, expert_offset=0,
                 rope_theta=1e6, norm_eps=1e-6, block_length=4, sample=128,
                 recompute=False, name=None):
        attn = layer.Attention(
            num_heads, causal=False, num_kv_heads=num_kv_heads,
            head_dim=head_dim, block_diffusion=int(block_length), rope=True,
            rope_theta=rope_theta, qk_norm="head")
        experts = layer.Experts(num_experts, experts_per_token, dim=ffn_dim,
                                dropless=True, held=experts_held,
                                offset=expert_offset)
        blocks = [layer.TransformerBlock(attn, experts,
                                         layer.Norms("rms", norm_eps))
                  for _ in range(num_layers)]
        super().__init__(vocab_size, dim, blocks, norm_eps, sample, recompute,
                         name)
        self.mask_id, self.block_length = vocab_size - 1, int(block_length)

    def hidden(self, doubled):
        """Of a doubled input (B, 2S): the noised half, sliced, normed."""
        S = doubled.shape[1] // 2
        assert doubled.shape[1] == 2 * S and S % self.block_length == 0, \
            (doubled.shape, self.block_length)
        h, counts = self.trunk(self.tok_embed(doubled))
        return self.ln_f(autograd.slice(h, [0], [S], [1])), counts

    def train_one_batch(self, ids, masked, weight):
        B, S = ids.shape
        n, b = B * S, self.block_length
        h, counts = self.hidden(_Noise(self.mask_id)(ids, masked))
        sampled = self.sampled_logits(h, sample_positions(B, S, self.sample))
        loss = self.sce(self.head(h), ids, weight)
        self.moe_plan(2 * n)
        observe.record_plan(
            "singa_blockdiff_plan", block=b, rows=2 * n, loss_rows=n,
            pairs_inside=B * (S * S + S * b), pairs_square=B * 4 * S * S,
            recomputed_blocks=self.rebuilt())
        self.optimizer(loss)
        return (loss, sampled, *self.outputs(counts))
