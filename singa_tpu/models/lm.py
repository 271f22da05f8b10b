"""The trained language models' skeleton (Mellum, SDAR, LFM2, Olmo Hybrid;
the looped model in part): h = E[ids] (fp32 rows: the residual stream stays
fp32 under `amp`), h = Block_l(h) for l = 1..L (`layer.TransformerBlock`),
loss = CE(N_f(h) W_head, targets) (an RMS norm, an untied fp32 head).

`recompute` (True: every block; n: the first n) makes those blocks
`autograd.Region`s while training, rebuilt in the backward pass. A step
hands back the loss, the head's logits at `sample` fixed positions (off
the tape, under the scope `head`: never the (tokens, vocabulary) logits,
which a loop that runs ahead would pile up) and what experts counted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd, layer, model, observe
from ..parallel.moe import rung_of, rungs


class _SampleLogits(autograd.Operator):
    """The head's logits at fixed flat positions, off the tape, in the
    step's compute dtype with fp32 sums."""

    never_requires_grad = True

    def __init__(self, rows):
        super().__init__()
        self.rows = rows

    def forward(self, h, W):
        dt = autograd.compute_dtype or h.dtype
        hs = h.reshape(-1, h.shape[-1])[self.rows]
        return jnp.dot(hs.astype(dt), W.astype(dt),
                       preferred_element_type=jnp.float32)


class _Stack(autograd.Operator):
    """Rows of numbers, one a layer, stacked off the tape."""

    never_requires_grad = True

    def forward(self, *rows):
        return jnp.stack(rows)


class _Zeros(autograd.Operator):
    """The row a layer without experts has in the step's counts."""

    never_requires_grad = True

    def __init__(self, n):
        super().__init__()
        self.n = n

    def forward(self, like):
        return jnp.zeros((self.n,), jnp.float32)


class LM(model.Model):
    """`forward(ids)` -> logits (B, S, V); `train_one_batch(ids, targets)`
    -> (loss, the logits at `sample` fixed positions, *`outputs`)."""

    def __init__(self, vocab_size, dim, blocks, norm_eps, sample=128,
                 recompute=False, name=None):
        super().__init__(name)
        self.vocab_size, self.dim, self.sample = vocab_size, dim, sample
        self.recompute = len(blocks) if recompute is True \
            else int(recompute)
        self.tok_embed = layer.Embedding(vocab_size, dim, out_dtype="float32")
        self.blocks = blocks
        self.register_layers(*blocks)
        self.ln_f = layer.RMSNorm(norm_eps)
        self.head = layer.Linear(vocab_size, bias=False, out_dtype="float32")
        self.sce = layer.SoftMaxCrossEntropy()

    def rebuilt(self):
        """How many leading blocks a step rebuilds: none outside training."""
        return min(self.recompute, len(self.blocks)) \
            if autograd.training else 0

    @staticmethod
    def region(fn, *xs, rebuild, reads=()):
        """`fn(*xs)`; if `rebuild`, as an `autograd.Region`."""
        return autograd.region(fn, *xs, reads=reads) if rebuild \
            else fn(*xs)

    def counts(self, i, y):
        """What block `i` hands on beside its output `y`."""
        return ()

    def trunk(self, h):
        """(the last block's output, [`counts`] a block)."""
        counts, rebuilt = [], self.rebuilt()
        for i, b in enumerate(self.blocks):
            def fn(x, i=i, b=b):
                y = b(x)
                return (y, *self.counts(i, y))
            out = self.region(fn, h, rebuild=i < rebuilt,
                              reads=b.get_params().values())
            h, *c = out if isinstance(out, tuple) else (out,)
            counts.append(c)
        return h, counts

    def hidden(self, ids):
        h, counts = self.trunk(self.tok_embed(ids))
        return self.ln_f(h), counts

    def forward(self, ids):
        return self.head(self.hidden(ids)[0])

    def sample_rows(self, n):
        """`sample` of n flat positions, spread evenly."""
        return np.linspace(0, n - 1, min(self.sample, n)).astype(np.int32)

    def sampled_logits(self, h, rows):
        with jax.named_scope("head"):
            return _SampleLogits(rows)(h, self.head.W)

    def plan(self, shape):
        """Set the plan gauges of a step over ids of `shape`."""

    def outputs(self, counts):
        """A step's outputs after its update, past the loss and sample."""
        return ()

    def train_one_batch(self, ids, targets):
        h, counts = self.hidden(ids)
        sampled = self.sampled_logits(h, self.sample_rows(
            int(np.prod(ids.shape))))
        loss = self.sce(self.head(h), targets)
        self.plan(ids.shape)
        self.optimizer(loss)
        return (loss, sampled, *self.outputs(counts))


class SparseLM(LM):
    """An `LM` whose blocks hold experts, all alike (`layer.Experts`), but
    for leading blocks without any. A step hands back the rows routed to
    each held expert of each block (L, held) and, with `loads`, the pairs
    sent to each of ALL the experts (L, experts); a block without experts
    has a row of zeros."""

    loads = False

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        moe = self.blocks[-1].moe
        self.num_experts, self.k, self.held = moe.num_experts, moe.k, \
            moe.held

    def counts(self, i, y):
        moe = getattr(self.blocks[i], "moe", None)
        rows = _Zeros(self.held)(y) if moe is None else moe.rows
        if not self.loads:
            return (rows,)
        return rows, _Zeros(self.num_experts)(y) \
            if moe is None or moe.load is None else moe.load

    def outputs(self, counts):
        return tuple(_Stack()(*c) for c in zip(*counts))

    def plan(self, shape):
        self.moe_plan(int(np.prod(shape)))

    def moe_plan(self, rows, **kinds):
        """`singa_moe_plan` of a step that routes `rows` tokens through
        each expert layer."""
        worst = rows * min(self.k, self.held)
        observe.record_plan(
            "singa_moe_plan", experts=self.num_experts, held=self.held,
            k=self.k, rows_worst=worst, recomputed_blocks=self.rebuilt(),
            **kinds, rung_least=rungs(worst)[0])


def record_rows(rows, first=0, load=None, bias=None):
    """A fetched step's rows routed (L, held), from layer `first` on:
    `singa_moe_rows{layer, kind=routed|held_max|held_min|buffer}` (their
    sum, the busiest and the idlest held expert's, and the rung of the
    sorted buffer the layer's row passes worked on: `parallel.moe.rung_of`
    on the traced step's `rows_worst`); with its `load` and the model's
    selection `bias` (L, experts), `singa_moe_load{layer, kind=max|mean|
    min}` over ALL the experts and `singa_moe_bias{layer, kind=max|min}`."""
    if not observe.is_enabled():
        return
    g = observe.gauge(
        "singa_moe_rows",
        "rows (token, choice pairs) routed to the experts this device "
        "holds in the latest fetched step, by layer: their sum, the largest "
        "and the least expert's, and the buffer length (a rung of the "
        "ladder) the layer's row passes worked on")
    plan = observe.gauge("singa_moe_plan", observe.PLANS["singa_moe_plan"])
    worst = int(plan.value(kind="rows_worst"))
    for i, r in enumerate(np.asarray(rows), first):
        for kind, v in (("routed", r.sum()), ("held_max", r.max()),
                        ("held_min", r.min()),
                        ("buffer", rung_of(r.sum(), worst))):
            g.set(float(v), layer=str(i), kind=kind)
    if load is None:
        return
    gl = observe.gauge(
        "singa_moe_load",
        "(token, choice) pairs the latest fetched step sent to an expert, "
        "over ALL experts of a layer (held here or not): the busiest's, "
        "the mean and the idlest's; what the selection bias acts on")
    gb = observe.gauge(
        "singa_moe_bias",
        "the router's selection bias of a layer after the latest fetched "
        "step: its largest and least entry (zero on a fresh model)")
    for i, (ld, b) in enumerate(zip(np.asarray(load), np.asarray(bias)),
                                first):
        for kind in ("max", "mean", "min"):
            gl.set(float(getattr(ld, kind)()), layer=str(i), kind=kind)
        for kind in ("max", "min"):
            gb.set(float(getattr(b, kind)()), layer=str(i), kind=kind)
