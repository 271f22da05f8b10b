"""A looped language model: one stack of blocks run T times on shared
weights, an exit gate and a loss at every pass (the Ouro / LoopLM family).

    block:  a = x + N2(Attn(N1(x)));  y = a + N4(MLP(N3(a)))     (RMS norms,
            rotary multi-head attention, gated SiLU feed-forward, no bias)
    loop:   h_0 = E[ids];  h_t = N_f(Stack(h_{t-1})),  t = 1..T
            z_t = h_t W_head (fp32);  lam_t = sigmoid(h_t . w_g + b_g)
    exit:   p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j), 1 < t < T;
            p_T = prod_{j<T}(1 - lam_j)
    loss:   mean over positions of [sum_t p_t CE(z_t, target) - beta H(p)]

Every N, Attn, MLP, W_head and the gate has ONE set of weights, used at
every pass: a parameter has T consumers on the tape, and its gradient is
whole only when the backward pass reaches the first pass's use of it.

The block is `layer.TransformerBlock` by arguments. With `recompute=True`
each block application and each pass's head-and-loss is an
`autograd.Region`: the step keeps the regions' inputs (T x L block inputs,
T hidden states) and rebuilds everything else on the way back; without it
they are recorded on the ordinary tape. Under `amp` the shared weights are
cast once a step (`autograd.cast_once`), not once a use, and a weight's T
gradients are summed in fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from .. import autograd, layer, model, observe
from .. import tensor as tensor_module
from ..tensor import Tensor


class _TokenCrossEntropy(autograd.Operator):
    """-log softmax(z)[target] at every position (no mean: the loop's loss
    weighs each position by its exit distribution). Forward and backward
    are the functions `SoftMaxCrossEntropy` uses."""

    def forward(self, z, t):
        lse = tensor_module.softmax_lse(z)
        self._cache = (z, t, lse)
        self._path = autograd.cross_entropy_path(z, t)
        return tensor_module.softmax_cross_entropy_fwd(z, t, lse)

    def backward(self, dy):
        z, t, lse = self._cache
        observe.record_cross_entropy(*self._path)
        return tensor_module.softmax_cross_entropy_bwd(z, t, lse) \
            * dy[..., None], None


class _Rows(autograd.Operator):
    """Fixed rows of a matrix, off the tape: what the step hands back of
    the last pass's logits."""

    never_requires_grad = True

    def __init__(self, rows):
        super().__init__()
        self.rows = rows

    def forward(self, z):
        return z[self.rows]


class _Gate(autograd.Operator):
    """sigmoid(h . w + b) as one (1, positions) row, in fp32 on the vector
    unit (a matmul would round h to the MXU's input precision)."""

    def forward(self, h, w, b):
        return jax.nn.sigmoid(
            jnp.sum(h.astype(jnp.float32) * w, axis=-1) + b).reshape(1, -1)


class _LoopLoss(autograd.Operator):
    """(lam_1..lam_T, ce_1..ce_T), each a row of positions -> (loss, mean
    ce a pass (T,), mean exit distribution (T,)); only the loss is
    differentiated."""

    def __init__(self, beta):
        super().__init__()
        self.beta = beta

    def forward(self, *rows):
        T = len(rows) // 2
        lam = jnp.concatenate([r.reshape(1, -1) for r in rows[:T]])
        ce = jnp.concatenate([r.reshape(1, -1) for r in rows[T:]])
        p = exit_distribution(lam)
        entropy = -jnp.sum(jax.scipy.special.xlogy(p, p), axis=0)
        loss = jnp.mean(jnp.sum(p * ce, axis=0) - self.beta * entropy)
        return (loss, lax.stop_gradient(jnp.mean(ce, axis=1)),
                lax.stop_gradient(jnp.mean(p, axis=1)))


def exit_distribution(lam):
    """lam (T, ...) in (0, 1) -> p (T, ...): exit at pass t with lam_t of
    what is left, the last pass takes the remainder (lam_T is not read)."""
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)
    ones = jnp.ones_like(lam[:1])
    return jnp.concatenate([lam[:-1] * jnp.concatenate([ones, stay[:-1]]),
                            stay[-1:]]) if lam.shape[0] > 1 else ones


class ExitGate(layer.Layer):
    """lam = sigmoid(h . w + b): one number a position, in fp32."""

    def initialize(self, h):
        w = Tensor((h.shape[-1],), device=h.device,
                   dtype=tensor_module.float32)
        w.gaussian(0.0, 0.02)
        self._register_param("w", w)
        b = Tensor((), device=h.device, dtype=tensor_module.float32)
        b.set_value(0.0)
        self._register_param("b", b)

    def forward(self, h):
        return _Gate()(h, self.w, self.b)


class LoopLM(model.Model):
    """`forward(ids)` -> (the T passes' logits (T, B, S, V), the exit
    distribution (T, B, S)); `train_one_batch(ids, targets)` -> (loss, the
    T passes' mean cross-entropies, the mean exit distribution, the last
    pass's logits at `sample` fixed positions): never the (tokens,
    vocabulary) logits, which a loop that runs ahead would pile up."""

    def __init__(self, vocab_size, dim=256, num_heads=8, num_layers=4,
                 ffn_dim=None, ut_steps=4, rope_theta=1e6, norm_eps=1e-6,
                 beta=0.1, sample=128, recompute=False, name=None):
        super().__init__(name)
        self.vocab_size, self.dim = vocab_size, dim
        self.ut_steps, self.beta, self.sample = ut_steps, beta, sample
        self.recompute = recompute
        # the residual stream stays fp32 under `amp`
        self.tok_embed = layer.Embedding(vocab_size, dim, out_dtype="float32")
        self.blocks = [layer.TransformerBlock(
            num_heads, causal=True, rope=True, rope_theta=rope_theta,
            norm="rms", norm_eps=norm_eps, ffn="swiglu", ffn_dim=ffn_dim,
            ffn_bias=False, post_norm=True) for _ in range(num_layers)]
        self.register_layers(*self.blocks)
        self.ln_f = layer.RMSNorm(norm_eps)
        self.head = layer.Linear(vocab_size, bias=False, out_dtype="float32")
        self.exit_gate = ExitGate()

    # -- the regions a step may recompute ----------------------------------
    def _region(self, fn, *xs, reads):
        """A block application or a pass's head-and-loss: what a training
        step with `recompute` keeps as its inputs alone and rebuilds on the
        way back."""
        if not (autograd.training and self.recompute):
            return fn(*xs)
        self._regions += 1
        return autograd.region(fn, *xs, reads=reads)

    def _passes(self, ids):
        """The hidden state after each pass, h_1..h_T."""
        h = self.tok_embed(ids)
        for t in range(1, self.ut_steps + 1):
            with jax.named_scope(f"ut{t}"):
                for b in self.blocks:
                    h = self._region(b, h, reads=b.get_params().values())
                h = self.ln_f(h)
            yield h

    def _shared_weights(self):
        """The parameters a step uses more than once and casts under
        `amp`: the blocks' and the head's matrices."""
        return [p for l in (*self.blocks, self.head)
                for p in l.get_params().values() if len(p.shape) > 1]

    def forward(self, ids):
        zs, lams = [], []
        for h in self._passes(ids):
            zs.append(self.head(h).data)
            lams.append(self.exit_gate(h).data.reshape((1,) + ids.shape))
        dev = ids.device
        return (Tensor(data=jnp.stack(zs), device=dev, requires_grad=False),
                Tensor(data=exit_distribution(jnp.concatenate(lams)),
                       device=dev, requires_grad=False))

    def _head_loss(self, rows):
        """(h, flat targets) -> a position's cross-entropy at this pass
        (and, with `rows`, those rows of the logits)."""
        def fn(h, tflat):
            z = self.head(h)
            with jax.named_scope("loop_loss"):
                z = autograd.reshape(z, (-1, self.vocab_size))
                ce = _TokenCrossEntropy()(z, tflat)
                return ce if rows is None else (ce, _Rows(rows)(z))
        return fn

    def train_one_batch(self, ids, targets):
        T = self.ut_steps
        n = int(np.prod(ids.shape))
        # the sampled positions: fixed, evenly spread over the batch
        rows = np.linspace(0, n - 1, min(self.sample, n)).astype(np.int32)
        self._regions = 0
        with jax.named_scope("loop_loss"):
            tflat = autograd.reshape(targets, (-1,))
        # the backward pass too: its rebuilt regions find the same copies
        with autograd.cast_once(self._shared_weights()):
            lams, ces, sampled = [], [], None
            for t, h in enumerate(self._passes(ids), 1):
                lams.append(self.exit_gate(h))
                out = self._region(
                    self._head_loss(rows if t == T else None), h, tflat,
                    reads=self.head.get_params().values())
                if t == T:
                    out, sampled = out
                ces.append(out)
            with jax.named_scope("loop_loss"):
                loss, ce_pass, p_mean = _LoopLoss(self.beta)(*lams, *ces)
            _loop_plan(passes=T, blocks=len(self.blocks),
                       applications=T * len(self.blocks),
                       recomputed_regions=self._regions,
                       weight_casts=1 if autograd.compute_dtype else 0)
            self.optimizer(loss)
        return loss, ce_pass, p_mean, sampled


def _loop_plan(**kinds):
    """What the latest traced step of a looped model does, readable with
    no chip: `singa_loop_plan{kind}`."""
    g = observe.gauge(
        "singa_loop_plan",
        "the latest traced step of a looped model, by kind: passes, blocks, "
        "block applications, regions recomputed in the backward pass, casts "
        "of one shared weight to the compute dtype (0: no amp)")
    for kind, v in kinds.items():
        g.set(v, kind=kind)


def create_model(vocab_size=256, **kwargs):
    return LoopLM(vocab_size, **kwargs)
