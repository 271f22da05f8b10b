"""A looped language model (Ouro / LoopLM) in plain jax.numpy, float32:
forward, the exit distribution, the loss and its parts, and `jax.grad` of
the loss. Written from the equations of ISSUE 28, not by calling the
program's models/. No kernel, no recomputation, no tape. It takes the
program's own parameter dict (name -> array) so both sides hold the same
weights.

    block:  a = x + N2(Attn(N1(x)));  y = a + N4(MLP(N3(a)))
            Attn: q, k, v = u Wq, u Wk, u Wv (no bias), rotary on q and k
                  over the whole head, causal softmax attention scaled by
                  1/sqrt(head size), output o Wo
            MLP(u) = (silu(u W_gate) * (u W_up)) W_down
    loop:   h_0 = E[ids];  h_t = N_f(Stack(h_{t-1})),  t = 1..T
            z_t = h_t W_head;  lam_t = sigmoid(h_t . w_g + b_g)
    exit:   p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j), 1 < t < T;
            p_T = prod_{j<T}(1 - lam_j)
    loss:   mean over positions of [sum_t p_t CE(z_t, target) - beta H(p)],
            H(p) = -sum_t p_t log p_t

N is an RMS norm with its own gain; every N, Attn, MLP, W_head and the gate
has one set of weights, used at every pass. What the published
`config.json` gives: T (`total_ut_steps`), every width, the norm's `eps`,
`rope_theta`, the untied head. What it does not give, and this file takes
from the family's paper as ISSUE 28 states it (`assumed` in the
configuration file):
  (a) the second norm on each branch, N2 and N4 ("sandwich" norms);
  (b) N_f at the end of every pass, its output fed to the next;
  (c) the exit gate as one linear map of h_t to a scalar, and the exit
      distribution and entropy-regularised expected loss built on it;
  (d) beta = 0.1.
Departures from the published description: none known beyond (a)-(d); the
weights are random, the rotary tables are the "rotate half" convention
(pairs (i, i + D/2)) of the family's released code.

On a TPU an fp32 matmul runs in lower precision unless the precision is
raised, so every function runs under default_matmul_precision("highest").
A block is one jitted function called once an application: every
application has the same shapes, so it compiles once whatever T and depth.
The head and the cross-entropy go through the positions `token_block` at a
time, so the (tokens, vocabulary) logits never exist whole; `grads` walks
back a block application and a token block at a time, for the same reason.
Nothing here names a dtype: every function computes in the dtype of the
parameters it is given (float32 from the program; bfloat16 for the control
that shows what a limit is worth).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rotary(x, theta):
    """x (B, H, S, D): pair (i, i + D/2) turned by pos * theta^(-2i/D)."""
    S, D = x.shape[-2:]
    inv = theta ** (-jnp.arange(D // 2, dtype=jnp.float32) / (D // 2))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "theta"))
def _block(x, p, n_head, eps, theta):
    B, S, E = x.shape
    D = E // n_head
    u = _rms(x, p["ln1.gamma"], eps)
    heads = lambda w: (u @ p[w]).reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
    q, k, v = _rotary(heads("attn.Wq"), theta), \
        _rotary(heads("attn.Wk"), theta), heads("attn.Wv")
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, E) @ p["attn.Wo"]
    a = x + _rms(o, p["ln1_post.gamma"], eps)                  # (a): N2
    u = _rms(a, p["ln2.gamma"], eps)
    m = (jax.nn.silu(u @ p["fc_gate.W"]) * (u @ p["fc1.W"])) @ p["fc2.W"]
    return a + _rms(m, p["ln2_post.gamma"], eps)               # (a): N4


def block_params(params):
    """[{short name: array}] a block, in depth order, from the program's
    flat names (`TransformerBlock_<i>.<short name>`)."""
    blocks = {}
    for name, a in params.items():
        m = re.match(r"TransformerBlock_(\d+)\.(.+)$", name)
        if m:
            blocks.setdefault(int(m.group(1)), {})[m.group(2)] = a
    return [blocks[i] for i in sorted(blocks)]


def hidden_states(params, ids, cfg, passes=None, skip=None, untied=None):
    """[h_1 .. h_T], each (B, S, E). `cfg`: the program's `create_model`
    arguments (`num_heads`, `ut_steps`, `norm_eps`, `rope_theta`, `beta`).
    `passes` runs that many passes and not T; `skip=(t, l)` leaves block l
    (from 0) out of pass t (from 1) and no other: two deliberately wrong
    models, which a tolerance has to tell from the right one. `untied`: T
    parameter dicts, pass t taking its stack and N_f from `untied[t - 1]`:
    the same numbers where all are `params`, and the gradient of copy t is
    what pass t adds to a shared parameter's."""
    T = passes or cfg["ut_steps"]
    eps, theta = float(cfg["norm_eps"]), float(cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        h, out = params["tok_embed.W"][jnp.asarray(ids, jnp.int32)], []
        for t, own in enumerate(untied or [params] * T, 1):
            for l, p in enumerate(block_params(own)):
                if (t, l) != skip:
                    h = _block(h, p, cfg["num_heads"], eps, theta)
            h = _rms(h, own["ln_f.gamma"], eps)                # (b): N_f
            out.append(h)
    return out


def gate(params, h):
    """lam (B, S): (c), one linear map of h to a scalar, squashed."""
    return jax.nn.sigmoid(
        jnp.sum(h * params["exit_gate.w"], -1) + params["exit_gate.b"])


def exit_distribution(lam):
    """[lam_1 .. lam_T] -> [p_1 .. p_T]; lam_T is not read."""
    p, left = [], jnp.ones_like(lam[0])
    for lam_t in lam[:-1]:
        p.append(lam_t * left)
        left = left * (1.0 - lam_t)
    return p + [left]


def logits(params, h):
    with jax.default_matmul_precision("highest"):
        return h @ params["head.W"]


def _ce(z, targets):
    lse = jax.nn.logsumexp(z, axis=-1)
    return lse - jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]


def loss_from_parts(ce, p, beta):
    """ce, p: [T arrays of positions] -> the loss (d)."""
    expected = sum(p_t * ce_t for p_t, ce_t in zip(p, ce))
    entropy = -sum(jax.scipy.special.xlogy(p_t, p_t) for p_t in p)
    return jnp.mean(expected - beta * entropy)


def forward(params, ids, cfg, passes=None, skip=None, untied=None):
    """(z (T, B, S, V), lam (T, B, S), p (T, B, S)): everything whole, for
    small sizes."""
    hs = hidden_states(params, ids, cfg, passes, skip, untied)
    lam = [gate(params, h) for h in hs]
    return (jnp.stack([logits(params, h) for h in hs]), jnp.stack(lam),
            jnp.stack(exit_distribution(lam)))


def loss_parts(params, ids, targets, cfg, rows=None, passes=None, skip=None,
               token_block=1024):
    """{"loss", "ce" (T,), "p" (T,): the mean cross-entropy and the mean
    exit probability of each pass, "sample": the last pass's logits at the
    flat positions `rows`}, the head taken `token_block` positions at a
    time."""
    hs = hidden_states(params, ids, cfg, passes, skip)
    tgt = jnp.asarray(targets, jnp.int32).reshape(-1)
    n = tgt.shape[0]
    ce = []
    for h in hs:
        flat = h.reshape(n, -1)
        ce.append(jnp.concatenate([
            _ce(logits(params, flat[i:i + token_block]),
                tgt[i:i + token_block])
            for i in range(0, n, token_block)]))
    p = exit_distribution([gate(params, h).reshape(n) for h in hs])
    out = {"loss": float(loss_from_parts(ce, p, cfg["beta"])),
           "ce": [float(jnp.mean(c)) for c in ce],
           "p": [float(jnp.mean(q)) for q in p]}
    if rows is not None:
        out["sample"] = logits(params, hs[-1].reshape(n, -1)[jnp.asarray(rows)])
    return out


def loss(params, ids, targets, cfg, untied=None):
    """The loss as one differentiable function of `params` (and of
    `untied`), the logits whole: small sizes."""
    with jax.default_matmul_precision("highest"):
        z, _, p = forward(params, ids, cfg, untied=untied)
        tgt = jnp.asarray(targets, jnp.int32)
        return loss_from_parts([_ce(z_t, tgt) for z_t in z], list(p),
                               cfg["beta"])


grad = jax.grad(loss)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "theta"))
def _block_vjp(x, p, dy, n_head, eps, theta):
    """(dx, dp) of one block application."""
    return jax.vjp(functools.partial(
        _block, n_head=n_head, eps=eps, theta=theta), x, p)[1](dy)


@jax.jit
def _head_vjp(h, w, targets, dce):
    """(dh, dW) of the head and cross-entropy of one token block."""
    return jax.vjp(lambda h, w: _ce(h @ w, targets), h, w)[1](dce)


def grads(params, ids, targets, cfg, token_block=1024):
    """({name: d loss / d parameter}, the same for the blocks' parameters
    from the LAST pass's use of them alone). `grad` for the sizes at which
    the whole graph does not fit: the forward keeps every block
    application's input, the way back takes one application and one token
    block of the head at a time. The second value is what a step would hold
    that dropped a shared weight's earlier consumers: a wrong gradient a
    tolerance has to tell from the right one."""
    T, H, beta = cfg["ut_steps"], cfg["num_heads"], cfg["beta"]
    eps, theta = float(cfg["norm_eps"]), float(cfg["rope_theta"])
    stack = sorted((int(re.match(r"TransformerBlock_(\d+)\.", k).group(1))
                    for k in params if k.startswith("TransformerBlock_")))
    stack = [f"TransformerBlock_{i}." for i in dict.fromkeys(stack)]
    blocks = block_params(params)
    ids = jnp.asarray(ids, jnp.int32)
    tgt = jnp.asarray(targets, jnp.int32).reshape(-1)
    n = tgt.shape[0]
    cuts = range(0, n, token_block)
    with jax.default_matmul_precision("highest"):
        h, xs, pre, flat = params["tok_embed.W"][ids], [], [], []
        for _ in range(T):
            for p in blocks:
                xs.append(h)
                h = _block(h, p, H, eps, theta)
            pre.append(h)
            h = _rms(h, params["ln_f.gamma"], eps)
            flat.append(h.reshape(n, -1))
        ce = jnp.stack([jnp.concatenate([
            _ce(f[i:i + token_block] @ params["head.W"],
                tgt[i:i + token_block]) for i in cuts]) for f in flat])
        lam = jnp.stack([gate(params, f) for f in flat])
        dlam, dce = jax.grad(lambda lam, ce: loss_from_parts(
            list(ce), exit_distribution(list(lam)), beta), (0, 1))(lam, ce)
        g = {k: jnp.zeros_like(v) for k, v in params.items()}
        last, dh = None, jnp.zeros_like(pre[0])
        for t in reversed(range(T)):
            parts = []
            for i in cuts:
                d, dw = _head_vjp(flat[t][i:i + token_block],
                                  params["head.W"], tgt[i:i + token_block],
                                  dce[t, i:i + token_block])
                parts.append(d)
                g["head.W"] = g["head.W"] + dw
                # one call in flight, not all of them queued with their
                # outputs: the device's memory is the program's to fill
                g["head.W"].block_until_ready()
            d, dw, db = jax.vjp(
                lambda f, w, b: gate({"exit_gate.w": w, "exit_gate.b": b}, f),
                flat[t], params["exit_gate.w"], params["exit_gate.b"]
            )[1](dlam[t])
            g["exit_gate.w"] = g["exit_gate.w"] + dw
            g["exit_gate.b"] = g["exit_gate.b"] + db
            dh = dh + (jnp.concatenate(parts) + d).reshape(dh.shape)
            dh, dgamma = jax.vjp(lambda x, gamma: _rms(x, gamma, eps),
                                 pre[t], params["ln_f.gamma"])[1](dh)
            g["ln_f.gamma"] = g["ln_f.gamma"] + dgamma
            for l in reversed(range(len(blocks))):
                dh, dp = _block_vjp(xs.pop(), blocks[l], dh, H, eps, theta)
                for k, v in dp.items():
                    g[stack[l] + k] = g[stack[l] + k] + v
                dh.block_until_ready()
            if last is None:    # to the host, for the same reason
                last = {k: np.asarray(v) for k, v in g.items()
                        if k.startswith("TransformerBlock_")}
        g["tok_embed.W"] = g["tok_embed.W"].at[ids].add(dh)
    return g, last
