"""GPT-2 forward and cross-entropy, plain jax.numpy in float32.

Written from the GPT-2 description (Radford et al. 2019; pre-LN blocks,
learned positions, tanh GELU, causal softmax attention scaled by
1/sqrt(head size)), not by calling the program's models/. No kernel, no
cache, no batching tricks. It takes the program's own parameter dict
(name -> array) so both sides hold the same weights.

One departure from GPT-2, following the program: the output head is its own
matrix `head.W` (d x V) and not the transposed token embedding (see the
configuration files' `assumed.untied_head`).

On a TPU an fp32 matmul runs in lower precision unless the precision is
raised, so every function runs under default_matmul_precision("highest").
A block is one jitted function called once a layer: every layer has the
same shapes, so it compiles once whatever the depth.
"""

import functools
import re

import jax
import jax.numpy as jnp

EPS = 1e-5  # layer_norm_epsilon of the published configs


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * g + b


def _gelu(x):  # "gelu_new": the tanh approximation
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames="n_head")
def _block(x, p, n_head):
    B, S, E = x.shape
    D = E // n_head
    zero = jnp.zeros((), jnp.float32)
    h = _ln(x, p["ln1.gamma"], p["ln1.beta"])

    def heads(w, b):
        y = h @ p[w] + p.get(b, zero)
        return y.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)

    q, k, v = heads("attn.Wq", "attn.bq"), heads("attn.Wk", "attn.bk"), \
        heads("attn.Wv", "attn.bv")
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, E)
    x = x + o @ p["attn.Wo"] + p.get("attn.bo", zero)
    h = _ln(x, p["ln2.gamma"], p["ln2.beta"])
    return x + _gelu(h @ p["fc1.W"] + p["fc1.b"]) @ p["fc2.W"] + p["fc2.b"]


@jax.jit
def _embed(ids, tok, pos):
    return tok[ids] + pos[:ids.shape[1]]


@jax.jit
def _head(x, g, b, w):
    return _ln(x, g, b) @ w


def block_params(params):
    """[{short name: array}] a block, in depth order, from the program's
    flat names (`TransformerBlock_<i>.<short name>`)."""
    blocks = {}
    for name, a in params.items():
        m = re.match(r"TransformerBlock_(\d+)\.(.+)$", name)
        if m:
            blocks.setdefault(int(m.group(1)), {})[m.group(2)] = a
    return [blocks[i] for i in sorted(blocks)]


def logits(params, ids, n_head, drop_last_blocks=0):
    """(B, S) int ids -> (B, S, V) float32 logits. `drop_last_blocks`
    leaves out that many of the deepest blocks: a deliberately wrong model,
    used to show that a tolerance would catch a skipped layer."""
    ids = jnp.asarray(ids, jnp.int32)
    blocks = block_params(params)
    blocks = blocks[:len(blocks) - drop_last_blocks]
    with jax.default_matmul_precision("highest"):
        x = _embed(ids, params["tok_embed.W"], params["pos_embed"])
        for p in blocks:
            x = _block(x, p, n_head)
        return _head(x, params["ln_f.gamma"], params["ln_f.beta"],
                     params["head.W"])


def cross_entropy(lg, targets):
    """Mean over every position of -log softmax(logits)[target]."""
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(
        lg, jnp.asarray(targets, jnp.int32)[..., None], axis=-1)[..., 0]
    return float(jnp.mean(lse - tgt))


def loss(params, ids, targets, n_head, drop_last_blocks=0):
    return cross_entropy(logits(params, ids, n_head, drop_last_blocks),
                         targets)
