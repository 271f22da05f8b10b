"""The gradient of reference.py's loss, in plain jax.numpy: the mean
cross-entropy over a batch, differentiated a row and a block at a time so
that a whole training batch fits on one chip beside the program (the
forward keeps each block's input, the way back takes one block's vjp at a
time). It calls reference.py's own pieces and adds nothing to the model.
"""

import functools

import jax
import jax.numpy as jnp

import reference


@functools.partial(jax.jit, static_argnames="n_head")
def _block_vjp(x, p, dy, n_head):
    """(dx, dp) of one block."""
    return jax.vjp(functools.partial(reference._block, n_head=n_head),
                   x, p)[1](dy)


@jax.jit
def _head_grad(x, g, b, w, targets, scale):
    """d (scale * sum of the positions' cross-entropy) / d (x, g, b, w)."""
    def f(x, g, b, w):
        lg = reference._head(x, g, b, w)
        hit = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
        return scale * jnp.sum(jax.nn.logsumexp(lg, axis=-1) - hit)
    return jax.grad(f, (0, 1, 2, 3))(x, g, b, w)


def grads(params, ids, targets, n_head):
    """{name: d loss / d parameter} of `reference.loss` on the batch."""
    ids = jnp.asarray(ids, jnp.int32)
    targets = jnp.asarray(targets, jnp.int32)
    names = sorted({k.split(".", 1)[0] for k in params
                    if k.startswith("TransformerBlock_")},
                   key=lambda k: int(k.split("_")[1]))
    blocks = reference.block_params(params)
    g = {k: jnp.zeros_like(v) for k, v in params.items()}
    S = ids.shape[1]
    with jax.default_matmul_precision("highest"):
        for r in range(ids.shape[0]):
            row = ids[r:r + 1]
            x, xs = reference._embed(row, params["tok_embed.W"],
                                     params["pos_embed"]), []
            for p in blocks:
                xs.append(x)
                x = reference._block(x, p, n_head)
            dx, *dhead = _head_grad(
                x, params["ln_f.gamma"], params["ln_f.beta"],
                params["head.W"], targets[r:r + 1], 1.0 / ids.size)
            for k, d in zip(("ln_f.gamma", "ln_f.beta", "head.W"), dhead):
                g[k] = g[k] + d
            for name, p, x in zip(names[::-1], blocks[::-1], xs[::-1]):
                dx, dp = _block_vjp(x, p, dx, n_head)
                for k, d in dp.items():
                    g[f"{name}.{k}"] = g[f"{name}.{k}"] + d
            g["tok_embed.W"] = g["tok_embed.W"].at[row[0]].add(dx[0])
            g["pos_embed"] = g["pos_embed"].at[:S].add(dx[0])
            # a row in flight, not the batch queued with its outputs
            g["pos_embed"].block_until_ready()
    return g
