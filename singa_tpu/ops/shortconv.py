"""The gated short convolution (the LFM2 family's operator beside
attention), in `jax.numpy`:

    (B, C, u) = split3(x W_in)                 W_in (D, 3D), in that order
    z = B * u
    c_t = sum_j w[:, j] * z_{t - (L - 1) + j}  depthwise, causal, z = 0
                                               before the sequence; w (D, L)
    y = (C * c) W_out                          W_out (D, D)

Three device scopes: `in_proj`, `mix` (the elementwise chain between the
products: bound by memory, XLA's fusions) and `out_proj`. The chain computes
in fp32 whatever the products' dtype, and is kept as its INPUT: under
`jax.checkpoint` the backward pass reads the product's (.., 3D) result again
and rebuilds z and c on the way, where the plain vjp would keep four fp32
arrays the size of the stream a layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_taps(z, w):
    """z (B, S, D), w (D, L) -> c (B, S, D): c_t = sum_j w[:, j] *
    z_{t-(L-1)+j}, with zeros before each sequence's start."""
    L, S = w.shape[1], z.shape[1]
    zp = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
    return sum(w[:, j] * zp[:, j:j + S] for j in range(L))


@jax.checkpoint
def gated_taps(bcu, w):
    """bcu (B, S, 3D) = [B ; C ; u] -> C * taps(B * u), in bcu's dtype."""
    b, c, u = (a.astype(jnp.float32) for a in jnp.split(bcu, 3, axis=-1))
    return (c * causal_taps(b * u, w.astype(jnp.float32))).astype(bcu.dtype)


def short_conv(x, W_in, w, W_out):
    """x (B, S, D) -> (B, S, D), the products in the dtype of x."""
    with jax.named_scope("in_proj"):
        bcu = jnp.matmul(x, W_in)
    with jax.named_scope("mix"):
        y = gated_taps(bcu, w)
    with jax.named_scope("out_proj"):
        return jnp.matmul(y, W_out)
