"""The first training step's change of every parameter, held to the
reference: the reference's gradient on the same batch put through the
optimizer's first step by hand. What a training driver's `correct` knows of
the backward pass and of the optimizer: a gradient part left out, a region
rebuilt wrongly, an all-reduce that did not happen or a state left
unchanged all read near 1 here, where rounding reads a tenth.

Adam's first step from a zero state is -lr g / (|g| + eps): nearly lr times
the sign of g. An entry whose gradient is smaller than the rounding of the
program's precision takes either sign, and counts with its full weight: so
a sound bf16 step reads about a tenth, not a thousandth, and a leaf whose
true gradient is zero (a key bias: softmax does not see it) reads 1.4 in
any precision. `compared` leaves such leaves out by the reference's own
numbers, never by the program's.
"""

import math

import jax.numpy as jnp
import numpy as np


def adam_first_step(g, lr, eps=1e-8):
    """opt.Adam's first update from its zero state (m-hat = g, v-hat =
    g^2, no weight decay)."""
    return -lr * g / (jnp.abs(g) + eps)


def compared(grads, floor):
    """The leaves whose reference gradient is more than rounding: a root
    mean square of at least `floor` times the whole gradient's."""
    sq = {k: float(jnp.sum(jnp.square(g.astype(jnp.float32))))
          for k, g in grads.items()}
    whole = sum(sq.values()) / sum(g.size for g in grads.values())
    return [k for k, g in grads.items() if sq[k] / g.size >= floor ** 2 * whole]


def _summary(sq_err, sq_ref, leaves):
    worst = max(sq_err, key=lambda k: sq_err[k] / sq_ref[k])
    return {"whole": math.sqrt(sum(sq_err.values()) / sum(sq_ref.values())),
            "worst_leaf": math.sqrt(sq_err[worst] / sq_ref[worst]),
            "worst_leaf_name": worst,
            "leaves_compared": len(sq_err), "leaves": leaves}


def _sq(a):
    return float(jnp.sum(jnp.square(a.astype(jnp.float32))))


class Expected:
    """What the parameters should be after the first step: `params` plus
    the reference update from `grads`, kept on the HOST (the step needs the
    device's memory), with each update's squared norm."""

    def __init__(self, params, grads, lr, floor):
        self.leaves, self.lr = len(params), lr
        self.names = compared(grads, floor)
        self.update_sq, self.after = {}, {}
        for k in self.names:
            u = adam_first_step(grads[k].astype(jnp.float32), lr)
            self.update_sq[k] = _sq(u)
            self.after[k] = np.asarray(params[k] + u)

    def error_of_step(self, after):
        """`after`: {name: the program's parameter on the default device}
        once the step has run. Norm of (got - expected) over the norm of
        the expected update: the whole, and the worst leaf."""
        return _summary({k: _sq(after[k] - jnp.asarray(self.after[k]))
                         for k in self.names}, self.update_sq, self.leaves)

    def error_of_gradient(self, grads, wrong):
        """The same reading for the update a WRONG gradient would give
        (`wrong` may hold some leaves only), against `grads`'s."""
        names = [k for k in self.names if k in wrong]
        return _summary(
            {k: _sq(adam_first_step(wrong[k].astype(jnp.float32), self.lr)
                    - adam_first_step(grads[k].astype(jnp.float32), self.lr))
             for k in names}, {k: self.update_sq[k] for k in names},
            self.leaves)
