"""Device time of the second forward of the blocks that a sparse model's
backward pass rebuilds (scope `recompute/<block>/...`: attention with its
flash forward call, the expert layer with its grouped products) as a share
of the device's busy time: what fitting the sequence's activations beside
the state costs. `recompute_share.train` reads the same scope on the looped
model, whose pass scopes it asks for first."""

import moe_scopes
import scopes


@scopes.reader
def read(record, trace):
    return scopes.share(trace, moe_scopes.seconds(
        trace, record["hlo_dir"], moe_scopes.is_recomputed))
