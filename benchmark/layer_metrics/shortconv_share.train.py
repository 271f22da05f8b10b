"""Device time under the gated short convolutions' scopes (`<block>/conv`:
the two projections, the chain between them and the casts; forward, the
recomputed forward and backward) as a share of the device's busy time."""

import conv_scopes
import scopes


@scopes.reader
def read(record, trace):
    return scopes.share(trace, conv_scopes.seconds(
        trace, record["hlo_dir"], conv_scopes.in_conv))
