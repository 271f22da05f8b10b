"""Of the device time under the gated short convolutions' scopes, the share
that is not under `in_proj` or `out_proj` (the two matrix products): the
chain B * u, the taps and C *, bound by memory, and the casts. What a
kernel for the chain would take over."""

import conv_scopes
import scopes


@scopes.reader
def read(record, trace):
    whole = conv_scopes.seconds(trace, record["hlo_dir"],
                                conv_scopes.in_conv)
    inner = conv_scopes.seconds(trace, record["hlo_dir"],
                                conv_scopes.in_projections)
    return 100.0 * (whole - inner) / whole if whole else None
