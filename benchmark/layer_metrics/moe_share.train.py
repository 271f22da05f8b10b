"""Device time under the expert layers' scopes (`<block>/moe`: router,
dispatch, experts, combine and the casts; forward, the recomputed forward
and backward) as a share of the device's busy time."""

import moe_scopes
import scopes


@scopes.reader
def read(record, trace):
    return scopes.share(trace, moe_scopes.seconds(
        trace, record["hlo_dir"], moe_scopes.in_moe))
