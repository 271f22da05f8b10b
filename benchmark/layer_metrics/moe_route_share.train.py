"""Of the device time under the expert layers' scopes, the share that is
not under `experts` (the grouped products and the gate between them): the
router, the top-k, the sort, the gathers both ways and the casts. What the
sorted dispatch costs beside the experts' own work."""

import moe_scopes
import scopes


@scopes.reader
def read(record, trace):
    whole = moe_scopes.seconds(trace, record["hlo_dir"], moe_scopes.in_moe)
    inner = moe_scopes.seconds(trace, record["hlo_dir"],
                               moe_scopes.in_experts)
    return 100.0 * (whole - inner) / whole if whole else None
