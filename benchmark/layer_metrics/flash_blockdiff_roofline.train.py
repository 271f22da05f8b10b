"""The least time for the traced flash passes under the block-diffusion
mask (flops_sdar.flash_cost: the pairs inside the whole mask, S^2 + S b a
head, and the doubled sequence's q k v o read and written once; the larger
of operations over peak FLOP/s and bytes over peak bytes/s) over the device
time of the `_bd` kernels' calls, in percent. A pass is counted once however
many Mosaic calls it is lowered to: a backward split in two counts by its
`_dkv` call, and the `_dq` call's time is in the sum all the same."""

import blockdiff_scopes
import flops_sdar
import scopes


@scopes.reader
def read(record, trace):
    v = record["values"]
    cfg, (B, S) = v.get("model_args"), v.get("batch", (0, 0))
    calls = blockdiff_scopes.flash_calls(trace, record["hlo_dir"])
    spent = sum(c[2] for c in calls)
    if not cfg or "block_length" not in cfg or not spent:
        return None
    least = sum(
        n * flops_sdar.least_seconds(
            flops_sdar.flash_cost(cfg, B, S, bwd), v["device_kind"])
        for bwd, part, _s, n in calls if part != "_dq")
    return 100.0 * least / spent
