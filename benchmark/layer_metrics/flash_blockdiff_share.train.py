"""Device time of the flash kernels that work under the block-diffusion
mask (the Mosaic calls whose name ends in `_bd`: forward, the recomputed
forward, dq and dkv) as a share of the device's busy time: what attention
over the doubled sequence costs the step. Scheduled by the mask it is about
half a causal pass over the same rows; masked densely it would be twice."""

import blockdiff_scopes
import scopes


@scopes.reader
def read(record, trace):
    spent = sum(c[2] for c in blockdiff_scopes.flash_calls(
        trace, record["hlo_dir"]))
    return scopes.share(trace, spent) if spent else None
