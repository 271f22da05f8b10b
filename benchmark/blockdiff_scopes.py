"""The block-diffusion flash kernels in a trace, for the readers of
`flash_blockdiff_roofline.train` and `flash_blockdiff_share.train`.

The kernels of ops/attention.py carry their own names in their `op_name`
(`singa_flash_fwd`, `singa_flash_bwd`, `singa_flash_bwd_dq`,
`singa_flash_bwd_dkv`) and end in `_bd` where they work under the
block-diffusion mask. kernels.py finds the Mosaic calls; this file only
picks. A program without such a kernel (another model's, or a commit
before the mask) gives an empty list, and the readers nothing.
"""

import re

import kernels

_FLASH_BD = re.compile(r"singa_flash_(fwd|bwd)(_dq|_dkv)?_bd\b")


def flash_calls(trace, hlo_dir):
    """[(backward?, part, seconds, calls)] of the step's `_bd` Mosaic
    calls that ran in the traced stretch; `part` is "", "_dq" or "_dkv"."""
    out = []
    for name, (key, op) in kernels.mosaic_calls(hlo_dir).items():
        m = _FLASH_BD.search(op)
        if key == "step" and m and name in trace["self_s"]:
            out.append((m.group(1) == "bwd", m.group(2) or "",
                        trace["self_s"][name], trace["calls"].get(name, 0)))
    return out
