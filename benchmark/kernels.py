"""Which device operations are the attention kernels.

A Pallas kernel reaches the device as an HLO custom call with target
`tpu_custom_call`, and the trace names a device operation by its HLO
instruction (`jvp__.24`, not the kernel's own name). So the names come from
the compiled text the program captured for this run (introspect.capture_hlo):
every instruction with that target, with the executable it is in and its
`op_name`. In these programs every such call is an attention kernel of
ops/attention.py: flash forward (`.../jvp()/pallas_call` when training),
flash backward (`.../transpose(jvp())/pallas_call`), paged decode.
"""

import functools
import glob
import os
import re

_MOSAIC = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?custom_call_target='
    r'"tpu_custom_call"[^\n]*?op_name="([^"]*)"', re.M)


@functools.lru_cache(maxsize=None)   # several readers ask in one run
def mosaic_calls(hlo_dir):
    """{instruction name: (executable key, op_name)} over every executable
    captured under `hlo_dir` (files `<key>_<sha>.hlo.txt`)."""
    out = {}
    for path in sorted(glob.glob(os.path.join(hlo_dir, "*.hlo.txt"))):
        key = os.path.basename(path).rsplit("_", 1)[0]
        with open(path, encoding="utf-8") as f:
            for name, op in _MOSAIC.findall(f.read()):
                out[name] = (key, op)
    return out


def attention_seconds(trace, hlo_dir, which=lambda key, op: True):
    """(seconds, calls) of the traced stretch spent in the Mosaic calls that
    `which(executable key, op_name)` selects."""
    names = {n for n, (key, op) in mosaic_calls(hlo_dir).items()
             if which(key, op)}
    return (sum(t for n, t in trace["self_s"].items() if n in names),
            sum(c for n, c in trace["calls"].items() if n in names))
