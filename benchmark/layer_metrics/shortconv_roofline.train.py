"""The least time for the traced short-convolution chains (each Mosaic call
named `singa_shortconv_fwd` or `singa_shortconv_bwd` is one pass of the
chain B * u, taps, C * over the step's (batch, seq, dim):
flops_lfm2.mix_cost, bound by its bytes over peak bytes/s) over the device
time those calls took, in percent. A program whose chain XLA's fusions
implement (the parent of PR 39, another model's) has no such call: nothing
to read."""

import re

import flops
import flops_lfm2
import kernels
import scopes

_CHAIN = re.compile(r"singa_shortconv_(fwd|bwd)\b")


@scopes.reader
def read(record, trace):
    v = record["values"]
    cfg, (B, S) = v.get("model_args"), v.get("batch", (0, 0))
    calls = [(m.group(1) == "bwd", trace["self_s"][name],
              trace["calls"].get(name, 0))
             for name, (key, op) in kernels.mosaic_calls(
                 record["hlo_dir"]).items()
             for m in [_CHAIN.search(op)]
             if key == "step" and m and name in trace["self_s"]]
    spent = sum(c[1] for c in calls)
    if not cfg or "conv_taps" not in cfg or not spent:
        return None
    least = sum(n * flops.roofline_seconds(*flops_lfm2.mix_cost(
        cfg, B, S, bwd), v["device_kind"])[0] for bwd, _s, n in calls)
    return 100.0 * least / spent
