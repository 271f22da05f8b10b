"""The least time for the traced delta rules (each pass of the chunked
gated delta rule over the step's (batch, seq) and a linear layer's heads:
flops_olmo_hybrid.delta_cost, bound by its bytes over peak bytes/s) over
the device time of the Mosaic calls named `singa_delta_*`, in percent. A
call named `singa_delta_fwd` or `singa_delta_bwd` is one pass; a pass
split over more calls (`singa_delta_bwd_<part>`) counts once, by the call
of the plain name, and the time of every part. A program whose rule XLA
implements (one from before the kernels, another model's) has no such
call: nothing to read."""

import re

import flops
import flops_olmo_hybrid
import kernels
import scopes

_RULE = re.compile(r"singa_delta_(fwd|bwd)(\w*)")


@scopes.reader
def read(record, trace):
    v = record["values"]
    cfg, (B, S) = v.get("model_args"), v.get("batch", (0, 0))
    calls = [(m.group(1) == "bwd", not m.group(2), trace["self_s"][name],
              trace["calls"].get(name, 0))
             for name, (key, op) in kernels.mosaic_calls(
                 record["hlo_dir"]).items()
             for m in [_RULE.search(op)]
             if key == "step" and m and name in trace["self_s"]]
    spent = sum(c[2] for c in calls)
    if not cfg or "linear_heads" not in cfg or not spent:
        return None
    least = sum(n * flops.roofline_seconds(*flops_olmo_hybrid.delta_cost(
        B, S, cfg["linear_heads"], cfg["linear_key_dim"],
        cfg["linear_value_dim"], cfg["chunk"], bwd), v["device_kind"])[0]
        for bwd, whole, _s, n in calls if whole)
    return 100.0 * least / spent
