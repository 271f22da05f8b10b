"""The least time for the grouped products of the traced stretch (each
Mosaic call under an expert layer's `experts` scope is one product over the
rows the step reported for that layer: flops_mellum.grouped_product_cost,
the larger of operations over peak FLOP/s and bytes over peak bytes/s) over
the device time those calls took, in percent. A product padded to the
worst case would read low, as it should."""

import flops_mellum
import moe_scopes
import scopes


@scopes.reader
def read(record, trace):
    v = record["values"]
    cfg, rows = v.get("model_args"), v.get("moe_rows")
    calls = moe_scopes.grouped_products(trace, record["hlo_dir"])
    spent = sum(c[1] for c in calls)
    if not cfg or not rows or not spent:
        return None
    least = sum(n * flops_mellum.least_seconds(
        flops_mellum.grouped_product_cost(cfg, sum(rows[layer])),
        v["device_kind"]) for layer, _s, n in calls)
    return 100.0 * least / spent
