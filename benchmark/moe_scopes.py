"""Device time of a sparse model's step by its expert layers' scopes and by
its kernels' names, for the readers of `moe_share.train`,
`moe_route_share.train`, `block_recompute_share.train`,
`expert_matmul_roofline.train`, `flash_window_roofline.train` and
`flash_full_roofline.train`.

The program (layer.DroplessMoE, parallel/moe.py) puts an expert layer under
`<block>/moe` and its parts under `router`, `dispatch`, `experts` and
`combine` (the second forward of a recomputed block behind a leading
`recompute`, the backward behind `bwd`, as every scope). The grouped
products are Mosaic calls under `experts` (the megablox kernels); the flash
kernels carry their own names in their `op_name`, `singa_flash_*`, and end
in `_win` where they work under a sliding window. scopes.py parses the
names; this file only picks.
"""

import re

import kernels
import scopes

MOE, EXPERTS = "moe", "experts"
_BLOCK = re.compile(r"TransformerBlock_(\d+)")
_FLASH = re.compile(r"singa_flash_(fwd|bwd)(_dq|_dkv)?(_win)?\b")


def in_moe(path):
    return MOE in path


def in_experts(path):
    return MOE in path and EXPERTS in path[path.index(MOE):]


def is_recomputed(path):
    return path[0] == "recompute"


def seconds(trace, hlo_dir, pick):
    """Seconds of the traced stretch in the step's instructions whose scope
    path `pick` takes. Raises ValueError where the step's text has no
    expert-layer scope at all (another model's program)."""
    table = scopes.instructions(hlo_dir)
    if not any(i["path"] and in_moe(i["path"]) for i in table.values()):
        raise ValueError("the step's text holds no `moe` scope")
    return sum(t for n, t in trace["self_s"].items()
               if n in table and table[n]["path"] and pick(table[n]["path"]))


def grouped_products(trace, hlo_dir):
    """[(layer, seconds, calls)] of the Mosaic calls under an expert
    layer's `experts` scope: the grouped products."""
    out = []
    for name, (key, op) in kernels.mosaic_calls(hlo_dir).items():
        path = scopes.parse_op_name(op)[1]
        layer = _BLOCK.search(op)
        if key == "step" and in_experts(path) and layer \
                and name in trace["self_s"]:
            out.append((int(layer.group(1)), trace["self_s"][name],
                        trace["calls"].get(name, 0)))
    return out


def flash_calls(trace, hlo_dir, windowed):
    """[(backward?, part, seconds, calls)] of the flash kernels' Mosaic
    calls that work under a window (`windowed`) or do not; `part` is "",
    "_dq" or "_dkv"."""
    out = []
    for name, (key, op) in kernels.mosaic_calls(hlo_dir).items():
        m = _FLASH.search(op)
        if key == "step" and m and bool(m.group(3)) == windowed \
                and name in trace["self_s"]:
            out.append((m.group(1) == "bwd", m.group(2) or "",
                        trace["self_s"][name], trace["calls"].get(name, 0)))
    return out


def flash_roofline_reader(windowed):
    """The least time for the traced flash passes of one kind of layer
    (flops_mellum.flash_cost at the pairs inside the mask) over the device
    time of their calls, in percent. A pass is counted once however many
    Mosaic calls it is split over: a backward split in two counts by its
    `_dkv` call, and the `_dq` call's time is in the sum all the same."""
    import flops_mellum

    @scopes.reader
    def read(record, trace):
        v = record["values"]
        cfg, (B, S) = v.get("model_args"), v.get("batch", (0, 0))
        calls = flash_calls(trace, record["hlo_dir"], windowed)
        spent = sum(c[2] for c in calls)
        if not cfg or not spent:
            return None
        window = cfg["window"] if windowed else None
        least = sum(
            n * flops_mellum.least_seconds(flops_mellum.flash_cost(
                cfg, B, S, window, bwd), v["device_kind"])
            for bwd, part, _s, n in calls if part != "_dq")
        return 100.0 * least / spent
    return read
