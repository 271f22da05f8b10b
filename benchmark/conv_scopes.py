"""Device time of a step by its gated short convolutions' scopes, for the
readers of `shortconv_share.train` and `shortconv_mix_share.train`.

The program (layer.ShortConv, ops/shortconv.py) puts a convolution
operator under `<block>/conv` and its parts under `in_proj`, `mix` and
`out_proj` (the second forward of a recomputed block behind a leading
`recompute`, the backward behind `bwd`, as every scope); the casts of
`amp` sit beside them under `conv`. scopes.py parses the names; this file
only picks. A fusion counts at its root, so a part's time can hold a
neighbour's elementwise work: the readers give shares of time, never a
share of a peak.
"""

import scopes

CONV, PROJECTIONS = "conv", ("in_proj", "out_proj")


def in_conv(path):
    return CONV in path


def in_projections(path):
    return CONV in path and any(
        p in path[path.index(CONV):] for p in PROJECTIONS)


def seconds(trace, hlo_dir, pick):
    """Seconds of the traced stretch in the step's instructions whose scope
    path `pick` takes. Raises ValueError where the step's text has no
    convolution scope at all (another model's program, or a program from
    before the layer)."""
    table = scopes.instructions(hlo_dir)
    if not any(i["path"] and in_conv(i["path"]) for i in table.values()):
        raise ValueError("the step's text holds no `conv` scope")
    return sum(t for n, t in trace["self_s"].items()
               if n in table and table[n]["path"] and pick(table[n]["path"]))
