"""Seconds of set-up under `model.create`, `model.init` (the eager init
pass: one small program an operator) and `Model.compile`'s `opt.setup`,
each net of what nests in it. From the program's span histogram."""

import setup_parts


def read(record, trace):
    return setup_parts.value(record, "setup_init_s")
