"""`setup_init_s` + `setup_trace_s` + `setup_compile_s` as a share of this
run's own `setup_s`: what the program's spans account for. The rest is
imports, the TPU's start, traffic generation, the reference, the warm-up."""

import setup_parts


def read(record, trace):
    return setup_parts.value(record, "setup_attributed_share")
