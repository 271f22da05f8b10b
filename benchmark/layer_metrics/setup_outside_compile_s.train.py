"""Seconds jax spent compiling, or reading its cache, under no span of the
program: the benchmark's own reference and checks. The sum of
`singa_xla_compile_seconds{where=none}`, both sources."""

import setup_parts


def read(record, trace):
    return setup_parts.value(record, "setup_outside_compile_s")
