"""Seconds of set-up under the `compile` phase spans (the backend's compile
or the cache's read), `introspect.warm_load` and
`introspect.first_dispatch` (the first call of a fresh executable). From
the program's span histogram."""

import setup_parts


def read(record, trace):
    return setup_parts.value(record, "setup_compile_s")
