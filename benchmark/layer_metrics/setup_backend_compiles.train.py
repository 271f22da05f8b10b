"""Programs the backend really compiled in this process, under any span or
none: 0 where the persistent cache held every one. The count of
`singa_xla_compile_seconds{source=backend}`."""

import setup_parts


def read(record, trace):
    return setup_parts.value(record, "setup_backend_compiles")
