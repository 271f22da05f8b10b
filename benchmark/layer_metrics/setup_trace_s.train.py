"""Seconds of set-up under `model.build` and the `trace` and `lower` phase
spans of every staged build: the host's share of building a program.
From the program's span histogram."""

import setup_parts


def read(record, trace):
    return setup_parts.value(record, "setup_trace_s")
