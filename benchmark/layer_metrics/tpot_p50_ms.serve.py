"""Median over requests of (finished_ts - first_token_ts) / (tokens - 1):
tokens surface every steps_per_sync steps, so a per-request mean is the
sound gap between tokens today."""


def read(record, trace):
    return record["values"].get("tpot_p50_ms")
