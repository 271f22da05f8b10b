"""Median of first_token_ts - submitted over requests submitted and finished
inside the window (the engine's own monotonic stamps)."""


def read(record, trace):
    return record["values"].get("ttft_p50_ms")
