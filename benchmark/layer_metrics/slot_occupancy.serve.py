"""Tokens the decode steps emitted inside the window (all tokens less the
first tokens, which prefill emits) over (decode steps x max_slots)."""


def read(record, trace):
    return record["values"].get("slot_occupancy")
