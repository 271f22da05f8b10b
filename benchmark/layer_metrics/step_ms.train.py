"""Window over steps, host clock between two fences."""


def read(record, trace):
    return record["values"].get("step_ms")
