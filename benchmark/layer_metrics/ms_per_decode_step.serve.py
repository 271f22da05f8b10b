"""Window over the decode steps the engine counted in it (report's `steps`)."""


def read(record, trace):
    return record["values"].get("ms_per_decode_step")
