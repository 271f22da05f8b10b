"""peak_bytes_in_use as the window closes, before the reference check."""


def read(record, trace):
    return record["values"].get("hbm_peak_gb")
