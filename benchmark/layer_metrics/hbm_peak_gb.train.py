"""peak_bytes_in_use as the window closes, before anything else allocates."""


def read(record, trace):
    return record["values"].get("hbm_peak_gb")
