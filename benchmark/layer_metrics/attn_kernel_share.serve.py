"""Device time of the attention kernels (every Mosaic custom call of the
run's executables: the Pallas flash and paged kernels) as a share of the
device's busy time in the traced stretch."""

import kernels


def read(record, trace):
    if not trace or not trace["busy_s"]:
        return None
    seconds, _calls = kernels.attention_seconds(trace, record["hlo_dir"])
    return 100.0 * seconds / trace["busy_s"]
