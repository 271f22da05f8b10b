"""Share of the traced stretch in which no operation ran on the device:
1 - (union of the device operations' intervals) / (traced stretch)."""


def read(record, trace):
    return 100.0 * trace["idle_share"] if trace else None
