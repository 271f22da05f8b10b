"""The largest load among the experts this chip holds over their mean load
(rows routed), averaged over the layers and the window's steps: 1 is even.
From the step's own third output, as the driver fetched it."""


def read(record, trace):
    return record["values"].get("expert_load_imbalance")
