"""The largest load among ALL the experts of a layer (held on this chip or
not) over their mean load ((token, choice) pairs sent), averaged over the
sparse layers and the window's steps: 1 is even. What the router's
selection bias acts on. From the step's own fourth output, as the driver
fetched it; None from a program whose step hands back no such count."""


def read(record, trace):
    return record["values"].get("router_load_imbalance")
