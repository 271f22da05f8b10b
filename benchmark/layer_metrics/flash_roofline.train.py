"""The least time the chip could take for the flash forward and backward
calls of the traced stretch (the larger of operations over peak FLOP/s and
bytes over peak bytes/s, from benchmark/flops.py, times the calls counted in
the trace) over the device time those calls took."""

import flops
import kernels


def read(record, trace):
    if not trace:
        return None
    shape, kind = record["values"]["flash_shape"], record["values"]["device_kind"]
    backward = lambda key, op: "transpose(" in op
    fwd_s, fwd_n = kernels.attention_seconds(
        trace, record["hlo_dir"], lambda key, op: not backward(key, op))
    bwd_s, bwd_n = kernels.attention_seconds(
        trace, record["hlo_dir"], backward)
    if not fwd_s + bwd_s:
        return None
    least = fwd_n * flops.roofline_seconds(
        *flops.flash_fwd_cost(*shape), kind)[0] \
        + bwd_n * flops.roofline_seconds(
            *flops.flash_bwd_cost(*shape), kind)[0]
    return 100.0 * least / (fwd_s + bwd_s)
