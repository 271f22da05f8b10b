"""The model's FLOPs a step (the driver's count from shapes, recomputation
not counted: `values["model_flops_per_step"]`) over the device's busy
seconds a step program and the chip's bf16 peak, in percent: the share of
the whole step's peak. Over 100 would be a fault in the count."""

import flops
import scopes


@scopes.reader
def read(record, trace):
    per_step = record["values"].get("model_flops_per_step")
    n = scopes.programs_run(trace, record["hlo_dir"])
    if not per_step or not n:
        return None
    peak = flops.peak(record["values"]["device_kind"], "bf16_flops")
    return 100.0 * per_step / (trace["busy_s"] / n * peak)
