"""Device busy seconds of the traced stretch over the step programs that
ran in it. The programs are counted from the device plane's events: every
instruction of the step's entry computation runs once a program, so the
median of their event counts (scopes.programs_run). Unlike `step_ms.train`
(host clock) it holds neither the profiler's start and stop nor the fences."""

import scopes


@scopes.reader
def read(record, trace):
    n = scopes.programs_run(trace, record["hlo_dir"])
    return 1e3 * trace["busy_s"] / n if n else None
