"""Where a run's set-up went, by the program's own spans and its compile
counter, for the readers of `setup_init_s.train`, `setup_trace_s.train`,
`setup_compile_s.train`, `setup_backend_compiles.train`,
`setup_outside_compile_s.train` and `setup_attributed_share.train`.

The program (singa_tpu/introspect.py, `setup_report`) sums its spans, each
net of the spans nested in it, and counts what jax compiled or read from
its cache by the span it fell under. This file only picks and adds; it is
read after the window, in the benchmark's process, from the registry as it
stands. Nothing of the run is repeated for it.
"""

import json
import os

NAMES = ("setup_init_s", "setup_trace_s", "setup_compile_s",
         "setup_backend_compiles", "setup_outside_compile_s",
         "setup_attributed_share")

INIT = ("model.create", "model.init")
STAGING = ("model.build", "trace", "lower")
COMPILING = ("compile", "introspect.warm_load", "introspect.first_dispatch")


def split(report, setup_s):
    """The six values of one `setup_report`, or None where it holds no span
    and no compile (observation off, or a program without them).

    `opt.setup` counts as initialisation where `Model.compile` ran it; the
    one `model.build` runs again walks state that is there and stays out.
    """
    spans, compiles = report.get("spans") or {}, report.get("compiles") or {}
    if not spans and not compiles:
        return None
    leaf_s = lambda names: sum(spans.get(n, {}).get("seconds", 0.0)
                               for n in names)
    init = leaf_s(INIT)
    for path, v in (report.get("paths") or {}).items():
        names = path.split("/")
        if names[-1] == "opt.setup" and "model.build" not in names:
            init += v["seconds"]
    staging, compiling = leaf_s(STAGING), leaf_s(COMPILING)
    rows = [(where, source, v) for where, by in compiles.items()
            for source, v in by.items()]
    return {
        "setup_init_s": init,
        "setup_trace_s": staging,
        "setup_compile_s": compiling,
        "setup_backend_compiles": sum(
            v["count"] for _w, source, v in rows if source == "backend"),
        "setup_outside_compile_s": sum(
            v["seconds"] for where, _s, v in rows if where == "none"),
        "setup_attributed_share":
            100.0 * (init + staging + compiling) / setup_s
            if setup_s else None,
    }


def parts(record):
    """{name: value} for NAMES, read once a record (the readers share it),
    and left with the whole report in `<cell's out dir>/setup_parts.json`
    for the operator. None where the program has no `setup_report` (a
    checkout from before it) or the report holds nothing."""
    if "setup_parts" not in record:
        from singa_tpu import introspect
        report_of = getattr(introspect, "setup_report", None)
        report = report_of() if report_of else {}
        setup_s = record["values"].get("setup_s")
        values = split(report, setup_s)
        record["setup_parts"] = values
        if values is not None:
            out = os.path.join(os.path.dirname(record["hlo_dir"]),
                               "setup_parts.json")
            os.makedirs(os.path.dirname(out), exist_ok=True)
            with open(out, "w", encoding="utf-8") as f:
                json.dump({"setup_s": setup_s, "values": values,
                           "report": report}, f, indent=1)
    return record["setup_parts"]


def value(record, name):
    values = parts(record)
    return None if values is None else values[name]
