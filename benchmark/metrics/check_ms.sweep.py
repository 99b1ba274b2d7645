"""check_ms.sweep: milliseconds per batch in the program's
``madsim.search.check`` span: the invariant, screen verdicts, flagged-
history gather and the report (program span, read from the run's trace
by program_trace)."""

import program_trace


def read(rec):
    if rec["kind"] != "sweep":
        return None
    return program_trace.per_unit(rec, "madsim.search.check",
                                  "bench.batch")
