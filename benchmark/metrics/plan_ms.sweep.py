"""plan_ms.sweep: milliseconds per batch in the program's
``madsim.search.plan`` span: ``plan.compile_batch`` and the plan-row
checks (program span, read from the run's trace by program_trace)."""

import program_trace


def read(rec):
    if rec["kind"] != "sweep":
        return None
    return program_trace.per_unit(rec, "madsim.search.plan",
                                  "bench.batch")
