"""fetch_ms.sweep: milliseconds per batch in the program's
``madsim.search.fetch`` span: the device->host copies of the results and
their scatter into seed order (program span, read from the run's trace
by program_trace)."""

import program_trace


def read(rec):
    if rec["kind"] != "sweep":
        return None
    return program_trace.per_unit(rec, "madsim.search.fetch",
                                  "bench.batch")
