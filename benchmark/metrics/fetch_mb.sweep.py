"""fetch_mb.sweep: megabytes copied device->host per batch, the
program's ``SearchReport.d2h_bytes`` counter as the ``madsim.search``
span carries it (program counter, read by program_trace)."""

import program_trace


def read(rec):
    if rec["kind"] != "sweep":
        return None
    total = program_trace.counter_sum(rec, "madsim.search", "d2h_bytes")
    if total is None:
        return None
    return total / 1e6 / len(rec["trace"]["spans"]["bench.batch"])
