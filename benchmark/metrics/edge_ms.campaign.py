"""edge_ms.campaign: milliseconds per campaign at the campaign driver's
host edges: the program's ``madsim.explore.start`` span (session
construction through start) plus ``madsim.explore.materialize`` (the
corpus and violations turned into host objects for the report)
(program spans, read by program_trace)."""

import program_trace


def read(rec):
    if rec["kind"] != "campaign":
        return None
    parts = [program_trace.per_unit(rec, name, "bench.campaign")
             for name in ("madsim.explore.start",
                          "madsim.explore.materialize")]
    if None in parts:
        return None
    return sum(parts)
