"""lockstep_eff.sweep: the share of the runner's paid row-steps (rows x
loop iterations, each compaction phase at its size) in which the row
had not yet halted, over the window's batches: the program's
``row_steps_live`` / ``row_steps_paid`` counters on its
``madsim.search`` spans (program counter, read by program_trace)."""

import program_trace


def read(rec):
    if rec["kind"] != "sweep":
        return None
    return program_trace.efficiency(rec, "madsim.search")
