"""lockstep_eff.campaign: the share of the generation programs' paid
row-steps (rows x step-loop iterations, summed over a mesh's shards) in
which the row had not yet halted, over the window's generations: the
program's counters on its ``madsim.explore.sync`` spans (program
counter, read by program_trace)."""

import program_trace


def read(rec):
    if rec["kind"] != "campaign":
        return None
    return program_trace.efficiency(rec, "madsim.explore.sync")
