"""The comparison that decides ``correct``: sampled schedules against the
plain reference (``reference/``), which imports nothing of the program.

A schedule is its inputs (seed, fault-plan rows) and the answers the
timed path gave for it: trace hash, final clock, halt flag, overflow
flag and, where the configuration names history checks, its verdict.
The number compared is how many sampled schedules disagree with the
reference in any answer; its limit is 0 (an exact comparison).

``control_quantum`` > 1 puts the control in the program's place: the
reference computed with event times held at that resolution.
"""

from __future__ import annotations

import numpy as np

from harness import BENCH
from reference.history import clean
from reference.oracle import Reference


def reference_answer(ref: Reference, config: dict, seed: int, rows) -> dict:
    r = ref.run(seed, rows)
    checks = config["reference"].get("checks", [])
    over = r["overflow"] > 0 or r["hist_drop"] > 0
    return {"trace": r["trace"], "now": r["now"], "halted": r["halted"],
            "halt_t": r["halt_time"] if r["halted"] else 0,
            "overflowed": over,
            "violating": (not over and bool(checks)
                          and not clean(r["hist"], checks, ref.bench))}


def compare(config: dict, schedules: list, control_quantum: int = 1,
            bench: str = BENCH):
    """(mismatched, details) over ``schedules``: dicts with ``seed``,
    ``rows`` (plan rows or None) and the program's answers under the
    keys of ``config['compared']`` plus ``overflowed``. ``bench`` is the
    benchmark directory whose ``reference/`` is compared with."""
    ref = Reference(config, bench=bench)
    ctl = Reference(config, time_quantum=control_quantum, bench=bench) \
        if control_quantum > 1 else None
    keys = list(config["compared"]) + ["overflowed"]
    bad, details = 0, []
    for s in schedules:
        exp = reference_answer(ref, config, s["seed"], s["rows"])
        got = (reference_answer(ctl, config, s["seed"], s["rows"])
               if ctl is not None else s)
        diff = [k for k in keys if k in got and got[k] != exp[k]]
        if diff:
            bad += 1
            if len(details) < 5:
                details.append({"seed": s["seed"], "differs": diff,
                                "program": {k: got[k] for k in diff},
                                "reference": {k: exp[k] for k in diff}})
    return bad, details


def pick(rng: np.random.Generator, pool: list, n: int, longest_key=None):
    """n entries of ``pool`` drawn from ``rng``, always with the one of
    largest ``longest_key`` among them."""
    if not pool:
        return []
    first = []
    if longest_key is not None:
        i = max(range(len(pool)), key=lambda j: pool[j][longest_key])
        first = [pool[i]]
        pool = pool[:i] + pool[i + 1:]
    take = min(max(n - len(first), 0), len(pool))
    idx = rng.choice(len(pool), take, replace=False) if take else []
    return first + [pool[int(j)] for j in sorted(idx)]
