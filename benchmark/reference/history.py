"""Plain per-schedule history checks, each found by name.

One history is a list of rows [op, key, arg, client, ok] in append
order. A check is ``checks/<name>.py`` with ``violated(hist, **args)``;
a configuration's ``reference.checks`` names each, as a name or as
``{"name": ..., "args": {...}}``.

The floor checks shared here: a successful read must return at least
the newest version whose write had completed before the read was
invoked: by any client (``stale_reads``) or by the reading client
(``read_your_writes``). The r-th read response of a (client, key) pairs
with its r-th read invoke; a response with no invoke at all is judged at
its own position, and one whose paired invoke comes after it constrains
nothing.
"""

from __future__ import annotations

from harness import BENCH
from reference import named

OP_WRITE, OP_READ = 1, 2
OK_PENDING, OK_OK = -1, 1


def _floor_violated(hist, own_writes_only: bool) -> bool:
    floors: dict = {}  # (client, key) -> floors sampled at read invokes
    taken: dict = {}   # (client, key) -> responses matched so far
    for pos, (op, key, arg, client, ok) in enumerate(hist):
        if op == OP_READ and ok == OK_PENDING:
            floors.setdefault((client, key), []).append(
                (pos, _floor(hist, pos, key, client, own_writes_only)))
        elif op == OP_READ and ok == OK_OK:
            r = taken.get((client, key), 0)
            taken[(client, key)] = r + 1
            inv = floors.get((client, key), [])
            if r < len(inv):
                floor = inv[r][1]
            elif not _later_invoke(hist, pos, client, key, r):
                floor = _floor(hist, pos, key, client, own_writes_only)
            else:
                continue
            if floor is not None and arg < floor:
                return True
    return False


def _floor(hist, pos, key, client, own_writes_only):
    best = None
    for op, k, arg, c, ok in hist[:pos]:
        if (op == OP_WRITE and ok == OK_OK and k == key
                and (not own_writes_only or c == client)):
            best = arg if best is None else max(best, arg)
    return best


def _later_invoke(hist, pos, client, key, rank):
    """Whether the rank-th read invoke of (client, key) comes after pos."""
    n = 0
    for p, (op, k, _a, c, ok) in enumerate(hist):
        if op == OP_READ and ok == OK_PENDING and c == client and k == key:
            if n == rank:
                return p > pos
            n += 1
    return False


def clean(hist, checks, bench: str = BENCH) -> bool:
    """True when every named check passes on this history; BenchError
    for a check with no file."""
    found = [(named.load("checks", name, bench).violated, args)
             for name, args in map(named.entry, checks)]
    return not any(violated(hist, **args) for violated, args in found)
