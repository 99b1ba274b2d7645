"""Plain compilation of the configurations' fault plans and campaign seeds.

Pure Python integers: threefry-2x32 (20 rounds) and the (seed,
plan-slot) draw stream, as the engine's published semantics define them.
Each spec type is ``plans/<type>.py``, with ``rows(spec, seed, slot)``
(its engine-kind rows, drawn from plan slots from ``slot`` on) and
``slots(spec)`` (how many slots it draws from). Imports nothing of
madsim_tpu.
"""

from __future__ import annotations

from harness import BENCH
from reference import named

M32 = 0xFFFFFFFF
_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = 0x1BD11BDA

# the engine's draw lanes (engine/rng.py PURPOSE_LANES), copied
PURPOSE_PLAN = 2654404608
PURPOSE_EXPLORE = 2654470144
KIND_KILL, KIND_RESTART, KIND_NOP = 0, 1, 7


def threefry2x32(k0: int, k1: int, x0: int, x1: int) -> tuple[int, int]:
    ks = (k0, k1, (k0 ^ k1 ^ _PARITY) & M32)
    x0 = (x0 + ks[0]) & M32
    x1 = (x1 + ks[1]) & M32
    for chunk in range(5):
        rots = _ROT[:4] if chunk % 2 == 0 else _ROT[4:]
        for r in rots:
            x0 = (x0 + x1) & M32
            x1 = ((x1 << r) | (x1 >> (32 - r))) & M32
            x1 ^= x0
        x0 = (x0 + ks[(chunk + 1) % 3]) & M32
        x1 = (x1 + ks[(chunk + 2) % 3] + chunk + 1) & M32
    return x0, x1


class Stream:
    """Draw j of plan slot ``slot`` for one seed (chaos/plan.py _Stream)."""

    def __init__(self, seed: int, slot: int):
        self.k0, self.k1 = seed & M32, (seed >> 32) & M32
        self.x1 = (PURPOSE_PLAN + slot) & M32

    def bits(self, j: int) -> int:
        return threefry2x32(self.k0, self.k1, j & M32, self.x1)[0]

    def uniform(self, lo: int, hi: int, j: int) -> int:
        return lo + self.bits(j) % max(hi - lo, 1)

    def pick(self, options, j: int) -> int:
        return options[self.bits(j) % len(options)]


def compile_plan(plan: dict, seed: int, bench: str = BENCH) -> dict:
    """One seed's plan rows: {time, kind, node, a0, a1, valid} lists.
    Spec i draws from plan slots [offset_i, offset_i + slots_i). A type
    with no file under ``plans/`` is a BenchError."""
    rows, off = [], 0
    for spec in plan["specs"]:
        mod = named.load("plans", spec["type"], bench)
        rows += mod.rows(spec, seed, off)
        off += mod.slots(spec)
    cols = ("time", "kind", "node", "a0", "a1", "valid")
    return {c: [r[i] for r in rows] for i, c in enumerate(cols)}


def literal_rows(events, enabled) -> dict:
    """Rows of a literal plan given as (t, kind, a0, a1, node) events."""
    return {"time": [e[0] for e in events], "kind": [e[1] for e in events],
            "a0": [e[2] for e in events], "a1": [e[3] for e in events],
            "node": [e[4] for e in events], "valid": list(enabled)}


def campaign_child_seed(root_seed: int, generation: int, j: int) -> int:
    """Seed of batch slot j of a campaign generation (explore's
    (root, generation, PURPOSE_EXPLORE + slot) derivation)."""
    a, b = threefry2x32(root_seed & M32, (root_seed >> 32) & M32,
                        generation & M32, (PURPOSE_EXPLORE + j) & M32)
    return a | (b << 32)
