"""Engine determinism verification helpers.

The batched analog of the reference's replay determinism checker
(``check_determinism``, runtime/mod.rs:165-190): run the same seeds
twice (or on two backends) and compare the uint64 trace hashes — any
divergence names the first differing seed. The strongest form is the
C++ oracle compare in engine/oracle.py; this module is the quick
self-check usable on any workload without an oracle implementation.
"""

from __future__ import annotations

import numpy as np

import jax

from ..runtime.rand import DeterminismError
from .core import (
    Build,
    EngineConfig,
    Workload,
    make_init,
    make_run,
    time32_eligible,
)

__all__ = [
    "HISTORY_FIELDS",
    "check_determinism",
    "check_layouts",
    "compare_traces",
]


# operation-history buffers (engine/core.py SimState): deliberately NOT
# folded into the trace hash (the C++ oracle mirrors the hash and knows
# nothing of histories), so determinism checks compare them directly
HISTORY_FIELDS = ("hist_count", "hist_drop", "hist_word", "hist_t")


def compare_traces(a, b, what: str = "run", history: bool = True) -> None:
    """Raise DeterminismError naming the first seed whose traces differ.

    With ``history=True`` (default) the operation-history buffers are
    compared too, when both states carry them — history columns are
    outside the trace hash, so a divergence there would otherwise be
    invisible to this check.
    """
    ta, tb = np.asarray(a.trace), np.asarray(b.trace)
    if ta.shape != tb.shape:
        raise DeterminismError(
            f"{what}: batch shapes differ ({ta.shape} vs {tb.shape})"
        )
    diff = np.nonzero(ta != tb)[0]
    if diff.size:
        s = int(diff[0])
        raise DeterminismError(
            f"non-determinism detected in {what}: seed index {s} "
            f"(seed {int(np.asarray(a.seed)[s])}) produced trace "
            f"{int(ta[s]):#x} vs {int(tb[s]):#x}"
        )
    if not history:
        return
    for field in HISTORY_FIELDS:
        da, db = getattr(a, field, None), getattr(b, field, None)
        if da is None or db is None:
            continue  # compacted results without banked history columns
        da, db = np.asarray(da), np.asarray(db)
        if da.shape != db.shape:
            raise DeterminismError(
                f"{what}: history field {field!r} shapes differ "
                f"({da.shape} vs {db.shape}) — runs used different "
                f"HistorySpec capacities"
            )
        if not np.array_equal(da, db):
            s = int(
                np.nonzero((da != db).reshape(da.shape[0], -1).any(axis=1))[0][0]
            )
            raise DeterminismError(
                f"non-determinism detected in {what}: history field "
                f"{field!r} diverged at seed index {s} "
                f"(seed {int(np.asarray(a.seed)[s])})"
            )


def check_determinism(
    wl: Workload, cfg: EngineConfig, seeds, n_steps: int
) -> None:
    """Run the workload twice over ``seeds``; raise on any divergence.

    Catches hidden nondeterminism in handlers (e.g. float ops that
    compile differently between runs) the way the reference's two-run
    RNG-log compare catches nondeterministic user code.
    """
    seeds = np.asarray(seeds, np.uint64)
    init = make_init(wl, cfg)
    run = jax.jit(make_run(wl, cfg, n_steps))
    a = run(init(seeds))
    b = run(init(seeds))
    compare_traces(a, b, what=f"{wl.name} x2")


def check_layouts(
    wl: Workload, cfg: EngineConfig, seeds, n_steps: int
) -> None:
    """Run the workload through BOTH step lowerings (dense and scatter,
    see Build's ``layout``) and raise on any trace divergence.

    The library form of the cross-backend determinism check
    (examples/cross_backend_check.py runs it across real silicon): the
    two lowerings are the same program in different clothes, so any
    difference is an engine bug, typically an out-of-range index whose
    gather/scatter semantics diverge from the dense masks.
    """
    seeds = np.asarray(seeds, np.uint64)
    # pool_index pinned OFF: the dense layout has no tile index, and
    # this check's subject is the dense/scatter duality — the indexed
    # lowering has its own on/off identity pins
    # (tests/test_pool_index.py, lint-soak cert 1c). The int32 offset
    # representation is a third value-identical lowering (``time32``);
    # where eligible it is crossed with both layouts.
    variants = [
        Build(layout=layout, time32=t32, pool_index=False)
        for t32 in ((False, True) if time32_eligible(wl, cfg) else (False,))
        for layout in ("dense", "scatter")
    ]
    runs = {}
    for build in variants:
        runs[(build.layout, build.time32)] = jax.jit(
            make_run(wl, cfg, n_steps, build)
        )(make_init(wl, cfg, build)(seeds))
    base_key = ("dense", False)
    base = runs[base_key]
    for key, other in runs.items():
        if key == base_key:
            continue
        what = f"{wl.name} {base_key}-vs-{key}"
        compare_traces(base, other, what=what)
        # the trace doesn't see everything (dropped-on-overflow events,
        # a mis-masked state write after the last fold): compare the
        # same field set the cross-backend artifact checks, plus the
        # node state. ev_time is excluded: representations differ by
        # design (absolute int64 vs rebased int32 offsets)
        for field in ("now", "halted", "halt_time", "msg_count", "overflow",
                      "node_state", "ev_valid", "hist_count", "hist_drop",
                      "hist_word", "hist_t"):
            da = np.asarray(getattr(base, field))
            sa = np.asarray(getattr(other, field))
            if not np.array_equal(da, sa):
                seed_idx = np.nonzero(
                    (da != sa).reshape(da.shape[0], -1).any(axis=1)
                )[0][0]
                raise DeterminismError(
                    f"{what}: field {field!r} diverged "
                    f"at seed index {int(seed_idx)} "
                    f"(seed {int(seeds[seed_idx])})"
                )
