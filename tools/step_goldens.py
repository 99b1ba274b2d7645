"""Capture golden full-state digests of the fused step function.

The PR-8 step refactor (rank-matched placement, per-dispatch batched
RNG, hot/cold state split) promises BIT-IDENTICAL values to the
pre-refactor engine. This tool pins that promise: it runs every
recorded model with the full observability surface on (history +
metrics + timeline + coverage/hit-counts + latency, army plans where
the model has a client surface) and digests EVERY SimState field —
including the event pool, whose stale-but-deterministic values any
placement rewrite is most likely to disturb.

The digests in ``tests/_step_goldens.py`` were generated by THIS tool
running the PRE-refactor engine (PR 7 tip); ``tests/test_stepident.py``
recomputes them on every run, so any value drift in the step function
— across dense/scatter layouts and the compacted runner — fails
loudly with the scenario name.

Regenerate (only when a value change is INTENDED and understood):

    python tools/step_goldens.py > tests/_step_goldens.py
"""

from __future__ import annotations

import hashlib
import sys

import _bootstrap  # noqa: F401  (sys.path for tools/)

import numpy as np

import jax

from madsim_tpu.chaos import ClientArmy, CrashStorm, FaultPlan, GrayFailure
from madsim_tpu.engine import (
    Build,
    EngineConfig,
    LatencySpec,
    make_init,
    make_run,
)
from madsim_tpu.engine.compact import make_run_compacted
from madsim_tpu.models import make_kvchaos, make_paxos, make_raft, make_raftlog
from madsim_tpu.models import kvchaos as kv_mod
from madsim_tpu.models import raftlog as rl_mod

N_SEEDS = 32
# enough steps that every scenario dispatches chaos + army + protocol
# traffic; small enough that a scenario digests in seconds on CPU
N_STEPS = 240

# every scenario runs with the full observability surface on: the
# refactor's cold-bank columns are exactly these taps' columns
OBS = dict(cov_words=8, metrics=True, timeline_cap=48, cov_hitcount=True)


def _army_plan(army_fn, n_ops, servers):
    """An army + chaos plan: client load, a crash storm and a gray
    failure so the extended-kind dispatch paths are all exercised."""
    return FaultPlan(
        (
            army_fn(n_ops=n_ops, t_min_ns=5_000_000, t_max_ns=400_000_000),
            CrashStorm(targets=servers, n=1,
                       t_min_ns=50_000_000, t_max_ns=200_000_000,
                       down_min_ns=20_000_000, down_max_ns=80_000_000),
            GrayFailure(targets=servers, n_links=1, mult_min=4, mult_max=8,
                        t_min_ns=30_000_000, t_max_ns=150_000_000,
                        dur_min_ns=50_000_000, dur_max_ns=150_000_000),
        )
    )


def scenarios():
    """name -> (workload, config, plan|None, latency)."""
    out = {}

    wl = make_raftlog(record=True, army=True)
    plan = _army_plan(rl_mod.client_army, 10, tuple(range(5)))
    out["raftlog/army-obs"] = (
        wl,
        EngineConfig(pool_size=96, loss_p=0.02,
                     clog_backoff_max_ns=2_000_000_000),
        plan,
        LatencySpec(ops=10, phases=3, phase_ns=1 << 27),
    )

    wl = make_raftlog(record=True, durable=True)
    out["raftlog/durable-obs"] = (
        wl,
        EngineConfig(pool_size=64, loss_p=0.02,
                     clog_backoff_max_ns=2_000_000_000),
        None,
        LatencySpec(ops=4),
    )

    wl = make_kvchaos(record=True, army=True, army_probes=2)
    plan = _army_plan(kv_mod.client_army, 10, tuple(range(5)))
    out["kvchaos/army-obs"] = (
        wl,
        EngineConfig(pool_size=72, loss_p=0.02,
                     clog_backoff_max_ns=2_000_000_000),
        plan,
        LatencySpec(ops=10, phases=3, phase_ns=1 << 27),
    )

    wl = make_raft(record=True)
    out["raft/record-obs"] = (
        wl,
        EngineConfig(pool_size=40, loss_p=0.02,
                     clog_backoff_max_ns=2_000_000_000),
        None,
        LatencySpec(ops=4),
    )

    wl = make_paxos(record=True)
    out["paxos/record-obs"] = (
        wl,
        EngineConfig(pool_size=48, loss_p=0.02,
                     clog_backoff_max_ns=2_000_000_000),
        None,
        LatencySpec(ops=4),
    )
    return out


def digest_state(st) -> str:
    """sha256 over every SimState field (or banked-result namespace),
    field-name-tagged so a silent shape change also shows up.

    The readiness-index tile summaries (POOL_INDEX_STATE_FIELDS) are
    excluded: they are derived by construction (a pure function of the
    pool columns, rebuilt on checkpoint restore, outside the
    checkpoint format), their stale empty-tile minima are
    intentionally meaningless, and excluding them keeps every digest
    captured from the PRE-refactor engine valid — which is exactly
    what lets the indexed-pool builds be pinned against the SAME
    goldens (tests/test_stepident.py: TestStepIdentityLean
    .test_raftlog_army_pool_indexed and the TestStepIdentityMatrix
    test_pool_indexed* parametrizations).

    The causal-provenance columns (CAUSAL_STATE_FIELDS, ISSUE 19) are
    excluded on the same grounds: zero-size derived state for every
    goldened scenario (all run causal=False), so excluding the field
    NAMES keeps the pre-causal digests valid; the causal=False value
    identity and the causal=True fold are both pinned by
    tests/test_causal.py.

    The client-retry columns (RETRY_STATE_FIELDS, ISSUE 20) follow the
    same rule — zero-size with no RetryPolicy, which is every goldened
    scenario. The metrics row grew two retry slots (MET_RETRY,
    MET_RETRY_GIVEUP) at the END of the vector; digesting only the
    pre-retry slots ``met[..., :MET_RETRY]`` keeps the pre-retry
    digests valid while still pinning every pre-existing counter —
    the new slots stay zero off-policy, pinned by tests/test_retry.py."""
    import dataclasses

    from madsim_tpu.engine import (
        CAUSAL_STATE_FIELDS,
        MET_RETRY,
        POOL_INDEX_STATE_FIELDS,
        RETRY_STATE_FIELDS,
    )

    skip = (set(POOL_INDEX_STATE_FIELDS) | set(CAUSAL_STATE_FIELDS)
            | set(RETRY_STATE_FIELDS))
    h = hashlib.sha256()
    if dataclasses.is_dataclass(st):
        items = [
            (f.name, getattr(st, f.name))
            for f in dataclasses.fields(st)
            if f.name not in skip
        ]
    else:  # SimpleNamespace from make_run_compacted
        items = [
            kv for kv in sorted(vars(st).items()) if kv[0] not in skip
        ]
    for name, arr in items:
        a = np.asarray(arr)
        if name == "met" and a.ndim >= 1 and a.shape[-1] > MET_RETRY:
            a = a[..., :MET_RETRY]
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def run_scenario(
    name, wl, cfg, plan, lat, layout=None, compact=False, placement=None,
    pool_index=None,
) -> str:
    seeds = np.arange(N_SEEDS, dtype=np.uint64)
    build = Build(
        layout=layout, placement=placement, pool_index=pool_index,
        plan_slots=plan.slots if plan is not None else 0, latency=lat,
        **OBS,
    )
    init = make_init(wl, cfg, build)
    rows = plan.compile_batch(seeds, wl=wl) if plan is not None else None
    st0 = init(seeds, rows) if rows is not None else init(seeds)
    if compact:
        run = make_run_compacted(wl, cfg, N_STEPS, build, min_size=8)
        return digest_state(run(st0))
    run = jax.jit(make_run(wl, cfg, N_STEPS, build))
    return digest_state(jax.block_until_ready(run(st0)))


def main() -> None:
    lines = [
        '"""Golden step digests — GENERATED by tools/step_goldens.py.',
        "",
        "Captured from the PRE-refactor engine (PR 7 tip); see that tool's",
        'docstring before regenerating."""',
        "",
        "GOLDENS = {",
    ]
    for name, (wl, cfg, plan, lat) in scenarios().items():
        d_sc = run_scenario(name, wl, cfg, plan, lat, layout="scatter")
        d_de = run_scenario(name, wl, cfg, plan, lat, layout="dense")
        if d_sc != d_de:
            raise SystemExit(
                f"{name}: dense/scatter digests differ pre-capture "
                f"({d_de} vs {d_sc}) — the layouts are not value-identical"
            )
        d_co = run_scenario(name, wl, cfg, plan, lat, compact=True)
        lines.append(f'    "{name}": "{d_sc}",')
        lines.append(f'    "{name}/compact": "{d_co}",')
        print(f"# {name}: full={d_sc[:16]}… compact={d_co[:16]}…",
              file=sys.stderr)
    lines.append("}")
    print("\n".join(lines))


if __name__ == "__main__":
    main()
