"""Per-config step profile: phase wall breakdown + HLO cost analysis.

Replaces the hand-run PROFILE_CPU_r05 flow: for each requested bench
config this tool measures where a fused step's wall time actually goes
— by timing ABLATED step variants whose difference isolates one phase —
and attaches XLA's own HLO cost analysis (flop / byte counts) for the
compiled step, so a perf claim can be attributed to a phase instead of
guessed. Ablated variants change values (nop handlers, shrunk pools);
they exist only to difference wall times, never to verify anything —
trace identity is tools/step_goldens.py's job.

Rows (JSONL, one per config):

    {"config": ..., "n_seeds": ..., "n_steps": ...,
     "ns_per_seed_step": {"full": ..., "nop_handlers": ...,
                          "placement_scatter": ..., "pool_half": ...,
                          "emits_k1": ...},
     "attribution": {"handlers": ..., "pool+placement (half-pool "
                     "delta)": ..., "emit+rng lanes (k1 delta)": ...},
     "hlo": {"flops": ..., "bytes_accessed": ..., "transcendentals": ...}}

Usage:

    python tools/profile_step.py [config ...] > PROFILE_CPU_rNN.jsonl
    python tools/profile_step.py --pool-sweep   # ISSUE-13 pool-size axis
    make profile

The ``--pool-sweep`` axis measures the O(E)-vs-O(ready) claim behind
the readiness-partitioned pool (ISSUE 13): raftlog at pool sizes
512/2048/8192 with the client army on and off, timing the flat
lowering against the indexed one (both write lowerings) plus
nop-handler ablations, so "the flat pop/free-search scales with pool
width and the index removes it" is a measured attribution, not an
asserted one (evidence PROFILE_CPU_r07.jsonl).

Not part of tier-1 (pure measurement, no assertions).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

import _bootstrap  # noqa: F401  (sys.path for tools/)

import numpy as np

import jax
from jax import lax

from madsim_tpu.engine import (
    Build,
    EngineConfig,
    LatencySpec,
    make_init,
    make_step,
)
from madsim_tpu.models import BENCH_SPECS

DEFAULT_CONFIGS = ("raftlog", "kvchaos", "raft")
N_SEEDS = 4096
N_STEPS = 200

# the pool-size sweep axis (ISSUE 13): (pool_size, n_seeds) — seeds
# shrink as pools grow so the flat O(E) cells stay within budget
POOL_SWEEP = ((512, 512), (2048, 256), (8192, 128))
POOL_SWEEP_STEPS = 200


def _nop_handler(ctx):
    return ctx.state, ctx.emits().build()


def _time_variant(wl, cfg, n_seeds, n_steps, **mk) -> float:
    """Best-of-3 wall of a jitted n_steps scan, ns per seed-step."""
    build = Build(**mk)
    step = jax.vmap(make_step(wl, cfg, build))

    def run(st):
        def body(s, _):
            return step(s), None

        final, _ = lax.scan(body, st, None, length=n_steps)
        return final

    r = jax.jit(run)
    st = make_init(wl, cfg, build)(np.arange(n_seeds, dtype=np.uint64))
    jax.block_until_ready(r(st))  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()  # lint: allow(wall-clock)
        jax.block_until_ready(r(st))
        best = min(best, time.perf_counter() - t0)  # lint: allow(wall-clock)
    return best / (n_seeds * n_steps) * 1e9


def _hlo_cost(wl, cfg) -> dict:
    """XLA's cost analysis of ONE vmapped step (the scan body)."""
    step = jax.vmap(make_step(wl, cfg))
    st = make_init(wl, cfg)(np.arange(N_SEEDS, dtype=np.uint64))
    try:
        cost = jax.jit(step).lower(st).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        }
    except Exception as exc:  # cost analysis is best-effort per backend
        return {"error": repr(exc)}


def profile_config(name: str, n_seeds: int = N_SEEDS, n_steps: int = N_STEPS) -> dict:
    factory, cfg_kwargs, _s, _n = BENCH_SPECS[name]
    wl, cfg = factory(), EngineConfig(**cfg_kwargs)
    wl_nop = dataclasses.replace(
        wl, handlers=tuple(_nop_handler for _ in wl.handlers),
        handler_names=None,
    )
    cfg_half = dataclasses.replace(
        cfg, pool_size=max(wl.n_nodes + 1, cfg.pool_size // 2)
    )
    wl_k1 = dataclasses.replace(
        wl_nop, max_emits=1, payload_words=0, handler_names=None
    )

    ns = {
        "full": _time_variant(wl, cfg, n_seeds, n_steps),
        "nop_handlers": _time_variant(wl_nop, cfg, n_seeds, n_steps),
        "placement_scatter": _time_variant(
            wl, cfg, n_seeds, n_steps, placement="scatter"
        ),
        "pool_half": _time_variant(wl_nop, cfg_half, n_seeds, n_steps),
        "emits_k1": _time_variant(wl_k1, cfg, n_seeds, n_steps),
    }
    row = {
        "config": name,
        "platform": jax.devices()[0].platform,
        "n_seeds": n_seeds,
        "n_steps": n_steps,
        "ns_per_seed_step": {k: round(v, 1) for k, v in ns.items()},
        "attribution": {
            "handlers": round(ns["full"] - ns["nop_handlers"], 1),
            "pool+placement (half-pool delta)": round(
                ns["nop_handlers"] - ns["pool_half"], 1
            ),
            "emit+rng lanes (k1 delta)": round(
                ns["nop_handlers"] - ns["emits_k1"], 1
            ),
        },
        "hlo": _hlo_cost(wl, cfg),
    }
    return row


def _time_pool_variant(wl, cfg, rows, slots, lat, n_seeds, n_steps, **mk) -> float:
    """Best-of-2 wall of a jitted plan-seeded scan, ns per seed-step."""
    build = Build(layout="scatter", plan_slots=slots, latency=lat, **mk)
    step = jax.vmap(make_step(wl, cfg, build))

    def run(st):
        final, _ = lax.scan(
            lambda s, _: (step(s), None), st, None, length=n_steps
        )
        return final

    r = jax.jit(run)
    init = make_init(wl, cfg, build)
    seeds = np.arange(n_seeds, dtype=np.uint64)
    st = init(seeds, rows) if rows is not None else init(seeds)
    jax.block_until_ready(r(st))  # compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()  # lint: allow(wall-clock)
        jax.block_until_ready(r(st))
        best = min(best, time.perf_counter() - t0)  # lint: allow(wall-clock)
    return best / (n_seeds * n_steps) * 1e9


def profile_pool_sweep() -> list:
    """The ISSUE-13 pool-size axis: raftlog x {512, 2048, 8192} x
    {army on, off}, flat vs indexed (both write lowerings) vs
    nop-handler ablations — pop/free-search wall attributed by
    differencing, exactly the profile methodology above."""
    from madsim_tpu.chaos import CrashStorm, FaultPlan
    from madsim_tpu.models import make_raftlog
    from madsim_tpu.models import raftlog as rl_mod

    out = []
    for pool, n_seeds in POOL_SWEEP:
        for army in (True, False):
            wl = make_raftlog(record=True, army=army)
            wl_nop = dataclasses.replace(
                wl, handlers=tuple(_nop_handler for _ in wl.handlers),
                handler_names=None,
            )
            cfg = EngineConfig(pool_size=pool, loss_p=0.02,
                               clog_backoff_max_ns=2_000_000_000)
            if army:
                n_ops = max(pool // 2 - 64, 64)
                plan = FaultPlan((
                    rl_mod.client_army(
                        n_ops=n_ops, t_min_ns=5_000_000,
                        t_max_ns=3_000_000_000,
                    ),
                    CrashStorm(targets=tuple(range(5)), n=1,
                               t_min_ns=50_000_000, t_max_ns=200_000_000,
                               down_min_ns=20_000_000,
                               down_max_ns=80_000_000),
                ))
                lat = LatencySpec(ops=n_ops, phases=3)
                slots = plan.slots
                rows = plan.compile_batch(
                    np.arange(n_seeds, dtype=np.uint64), wl=wl
                )
            else:
                n_ops, lat, slots, rows = 0, None, 0, None

            def t(w, **mk):
                return _time_pool_variant(
                    w, cfg, rows, slots, lat, n_seeds, POOL_SWEEP_STEPS,
                    **mk,
                )

            ns = {
                "flat": t(wl, pool_index=False),
                "indexed": t(wl, pool_index=True, placement="scatter"),
                "indexed_rank_chains": t(wl, pool_index=True,
                                         placement="rank"),
                "flat_nop": t(wl_nop, pool_index=False),
                "indexed_nop": t(wl_nop, pool_index=True,
                                 placement="scatter"),
            }
            out.append({
                "config": "raftlog/pool-sweep",
                "platform": jax.devices()[0].platform,
                "pool_size": pool,
                "army_ops": n_ops,
                "n_seeds": n_seeds,
                "n_steps": POOL_SWEEP_STEPS,
                "ns_per_seed_step": {k: round(v, 1) for k, v in ns.items()},
                "attribution": {
                    "handlers": round(ns["flat"] - ns["flat_nop"], 1),
                    "pop+placement (index delta)": round(
                        ns["flat"] - ns["indexed"], 1
                    ),
                    "pop argmin + free search (nop index delta)": round(
                        ns["flat_nop"] - ns["indexed_nop"], 1
                    ),
                },
                "speedup_indexed": round(ns["flat"] / ns["indexed"], 2),
            })
            print(json.dumps(out[-1]), flush=True)
    return out


def main() -> None:
    args = [a for a in sys.argv[1:] if a != "--pool-sweep"]
    sweep = "--pool-sweep" in sys.argv[1:]
    names = args or ([] if sweep else list(DEFAULT_CONFIGS))
    for name in names:
        if name not in BENCH_SPECS:
            raise SystemExit(f"unknown config {name!r} (BENCH_SPECS)")
        row = profile_config(name)
        print(json.dumps(row), flush=True)
    if sweep:
        profile_pool_sweep()


if __name__ == "__main__":
    main()
