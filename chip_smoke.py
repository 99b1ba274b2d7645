"""Bring-up smoke: the simulator's main path, once, on a TPU chip.

    python chip_smoke.py             # one chip: phases 1-3
    python chip_smoke.py --chips 4   # four chips: the seed-sharded path only

Phase 1 finds the device and refuses anything that is not a TPU: there
is no CPU fallback. Phase 2 runs two ``models.BENCH_SPECS`` entries at
bench width through ``engine.make_run_compacted`` (the bench's runner,
lowering auto-resolved for the backend) and checks a spread sample of
seeds bit for bit against the C++ oracle and against the same program
on the CPU backend of this process. Phase 3 runs a planted-bug campaign
through ``explore.run_device`` with device history screens, as users
run it, and replays the first violation single-seed on the host driver
(``engine.search_seeds``); the same campaign at a smaller size then
runs on the chip and on the CPU backend, and the two must agree entry
for entry (corpus, violations, coverage). The ``--chips 4`` path runs the seed-sharded
runner and the sharded campaign, each against its one-chip twin.

Every phase raises on a wrong result; the last stdout line, printed only
when all of them passed, is the one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``. Everything
runs in this one process, which holds the chip. The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` here.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
# before jax is imported: jax reads the variable once, at import
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(_HERE, ".jax_cache")
)
sys.path.insert(0, _HERE)

import numpy as np  # noqa: E402

import jax  # noqa: E402

from madsim_tpu import explore  # noqa: E402
from madsim_tpu.chaos import CrashStorm, FaultPlan  # noqa: E402
from madsim_tpu.check import device as dc  # noqa: E402
from madsim_tpu.engine import (  # noqa: E402
    Build,
    EngineConfig,
    make_init,
    make_run_compacted,
    search_seeds,
)
from madsim_tpu.engine.oracle import run_oracle  # noqa: E402
from madsim_tpu.models import BENCH_SPECS, make_kvchaos  # noqa: E402
from madsim_tpu import parallel  # noqa: E402

# result columns compared bit for bit between two runs of one seed
# ('step' is excluded: compaction stops counting for banked rows)
SAME_FIELDS = ("now", "halted", "halt_time", "trace", "overflow",
               "msg_count", "node_state")


def log(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


def mem(dev, key: str = "peak_bytes_in_use") -> int | None:
    stats = dev.memory_stats()  # None on backends that keep no stats
    return None if stats is None else int(stats[key])


def timed(fn, *args):
    t0 = time.perf_counter()  # lint: allow(wall-clock)
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0  # lint: allow(wall-clock)


# ------------------------------------------------------------ phase 1
def find_device() -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found {len(devs)} "
            f"{d.platform} device(s) ({d.device_kind})"
        )
    import importlib.metadata as md

    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    log("device", **info, jax=jax.__version__,
        libtpu=md.version("libtpu"),
        cache_dir=jax.config.jax_compilation_cache_dir)
    return info


# ------------------------------------------------------------ phase 2
def engine_phase(name: str, n_seeds: int, n_steps: int, n_sample: int,
                 layout: str | None = None, time32: bool | None = None):
    """One BENCH_SPECS entry at ``n_seeds`` through the compacted runner
    on the default device, then ``n_sample`` spread seeds checked against
    the C++ oracle and against the same program on the CPU backend.
    ``layout``/``time32`` None = what the default backend resolves."""
    factory, cfg_kw, _, _ = BENCH_SPECS[name]
    wl, cfg = factory(), EngineConfig(**cfg_kw)
    # resolved once, on the default backend: the CPU reference below
    # runs the same lowering
    build = Build(layout=layout, time32=time32).resolve(wl, cfg)
    dev = jax.devices()[0]
    init = jax.jit(make_init(wl, cfg, build))
    run = make_run_compacted(wl, cfg, n_steps, build,
                             min_size=min(2048, max(n_seeds // 4, 1)))
    seeds = np.arange(n_seeds, dtype=np.uint64)

    st, init_s = timed(init, seeds)
    t0 = time.perf_counter()  # lint: allow(wall-clock)
    prog = run.compute.lower(st).compile()
    compile_s = time.perf_counter() - t0  # lint: allow(wall-clock)
    packed, run_s = timed(prog, st)
    res = run.assemble(packed)
    halted, overflow = int(res.halted.sum()), int(res.overflow.sum())
    log(f"engine:{name}", seeds=n_seeds, max_steps=n_steps,
        layout=build.layout, time32=build.time32, compile_s=compile_s,
        init_s=init_s, run_s=run_s,
        sim_seconds=float(res.now.sum()) / 1e9, halted=halted,
        overflow=overflow, peak_bytes_in_use=mem(dev))
    if halted != n_seeds or overflow:
        raise AssertionError(
            f"{name}: {n_seeds - halted} seeds did not halt, "
            f"{overflow} events overflowed"
        )

    pick = np.unique(np.linspace(0, n_seeds - 1, n_sample).astype(np.int64))
    for i in pick:
        o = run_oracle(wl, cfg, int(seeds[i]), n_steps)
        got = (int(res.trace[i]), int(res.now[i]), bool(res.halted[i]))
        if got != (o.trace, o.now, o.halted):
            raise AssertionError(
                f"{name} seed {seeds[i]}: engine (trace, now, halted) "
                f"{got} != oracle {(o.trace, o.now, o.halted)}"
            )

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref_packed, cpu_s = timed(run.compute, init(seeds[pick]))
    if any(x.devices() != {cpu} for x in jax.tree.leaves(ref_packed)):
        raise AssertionError(f"{name}: the reference run left the CPU")
    ref = run.assemble(ref_packed)
    for f in SAME_FIELDS:
        if not np.array_equal(getattr(res, f)[pick], getattr(ref, f)):
            raise AssertionError(
                f"{name}: field {f!r} differs between {dev.platform} "
                f"and the CPU backend on the sampled seeds"
            )
    log(f"engine:{name}:check", sampled=len(pick), oracle="identical",
        cpu_backend="identical", cpu_wall_s=cpu_s)
    return res


# ------------------------------------------------------------ phase 3
def campaign_phase(batch: int, generations: int, max_steps: int = 4000,
                   mesh=None, replay: bool = True, label: str | None = None):
    """The kvchaos lost-write hunt (tools/campaign_bench.py cert 4's
    plan) as a device campaign with history screens; with ``replay``
    the first violation replays single-seed on the host driver."""
    wl = make_kvchaos(writes=10, record=True, bug=True, chaos=False)
    cfg = EngineConfig(pool_size=192, loss_p=0.05)
    plan = FaultPlan((
        CrashStorm(targets=(1, 2, 3, 4), n=2, t_min_ns=20_000_000,
                   t_max_ns=400_000_000, down_min_ns=50_000_000,
                   down_max_ns=250_000_000),
    ), name="kv-nemesis")
    screens = (dc.stale_reads(), dc.read_your_writes())
    label = label or ("campaign" if mesh is None else "campaign:sharded")
    t0 = time.perf_counter()  # lint: allow(wall-clock)
    rep = explore.run_device(
        wl, cfg, plan, invariant=None, history_check=screens,
        generations=generations, batch=batch, root_seed=7,
        max_steps=max_steps, cov_words=64, mesh=mesh,
        viol_cap=batch * generations,  # room for every seed to violate
    )
    wall_s = time.perf_counter() - t0  # lint: allow(wall-clock)
    log(label, batch=batch, generations=generations, sims=rep.sims,
        compile_s=rep.wall_compile_s, dispatch_s=rep.wall_dispatch_s,
        host_s=rep.wall_host_s, wall_s=wall_s, corpus=len(rep.corpus),
        coverage_bits=rep.coverage_bits, violations=len(rep.violations),
        fingerprint=fingerprint(campaign_key(rep)),
        peak_bytes_in_use=mem(jax.devices()[0]))
    if not rep.violations:
        raise AssertionError(f"{label}: the planted bug was not found")
    if not replay:
        return rep

    e = rep.violations[0]
    t0 = time.perf_counter()  # lint: allow(wall-clock)
    again = search_seeds(
        wl, cfg, None, n_seeds=1, seed_base=e.seed, plan=e.plan,
        max_steps=max_steps, require_halt=False,
        history_invariant=dc.screens_invariant(screens),
    )
    replay_s = time.perf_counter() - t0  # lint: allow(wall-clock)
    if int(again.traces[0]) != e.trace or bool(again.ok[0]):
        raise AssertionError(
            f"replay of seed {e.seed}: trace {int(again.traces[0]):#x} "
            f"ok={bool(again.ok[0])}, device found {e.trace:#x} violating"
        )
    log("campaign:replay", seed=int(e.seed), trace=f"{e.trace:#018x}",
        plan=e.plan.hash(), replay_s=replay_s, trace_identical=True)
    return rep


def campaign_cpu_check(batch: int, generations: int,
                       max_steps: int = 4000) -> None:
    """The campaign at a smaller size on the default device and again on
    the CPU backend of this process (which the tests pin equal to the
    host driver): corpus, violations and coverage must be identical."""
    rep = campaign_phase(batch, generations, max_steps, replay=False,
                         label="campaign:small")
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = campaign_phase(batch, generations, max_steps, replay=False,
                             label="campaign:small:cpu")
    if last_campaign_devices() != {cpu}:
        raise AssertionError("the reference campaign left the CPU")
    diff = key_diff(campaign_key(rep), campaign_key(ref))
    if diff:
        raise AssertionError(
            f"campaign on {jax.devices()[0].platform} differs from the CPU "
            f"backend: {diff}"
        )
    log("campaign:small:check", cpu_backend="identical",
        violations=len(rep.violations), corpus=len(rep.corpus))


def last_campaign_devices() -> set:
    """Devices the newest cached campaign program was compiled for."""
    uniform, _, _ = next(reversed(explore.device._GEN_CACHE.values()))
    return {d for exe in uniform._exes.values()
            for s in jax.tree.leaves(exe.input_shardings)
            for d in s.device_set}


def key_diff(a, b) -> str:
    """'' when two campaign keys are equal, else which parts differ."""
    if a == b:
        return ""
    va, vb = set(a[1]), set(b[1])
    return (f"corpus {'same' if a[0] == b[0] else 'differs'}, coverage "
            f"{'same' if a[2] == b[2] else 'differs'}, violations "
            f"{len(va - vb)} only in the first, {len(vb - va)} only in the "
            f"second")


def campaign_key(rep):
    """(corpus, violations, coverage map): what two equal campaigns share."""
    return (
        [(e.id, e.seed, e.trace, e.violating, e.plan.hash())
         for e in rep.corpus],
        [(e.seed, e.trace) for e in rep.violations],
        rep.cov_map.tobytes(),
    )


def fingerprint(key) -> dict:
    """Short hashes of a campaign key, comparable across runs and hosts."""
    corpus, viol, cov = key
    return {name: hashlib.sha256(raw).hexdigest()[:16] for name, raw in (
        ("corpus", repr(corpus).encode()),
        ("violations", repr(viol).encode()),
        ("coverage", cov))}


# ------------------------------------------------------ --chips 4
def sharded_phase(n_seeds: int, n_steps: int = 600, batch: int = 8192,
                  generations: int = 2, max_steps: int = 4000) -> None:
    """raft through ``parallel.shard_run_compacted`` over every chip vs
    the same seeds on one chip, then the sharded campaign vs the
    unsharded one: both bit-identical. The campaigns are smaller than
    phase 3's: each compiles for minutes, and four chips bill four
    times over."""
    devs = jax.devices()
    mesh = parallel.make_mesh()
    factory, cfg_kw, _, _ = BENCH_SPECS["raft"]
    wl, cfg = factory(), EngineConfig(**cfg_kw)
    seeds = np.arange(n_seeds, dtype=np.uint64)
    init = make_init(wl, cfg)
    min_size = min(2048, max(n_seeds // len(devs) // 4, 1))

    # each device builds its own shard: built on one chip and then
    # split, the state would skew that chip's bytes in use
    st = parallel.shard_over_seeds(init, mesh)(seeds)
    rows = {s.device.id: int(s.data.shape[0])
            for s in st.seed.addressable_shards}
    run = parallel.shard_run_compacted(wl, cfg, n_steps, mesh,
                                       min_size=min_size)
    t0 = time.perf_counter()  # lint: allow(wall-clock)
    prog = jax.jit(run.compute).lower(st).compile()
    compile_s = time.perf_counter() - t0  # lint: allow(wall-clock)
    in_use = [mem(d, "bytes_in_use") for d in devs]
    banked, run_s = timed(prog, st)
    sharded = run.assemble(banked)
    log("sharded:raft", seeds=n_seeds, devices=len(devs),
        shard_rows=[rows[d.id] for d in devs], bytes_in_use=in_use,
        peak_bytes_in_use=[mem(d) for d in devs],
        compile_s=compile_s, run_s=run_s,
        halted=int(sharded.halted.sum()), overflow=int(sharded.overflow.sum()))
    del st, banked
    if len(set(rows.values())) != 1 or len(rows) != len(devs):
        raise AssertionError(f"uneven seed shards: {rows}")
    # checked last, so a skewed placement still reports everything else
    skewed = None not in in_use and max(in_use) > 2 * min(in_use)
    if not sharded.halted.all() or sharded.overflow.any():
        raise AssertionError("sharded raft: unhalted seeds or overflow")

    one = make_run_compacted(wl, cfg, n_steps,
                             min_size=min(2048, max(n_seeds // 4, 1)))
    base, one_s = timed(one.compute, jax.jit(init)(seeds))
    base = one.assemble(base)
    for f in SAME_FIELDS:
        if not np.array_equal(getattr(sharded, f), getattr(base, f)):
            raise AssertionError(f"sharded raft: field {f!r} differs "
                                 f"from the one-chip run")
    log("sharded:raft:check", one_chip_run_s=one_s, identical=True)

    rep_1 = campaign_phase(batch, generations, max_steps, replay=False)
    rep_n = campaign_phase(batch, generations, max_steps, mesh=mesh,
                           replay=False)
    diff = key_diff(campaign_key(rep_1), campaign_key(rep_n))
    if diff:
        raise AssertionError(
            f"sharded campaign differs from the one-chip campaign "
            f"(first: one chip, second: sharded): {diff}"
        )
    log("campaign:sharded:check", identical=True,
        violations=len(rep_n.violations), corpus=len(rep_n.corpus))
    if skewed:
        raise AssertionError(f"device memory is not split evenly: {in_use}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the seed-sharded path across 4 chips")
    args = ap.parse_args(argv)
    info = find_device()
    if args.chips == 4:
        if info["count"] != 4:
            raise SystemExit(f"--chips 4 needs 4 devices, found "
                             f"{info['count']}")
        sharded_phase(262144)
    else:
        engine_phase("raft", 65536, 600, 256)
        engine_phase("raftlog", 16384, 4000, 256)
        campaign_phase(65536, 4)
        campaign_cpu_check(4096, 2)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
