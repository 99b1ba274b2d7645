"""Static-determinism soak: the full jaxpr non-interference matrix plus
the repo-wide nondeterminism-leak lint. The LINT evidence artifact.

Four certificates:

1. **Non-interference matrix** — the six recorded models (raft,
   kvchaos, paxos, raftlog, leasekv, shardkv; each with history
   recording on and off, kvchaos additionally with the client-army
   latency markers, raftlog additionally with the disk discipline on,
   the service models with their own army rows) x every observability
   build axis (base / metrics / timeline / coverage / hit-count /
   latency / all) x every lowering tuple (scatter/int64, dense, time32
   where eligible, and the readiness-indexed pool rows — ISSUE 13:
   the tile-summary columns sit on the CORE side, so the proof
   obligation over the indexed program is that no obs column reaches
   them or anything else core), traced via the single-seed step AND
   the vmapped ``make_run`` scan path, plus the sharded-campaign row
   (every model under the campaign tap set, proved through the
   ``shard_map`` call boundary — the program shape
   ``explore.run_device`` dispatches), plus the flight-recorder
   boundary row (the same campaign program traced with an
   ``obs.prof.ProgramProfiler`` active: no host-callback primitive,
   taint unchanged — the flight taps are provably host-side): every
   derived column provably isolated from every core column and the
   trace fold.

   **1c (dynamic):** the tile summaries' own derived-only certificate
   — a taint proof cannot state "value-identical", so the pool-index
   row is paired with a runtime bit-identity check: the indexed and
   flat lowerings produce identical traces/pools/histories on a
   chaos-bearing batch, and the carried summaries equal a
   from-scratch ``engine.build_pool_index`` rebuild.

   **1d (dynamic):** the farm non-interference row (ISSUE 16) — the
   energy machinery draws on its own registered threefry lane, so
   passing ``energy=None`` / ``EnergySchedule(mode="uniform")`` to
   ``explore.run`` must be bit-identical to not passing the argument
   at all: energy off is provably inert, the reproducible default.

   **1e (dynamic):** the device-detector on/off certificate (ISSUE
   18) — arming a fused ``check.device`` history screen
   (``search_seeds(device_check=...)``) is verdict-only: the
   simulation columns (traces, halt set, histories) are bit-identical
   to the unarmed host-judged sweep, and the screen's verdict equals
   the authoritative numpy detector on the unarmed arm's histories.
2. **Planted-leak positive control** — the ``met -> step`` mutant (one
   value-identical op reading a metrics counter into the RNG cursor)
   is caught, with the offending equation chain and the column names.
3. **Repo-wide lint** — the default surface (madsim_tpu/, examples/,
   tools/, bench.py) is finding-free; every intentional real-mode site
   is enumerated by a live ``# lint: allow(rule)`` pragma (the checked
   allowlist — a stale pragma is itself a finding).
4. **Rule fixtures** — every linter rule fires on a canonical negative
   fixture (the linter's own positive control).
5. **Interval-prover smoke** — the absint overflow + lane proofs on
   raft/record across the full lowering sweep, both planted mutants
   (time32 sentinel decay, lane collision) caught with cited chains.
   The FULL absint matrix is its own artifact (tools/absint_soak.py,
   `make absint-soak`).

Usage: python tools/lint_soak.py > LINT_r11.txt
Exit 0 iff every certificate holds.
"""

import _bootstrap  # noqa: F401  (repo root on sys.path)

import sys
import time

import jax

from madsim_tpu.lint import (  # noqa: E402
    check_matrix,
    check_noninterference,
    lint_repo,
    lint_source,
    plant_met_leak,
)
from madsim_tpu.lint.noninterference import (  # noqa: E402
    BUILD_AXES,
    CAMPAIGN_AXES,
    CHECK_AXES,
    FLIGHT_AXES,
    LAYOUT_AXES,
)
from madsim_tpu.engine import EngineConfig  # noqa: E402
from madsim_tpu.models import make_raft  # noqa: E402


def main() -> None:
    failures = []
    t_all = time.monotonic()  # lint: allow(wall-clock)
    print(f"# lint soak: platform={jax.devices()[0].platform}")

    # ---- certificate 1: the full non-interference matrix ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 1: jaxpr non-interference, model x build-flag matrix ==")
    reports = check_matrix(layouts=LAYOUT_AXES, log=lambda s: print(f"  {s}"))
    bad = [r for r in reports if not r.ok]
    n_eqns = sum(r.n_eqns for r in reports)
    print(f"  step-entry matrix: {len(reports)} proofs, "
          f"{n_eqns} equations walked, {len(bad)} leak(s)")
    # the scan path: one run-entry proof per model at the widest flags
    run_reports = check_matrix(
        axes={"all": BUILD_AXES["all"]}, entry="run",
        log=lambda s: print(f"  {s}"),
    )
    bad += [r for r in run_reports if not r.ok]
    # the pod-scale row: every model under the campaign tap set, the
    # batched run proved THROUGH the shard_map boundary — the program
    # shape explore.run_device dispatches every generation
    sharded_reports = check_matrix(
        axes=CAMPAIGN_AXES, entry="sharded_run",
        log=lambda s: print(f"  {s}"),
    )
    bad += [r for r in sharded_reports if not r.ok]
    # the flight-recorder boundary row: the campaign tap set traced
    # with an obs.prof.ProgramProfiler ACTIVE through the shard_map
    # boundary — the profiler/heartbeat/memory taps are host-side by
    # design, and this proves the traced program stays callback-free
    # and taint-isolated with them armed
    flight_reports = check_matrix(
        axes=FLIGHT_AXES, entry="sharded_run",
        log=lambda s: print(f"  {s}"),
    )
    bad += [r for r in flight_reports if not r.ok]
    # the device-verification row (ISSUE 14): every model with the
    # check.device detector kernels traced WITH the sim through the
    # shard_map boundary — the explore.run_device history-hunt program
    # shape. Proof obligations: the detectors touch only the derived
    # history columns and the new check_ok verdict output (taint set
    # unchanged), and the program stays host-callback-free
    check_reports = check_matrix(
        axes=CHECK_AXES, entry="sharded_run",
        log=lambda s: print(f"  {s}"),
    )
    bad += [r for r in check_reports if not r.ok]
    if bad:
        failures.append("noninterference")
        for r in bad:
            print(r.summary())
    print(f"cert1 {'PASS' if not bad else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 1c: pool-index derived-only, the dynamic half ----
    # (the static rows above prove obs isolation over the indexed
    # program; bit-identity of the indexed lowering itself is a VALUE
    # property no taint walk can witness — certified here at runtime)
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 1c: readiness-index on/off bit-identity (dynamic) ==")
    import dataclasses as _dc

    import numpy as _np

    from madsim_tpu.engine import (
        POOL_INDEX_STATE_FIELDS,
        Build,
        build_pool_index,
        make_init,
        make_run,
        pool_tile,
    )

    _wl = make_raft(record=True)
    _cfg = EngineConfig(
        pool_size=40, loss_p=0.02, clog_backoff_max_ns=2_000_000_000
    )
    _seeds = _np.arange(64, dtype=_np.uint64)
    _off = Build(layout="scatter", pool_index=False)
    _on = Build(layout="scatter", pool_index=True)
    _a = jax.block_until_ready(jax.jit(make_run(_wl, _cfg, 300, _off))(
        make_init(_wl, _cfg, _off)(_seeds)))
    _b = jax.block_until_ready(jax.jit(make_run(_wl, _cfg, 300, _on))(
        make_init(_wl, _cfg, _on)(_seeds)))
    _div = [
        f.name for f in _dc.fields(_a)
        if f.name not in POOL_INDEX_STATE_FIELDS
        and not _np.array_equal(
            _np.asarray(getattr(_a, f.name)), _np.asarray(getattr(_b, f.name))
        )
    ]
    _tm, _tc = build_pool_index(
        _b.ev_time, _b.ev_valid, pool_tile(_cfg.pool_size)
    )
    _mask = _np.asarray(_tc) > 0
    _sum_ok = _np.array_equal(
        _np.asarray(_tc), _np.asarray(_b.tile_cnt)
    ) and _np.array_equal(
        _np.asarray(_tm)[_mask], _np.asarray(_b.tile_min)[_mask]
    )
    if _div or not _sum_ok:
        failures.append("pool-index-identity")
        print(f"  DIVERGED fields={_div} summaries_ok={_sum_ok}")
    else:
        print(f"  indexed == flat over {len(_dc.fields(_a)) - 2} fields; "
              f"carried summaries == from-scratch rebuild")
    print(f"cert1c {'PASS' if not (_div or not _sum_ok) else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 1d: farm energy off is provably inert ----
    # (ISSUE 16: energy draws live on their own registered threefry
    # lane; off/uniform must replay the historical schedule exactly)
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 1d: farm energy-off bit-identity (dynamic) ==")
    from madsim_tpu import explore as _explore
    from madsim_tpu.chaos import FaultPlan as _FaultPlan
    from madsim_tpu.chaos import PauseStorm as _PauseStorm
    from madsim_tpu.farm import EnergySchedule as _ES

    _eplan = _FaultPlan((
        _PauseStorm(targets=(0, 1, 2, 3, 4), n=1, t_min_ns=20_000_000,
                    t_max_ns=300_000_000, down_min_ns=50_000_000,
                    down_max_ns=200_000_000),
    ), name="lint-energy")
    _ekw = dict(generations=3, batch=16, root_seed=11, max_steps=200,
                cov_words=8, invariant=lambda v: (v["trace"] & 7) != 0)
    _ewl = make_raft()

    def _efp(rep):
        return (
            [(e.id, e.seed, e.trace, e.new_bits) for e in rep.corpus],
            rep.cov_map.tolist(),
            [(e.seed, e.trace) for e in rep.violations],
            rep.curve, rep.viol_curve,
        )

    _base = _efp(_explore.run(_ewl, _cfg, _eplan, **_ekw))
    _off = _efp(_explore.run(_ewl, _cfg, _eplan, energy=None, **_ekw))
    _uni = _efp(_explore.run(
        _ewl, _cfg, _eplan, energy=_ES(mode="uniform"), **_ekw
    ))
    _energy_ok = _base == _off == _uni
    if not _energy_ok:
        failures.append("farm-energy-identity")
        print("  DIVERGED: energy off/uniform changed the campaign")
    else:
        print(f"  absent == None == uniform over {len(_base[0])} corpus "
              f"entries, {len(_base[2])} violations")
    print(f"cert1d {'PASS' if _energy_ok else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 1e: device detector on/off bit-identity ----
    # (ISSUE 18: the fused history screens are verdict-only — arming
    # one must not perturb a single simulation bit, and its verdict
    # must equal the authoritative numpy detector on the unarmed arm)
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 1e: device-detector on/off bit-identity (dynamic) ==")
    from madsim_tpu.check import device as _dcheck
    from madsim_tpu.check import lease_safety as _lease_safety
    from madsim_tpu.engine import search_seeds as _search_seeds
    from madsim_tpu.models import make_leasekv as _make_leasekv
    from madsim_tpu.models.leasekv import OP_EXPIRE as _OPE
    from madsim_tpu.models.leasekv import OP_PUT as _OPP

    _det_ok = True
    _lcfg = EngineConfig(
        pool_size=48, loss_p=0.02, clog_backoff_max_ns=2_000_000_000
    )
    _lscreens = (_dcheck.lease_safety(_OPP, _OPE),)
    for _tag, _lkw in (
        ("clean", dict(record=True)),
        ("mutant", dict(record=True, bug=True, ttl_ms=50)),
    ):
        _lwl = _make_leasekv(**_lkw)
        _lbox = {}

        def _lhinv(h, _b=_lbox):
            _b["h"] = h
            return _np.ones(len(h.count), bool)

        _skw = dict(n_seeds=128, max_steps=2500, require_halt=False)
        _roff = _search_seeds(
            _lwl, _lcfg, None, history_invariant=_lhinv, **_skw
        )
        _ron = _search_seeds(
            _lwl, _lcfg, None, device_check=_lscreens, **_skw
        )
        _h = _lbox["h"]
        _host_mask = _lease_safety(_h, _OPP, _OPE)
        _sim_same = _np.array_equal(_roff.traces, _ron.traces) and \
            _np.array_equal(_roff.halted, _ron.halted) and \
            _np.array_equal(_roff.overflowed, _ron.overflowed)
        _verdict_same = _np.array_equal(_ron.screen_ok, _host_mask)
        # the escalation payload: exactly the flagged seeds' histories,
        # bit-identical to the unarmed arm's rows
        _fl = _ron.flagged_idx
        _fh = _ron.flagged_history
        _payload_same = _np.array_equal(
            _fl, _np.nonzero(~_host_mask & ~_roff.overflowed)[0]
        ) and _np.array_equal(_fh.count, _h.count[_fl]) and \
            _np.array_equal(_fh.word, _h.word[_fl])
        _n_fl = len(_fl)
        if not (_sim_same and _verdict_same and _payload_same):
            _det_ok = False
            print(f"  {_tag}: DIVERGED sim={_sim_same} "
                  f"verdict={_verdict_same} payload={_payload_same}")
        else:
            print(f"  {_tag}: armed == unarmed over 128 seeds "
                  f"({_n_fl} flagged, payloads bit-identical)")
    if not _det_ok:
        failures.append("detector-identity")
    print(f"cert1e {'PASS' if _det_ok else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 2: the planted met->step leak is caught ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 2: planted derived->core leak (positive control) ==")
    rep = check_noninterference(
        make_raft(record=True),
        EngineConfig(
            pool_size=40, loss_p=0.02, clog_backoff_max_ns=2_000_000_000
        ),
        Build(layout="scatter", time32=False, metrics=True),
        mutate=plant_met_leak,
    )
    caught = (
        not rep.ok
        and "step" in rep.leaks
        and "met" in rep.leaks["step"]["labels"]
        and bool(rep.leaks["step"]["chain"])
    )
    print(rep.summary())
    if not caught:
        failures.append("mutant")
    print(f"cert2 {'PASS' if caught else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 3: repo-wide lint is clean ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 3: repo-wide nondeterminism-leak lint ==")
    res = lint_repo()
    for f in res.findings:
        print(f"  FINDING {f}")
    by_rule: dict = {}
    for f in res.allowed:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    print(f"  {res.n_files} files, {len(res.findings)} finding(s), "
          f"{len(res.allowed)} allowlisted site(s) by rule: "
          + ", ".join(f"{k}={v}" for k, v in sorted(by_rule.items())))
    for f in res.allowed:
        print(f"  allow {f.path}:{f.line} [{f.rule}]")
    if not res.ok:
        failures.append("repo-lint")
    print(f"cert3 {'PASS' if res.ok else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 4: every rule fires on its negative fixture ----
    print("== cert 4: rule fixtures (linter positive controls) ==")
    fixtures = {
        "wall-clock": "import time\ns = int(time.time_ns())\n",
        "ambient-entropy": "import os\nx = os.urandom(8)\n",
        "uuid-entropy": "import uuid\nu = uuid.uuid4()\n",
        "np-random": "import numpy as np\nx = np.random.rand()\n",
        "unordered-iter": "for x in set([1, 2]):\n    pass\n",
        "id-hash-branch": "if id(object()) % 2:\n    pass\n",
        "host-callback": (
            "from jax.experimental import io_callback\n"
            "io_callback(print, None, 1)\n"
        ),
        "fixed-key": "import jax\nk = jax.random.PRNGKey(0)\n",
        "unused-allow": "x = 1  # lint: allow(np-random)\n",
    }
    rules_ok = True
    for rule, src in fixtures.items():
        hit = rule in [
            f.rule for f in lint_source(src, "fx.py", sim_code=True).findings
        ]
        print(f"  {rule}: {'fires' if hit else 'MISSED'}")
        rules_ok &= hit
    if not rules_ok:
        failures.append("rule-fixtures")
    print(f"cert4 {'PASS' if rules_ok else 'FAIL'}")

    # ---- certificate 5: interval-prover smoke (absint) ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 5: absint overflow + lane smoke (full matrix: "
          "make absint-soak) ==")
    from madsim_tpu.lint import (
        ABSINT_AXES,
        absint_matrix,
        absint_model_matrix,
        run_mutant_controls,
    )

    amodels = [m for m in absint_model_matrix() if m[0] == "raft/record"]
    areps = absint_matrix(
        amodels, {"all": ABSINT_AXES["all"]}, layouts=LAYOUT_AXES,
        log=lambda s: print(f"  {s}"),
    )
    abad = [r for r in areps if not r.ok]
    controls = run_mutant_controls()
    mut_ok = all(caught for _n, _r, caught in controls)
    for name, _rep, caught in controls:
        print(f"  {name} mutant caught: {caught}")
    if abad or not mut_ok:
        failures.append("absint")
        for r in abad:
            print(r.summary())
    print(f"cert5 {'PASS' if not abad and mut_ok else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    print(f"# done in {time.monotonic() - t_all:.0f}s wall")  # lint: allow(wall-clock)
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all certificates PASS")


if __name__ == "__main__":
    main()
