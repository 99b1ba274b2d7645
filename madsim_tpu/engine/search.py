"""Batched chaos-schedule search: hunt seeds that violate an invariant.

The reference's multi-seed runner executes ``MADSIM_TEST_NUM`` seeds and
prints a repro banner for the first failure (reference
madsim/src/sim/runtime/builder.rs:110-148, runtime/mod.rs:193-200). At
engine scale the same idea becomes a *search*: sweep tens of thousands
of seeded chaos schedules in one batched run (BASELINE.md config 5 —
"4,096-seed chaos schedule search") and report every seed whose final
state breaks a user invariant, each with the exact repro recipe.

    report = search_seeds(
        wl, cfg,
        invariant=lambda view: view["node_state"][:, 0, 0] >= 1,
        n_seeds=4096, max_steps=900,
    )
    report.failing_seeds  # -> np.ndarray of violating seeds
    report.banner()       # -> repro lines, seed + config hash each

The invariant is a host-side predicate over the final batched state
(numpy views), returning a boolean array over the seed axis — True =
invariant holds. Deterministic by construction: re-running any failing
seed (alone or in any batch) reproduces the identical trace.

Final-state predicates cannot see operations that were lost along the
way; for workloads with ``Workload.history`` the sweep also accepts a
``history_invariant`` over the recorded per-seed operation histories
(madsim_tpu.check) — the FoundationDB-style workload check.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Mapping

import numpy as np

import jax

from .compact import (
    RESULT_FIELDS,
    SCREEN_FIELDS,
    HostFetch,
    make_run_compacted,
    row_steps,
)
from .core import (
    Build,
    EngineConfig,
    Workload,
    check_plan_horizon,
    make_init,
    make_run_while,
)

__all__ = ["SearchReport", "make_sweep", "search_seeds"]

# compiled-run cache: repeated searches over the same (workload, config,
# step budget, build) — the tool's own repro workflow — reuse the XLA
# program instead of re-tracing per call (jit's cache keys on function
# identity, so a fresh closure per call would defeat it). Entries hold
# obs.prof.AotProgram wrappers, so every build is phase-timed and
# retrace-counted (the flight-recorder attribution), and the build
# share of a dispatch is separable from execution
# (SearchReport.build_wall_s).
_RUN_CACHE: dict = {}

# compiled device-verification programs, keyed on the screen tuple (a
# value-hashable invariant identity — check.device.HistoryScreen): the
# screen kernels + the verdict-word pack as ONE jitted program applied
# to the run's device-resident history columns. AotProgram entries, so
# retraces are counted like every other cached program.
_SCREEN_CACHE: dict = {}


def _screen_prog(screens: tuple):
    prog = _SCREEN_CACHE.get(screens)
    if prog is None:
        from ..check.device import pack_verdicts, screen_ok
        from ..obs.prof import AotProgram

        def run_screen(word, t, count, drop):
            ok = screen_ok(screens, word, t, count, drop)
            return pack_verdicts(ok)

        prog = _SCREEN_CACHE[screens] = AotProgram(
            "engine.search.screen", screens, run_screen
        )
    return prog


def _compiled_run(wl: Workload, cfg: EngineConfig, max_steps: int,
                  build: Build, compact: bool = False, hist_screen=None):
    """THE (init, run) pair of a batched sweep, built once per resolved
    spec and cached: ``(init, run, wl)``, with ``init`` an
    ``AotProgram`` (``jit_init`` on the device trace) and ``run`` the
    compacted runner (``compact=True``, ``hist_screen`` embedded) or an
    ``AotProgram`` of the lockstep ``make_run_while(...).counted``,
    which returns ``(final state, row steps)``. Every (init, run) user
    takes its pair from here: ``search_seeds``, ``make_sweep``,
    ``chaos.shrink_plan`` and the ``obs`` helpers. Plan VALUES are
    runtime data, so one entry serves every plan of the same width."""
    b = build.resolve(wl, cfg)
    key = (id(wl), cfg.hash(), max_steps, compact, b, hist_screen)
    if key not in _RUN_CACHE:
        # imported here: obs is a consumer of the engine — a module-level
        # import would run the whole obs package during engine import
        from ..obs.prof import AotProgram

        if compact:
            # make_run_compacted jits internally (its build wall stays
            # inside dispatch — documented limitation)
            run = make_run_compacted(
                wl, cfg, max_steps, b, hist_screen=hist_screen
            )
        else:
            run = AotProgram(
                "engine.search.run", key,
                make_run_while(wl, cfg, max_steps, b).counted,
            )
        _RUN_CACHE[key] = (
            # compiled once per batch shape and plan-row structure: run
            # eagerly, the vmapped init dispatches each of its ~130
            # primitives on its own
            AotProgram("engine.search.init", key, make_init(wl, cfg, b)),
            run,
            wl,  # keep the workload alive so id() stays unique
        )
    return _RUN_CACHE[key]


def make_sweep(
    wl: Workload, cfg: EngineConfig, max_steps: int, build: Build = Build()
):
    """Build the traceable batched sweep: ``sweep(seeds[, rows]) -> view``.

    The device-resident form of one ``search_seeds`` dispatch: init the
    seed batch (with the compiled ``PlanRows`` when ``build.plan_slots
    > 0``), run ``make_run_while`` to the step cap, and return the final
    state as a ``{field name: device array}`` view — NO host transfer,
    no invariant evaluation, and the function is jit/shard_map-
    traceable, so callers can fuse it into larger device programs (the
    explore device campaign composes it with on-device mutation and
    admission; ``search_seeds`` wraps it with the host-side report
    instead). It traces the same (init, run) pair ``search_seeds``
    caches (:func:`_compiled_run`). The view also holds ``row_steps``,
    the loop's int64 ``[paid, live]`` row-step counters.
    """
    init, run, _ = _compiled_run(wl, cfg, max_steps, build)
    init, counted, plan = init.fn, run.fn, build.plan_slots

    def sweep(seeds, rows=None):
        out, counts = counted(init(seeds, rows) if plan else init(seeds))
        view = {
            f.name: getattr(out, f.name) for f in dataclasses.fields(out)
        }
        view["row_steps"] = counts
        return view

    return sweep


@dataclasses.dataclass
class SearchReport:
    """Outcome of one batched invariant sweep."""

    workload: str
    config_hash: str
    seeds: np.ndarray  # every seed searched
    ok: np.ndarray  # (S,) bool — invariant held
    halted: np.ndarray  # (S,) bool
    overflowed: np.ndarray  # (S,) bool — event-pool or history-buffer
    # drops: verdict unreliable
    traces: np.ndarray  # (S,) uint64 — per-seed trace hashes
    # max per-seed step coordinate. Under compact=True the per-row step
    # counters freeze when a row is banked out, so this equals the
    # lockstep loop's iteration count only for the last-halting seed
    # (per-seed values are still bit-identical between the two paths).
    steps: int
    # fault-plan hash when the sweep ran under a chaos plan: the repro
    # key is then (seed, config, plan) — all three printed in the banner
    plan_hash: str = ""
    # wall this call spent building (trace + lower + compile) its init
    # and run programs — nonzero only on a cold compiled-run cache entry
    # or a signature change. Callers timing the dispatch subtract this
    # to get pure execution wall (the explore drivers' compile_wall_s
    # split); on the compact path only the init's share, as the staged
    # internal jits of its runner are not separable.
    build_wall_s: float = 0.0
    # per-seed coverage bitmaps, (S, cov_words) uint32 — None unless the
    # sweep ran with cov_words > 0 (madsim_tpu.explore)
    cov: np.ndarray | None = None
    # (S,) int64 per-seed halt clock (0 while running) — the causal
    # horizon explore's mutators use to avoid perturbing post-halt slots
    halt_times: np.ndarray | None = None
    # observability columns (madsim_tpu.obs) — None unless the sweep
    # ran with the corresponding tap enabled:
    # (S, N_METRICS) per-seed fleet counters (metrics=True); reduce
    # fleet-wide with obs.fleet_reduce
    met: np.ndarray | None = None
    # per-seed timeline ring views (timeline_cap > 0): a namespace of
    # tl_count/tl_drop/tl_t/tl_meta/tl_args arrays, each seed-leading;
    # decode one seed's stream with obs.decode_timeline(report.timeline,
    # wl, i)
    timeline: object | None = None
    # overflow breakdown: which channel voided which seeds. overflowed
    # stays the union the quarantine uses; tl_dropped does NOT void a
    # verdict (the timeline is forensics, not evidence) but is loud in
    # the banner
    pool_overflowed: np.ndarray | None = None
    hist_dropped: np.ndarray | None = None
    tl_dropped: np.ndarray | None = None
    # tail-latency columns (latency=LatencySpec(...)): the per-seed
    # log-linear sketches (S, phases, N_LAT_BUCKETS) and completed-op
    # counts — reduce fleet-wide with obs.latency_reduce; SLO verdicts
    # come from check.slo_bounded as the sweep's invariant. lat_dropped
    # flags seeds whose markers named op ids outside LatencySpec.ops —
    # their sketches undercount, so it is loud in the banner (the
    # tl_drop rule: forensic loudness, verdicts judge what WAS folded)
    lat_hist: np.ndarray | None = None
    lat_count: np.ndarray | None = None
    lat_dropped: np.ndarray | None = None
    # device-verification columns (device_check=...): the per-seed
    # screen verdict (True = clean), its packed uint32 transfer form
    # (ceil(S/32) words — what actually crossed the device boundary on
    # the lockstep path), and the escalation input: the FULL histories
    # of exactly the seeds the screen flagged (and that did not
    # overflow), as a check.BatchHistory over flagged_idx rows — feed
    # these to the exact Wing–Gong checker for confirmation (the PR-1
    # cross-check discipline). hist_fold (compact path only) counts
    # records prefix-compaction folded out per seed.
    screen_ok: np.ndarray | None = None
    verdict_words: np.ndarray | None = None
    flagged_idx: np.ndarray | None = None
    flagged_history: object | None = None
    hist_fold: np.ndarray | None = None
    # causal provenance (causal=True): the final per-node Lamport
    # clocks, (S, N) uint32 — per-seed causal depth/width stats reduce
    # with obs.fleet_reduce(lam=...); with timeline_cap the ring's
    # tl_seq/tl_parent/tl_lam columns ride report.timeline and
    # obs.causal.causal_slice computes violation cones from them
    lam: np.ndarray | None = None
    # host-path counters (also the ``madsim.search`` span's metadata,
    # obs.prof): bytes and arrays this call copied device->host (a
    # compacted sweep's results cross as one packed buffer,
    # engine/compact.py), and the runner's row-steps — rows x loop
    # iterations paid (the compacted runner at each phase's size) and
    # those whose row had not yet halted, so row_steps_live /
    # row_steps_paid is the lockstep efficiency
    d2h_bytes: int = 0
    d2h_transfers: int = 0
    row_steps_paid: int = 0
    row_steps_live: int = 0

    @property
    def failing_seeds(self) -> np.ndarray:
        """Violations on seeds whose simulation was trustworthy (no
        pool or history overflow — see :attr:`overflowed_seeds`)."""
        return self.seeds[~self.ok & ~self.overflowed]

    @property
    def unhalted_seeds(self) -> np.ndarray:
        """Seeds still running at max_steps — schedules the step budget
        could not finish (raise max_steps or treat as liveness bugs)."""
        return self.seeds[~self.halted]

    @property
    def overflowed_seeds(self) -> np.ndarray:
        """Seeds whose event pool dropped events (raise
        ``cfg.pool_size``) or whose history buffer dropped records
        (raise ``HistorySpec.capacity`` / the model's
        ``hist_capacity``): their verdicts are simulator artifacts, not
        evidence — fix the capacity and re-search (the same rule
        bench.py applies to its metric)."""
        return self.seeds[self.overflowed]

    def banner(self, limit: int = 10) -> str:
        """Repro recipe per failing seed (runtime/mod.rs:193-200 shape),
        with the per-seed halt/overflow breakdown when available."""
        bad = self.failing_seeds
        s = len(self.seeds)
        lines = [
            f"chaos search over {s} seeds of "
            f"{self.workload!r}: {len(bad)} violation(s)",
        ]
        n_halt = int(np.asarray(self.halted).sum())
        if self.met is not None:
            # metrics carry the per-seed halt reason (engine HALT_* codes)
            from .core import (
                HALT_DONE,
                HALT_IDLE,
                HALT_TIME_LIMIT,
                MET_HALT_CODE,
            )
            codes = np.asarray(self.met)[:, MET_HALT_CODE]
            done = int((codes == HALT_DONE).sum())
            tlim = int((codes == HALT_TIME_LIMIT).sum())
            idle = int((codes == HALT_IDLE).sum())
            running = s - n_halt - idle
            lines.append(
                f"  halted {n_halt}/{s}: {done} workload-halt, "
                f"{tlim} time-limit; {idle} idle (empty pool), "
                f"{running} still running at the step cap"
            )
        elif n_halt < s:
            lines.append(
                f"  halted {n_halt}/{s}; {s - n_halt} still running at "
                f"the step cap (run with metrics=True for the halt-"
                f"reason breakdown)"
            )
        if self.overflowed.any():
            pool = (
                int(np.asarray(self.pool_overflowed).sum())
                if self.pool_overflowed is not None else 0
            )
            hist = (
                int(np.asarray(self.hist_dropped).sum())
                if self.hist_dropped is not None else 0
            )
            detail = f" (pool {pool}, history {hist})" if pool or hist else ""
            lines.append(
                f"  WARNING: {int(self.overflowed.sum())} seed(s) "
                f"overflowed the event pool or history buffer{detail}; "
                f"excluded (raise pool_size / HistorySpec capacity)"
            )
        if self.tl_dropped is not None and self.tl_dropped.any():
            lines.append(
                f"  WARNING: {int(self.tl_dropped.sum())} seed(s) "
                f"overflowed the timeline ring (raise timeline_cap; "
                f"verdicts unaffected — the timeline is forensics only)"
            )
        if self.lat_dropped is not None and self.lat_dropped.any():
            lines.append(
                f"  WARNING: {int(self.lat_dropped.sum())} seed(s) "
                f"dropped latency markers (op ids outside "
                f"LatencySpec.ops) — their sketches undercount; size "
                f"LatencySpec.ops to cover every army op id"
            )
        if self.screen_ok is not None:
            n_flag = (
                len(self.flagged_idx) if self.flagged_idx is not None
                else int((~self.screen_ok).sum())
            )
            fold = (
                f", {int(self.hist_fold.sum())} records prefix-compacted"
                if self.hist_fold is not None else ""
            )
            lines.append(
                f"  device screen: {n_flag} flagged seed(s) escalated "
                f"with full histories ({len(self.verdict_words)} verdict "
                f"words transferred{fold})"
            )
        plan = f" plan_hash={self.plan_hash}" if self.plan_hash else ""
        for s in bad[:limit]:
            lines.append(
                f"  seed {int(s)}: rerun with seeds=[{int(s)}] "
                f"config_hash={self.config_hash}{plan}"
            )
        if len(bad) > limit:
            lines.append(f"  ... and {len(bad) - limit} more")
        return "\n".join(lines)


def _state_view(out, fetch=np.asarray,
                keep_device: tuple = ()) -> Mapping[str, np.ndarray]:
    """Host-side numpy views of EVERY final-state field, keyed by name
    (the checkpoint.py pattern) — invariants can reach anything,
    including paused/clog chaos state and the raw event pool.
    ``keep_device`` names stay as device arrays (a device-checked sweep
    never materializes the big history columns on the host — that is
    the transfer the verdict words replace). ``fetch`` copies the
    others (a ``compact.HostFetch`` counts the bytes)."""
    return {
        f.name: (
            getattr(out, f.name) if f.name in keep_device
            else fetch(getattr(out, f.name))
        )
        for f in dataclasses.fields(out)
    }


def search_seeds(
    wl: Workload,
    cfg: EngineConfig,
    invariant: Callable[[Mapping[str, np.ndarray]], np.ndarray] | None,
    n_seeds: int = 4096,
    max_steps: int = 1000,
    seed_base: int = 0,
    require_halt: bool = True,
    layout: str | None = None,
    compact: bool = False,
    history_invariant: Callable | None = None,
    plan=None,
    seeds: np.ndarray | None = None,
    plan_rows=None,
    plan_hash: str | None = None,
    dup_rows: bool | None = None,
    cov_words: int = 0,
    metrics: bool = False,
    timeline_cap: int = 0,
    cov_hitcount: bool = False,
    latency=None,
    pool_index: bool | None = None,
    device_check=None,
    causal: bool = False,
    retry=None,
) -> SearchReport:
    """Run ``n_seeds`` chaos schedules and evaluate ``invariant`` on the
    final states.

    ``require_halt=True`` (default) additionally counts a seed that
    never halts within ``max_steps`` as a violation — an unfinished
    schedule means the scenario's goal condition was never reached,
    which is exactly the liveness bug a chaos search is hunting.

    ``compact=True`` runs the seed-compaction path (engine/compact.py):
    typically 2-3x faster on halting workloads, per-seed values
    identical — but the invariant's view then contains only the banked
    result fields (RESULT_FIELDS: seed/now/step/halted/halt_time/trace/
    overflow/msg_count/node_state plus the history columns), not the
    raw event pool or clog/alive arrays. Invariants over ``node_state``
    (the overwhelmingly common kind) are unaffected.

    ``history_invariant`` makes the sweep a *workload* check instead of
    a final-state check: it receives a ``check.history.BatchHistory``
    over the recorded operation histories of every seed at once and
    returns a ``(n_seeds,)`` boolean array (True = history clean).
    Requires ``wl.history``; composes with ``invariant`` (a seed must
    pass both), and ``invariant=None`` means history-only. Seeds that
    overflowed the history buffer are quarantined exactly like event-
    pool overflows: their verdicts land in ``overflowed_seeds``, never
    in ``failing_seeds`` — the invariant sees them as *empty* histories
    (count 0, drop 0), so strict per-seed checkers
    (``BatchHistory.ops``) can run over every seed without crashing on
    one whose verdict would be discarded anyway.

    ``plan`` injects a declarative fault plan (``madsim_tpu.chaos``):
    each seed's plan compiles to its own deterministic fault trajectory
    (pre-seeded event-pool rows), the nemesis analog of the reference's
    hand-rolled per-model chaos. The plan hash joins the repro banner —
    ``(seed, config, plan)`` is then the complete repro key. Requires
    ``cfg.pool_size >= n_nodes + plan.slots``.

    The coverage-guided exploration loop (``madsim_tpu.explore``) uses
    three extensions: ``seeds`` replaces the contiguous
    ``seed_base..+n_seeds`` range with an explicit seed array (mutated
    corpora draw fresh threefry-derived seeds, not consecutive ints);
    ``plan_rows`` injects PRE-COMPILED per-seed plan rows — every row
    may carry a *different* mutated plan, which no single ``plan``
    object can express (pass ``plan_hash`` to label the banner, and
    ``dup_rows=True`` if any row uses duplication); ``cov_words=CW``
    runs the engine's coverage taps and returns the per-seed bitmaps
    as ``report.cov`` (S, CW).

    The observability taps (madsim_tpu.obs) ride the same way:
    ``metrics=True`` returns per-seed fleet counters as ``report.met``
    (S, N_METRICS) and upgrades the banner with the halt-reason
    breakdown; ``timeline_cap=T`` captures each seed's dispatched-event
    stream (``report.timeline``, decode with ``obs.decode_timeline``);
    ``cov_hitcount=True`` switches the coverage bitmaps to AFL-style
    hit-count bucketing; ``latency=LatencySpec(...)`` runs the
    tail-latency tap (client-army op clocks + per-seed sketches,
    ``report.lat_hist``/``lat_count`` — reduce with
    ``obs.latency_reduce``, judge with ``check.slo_bounded`` as the
    invariant). All of them are derived state only — the traces and
    verdicts are bit-identical with them off or on.

    ``layout`` and ``pool_index`` pick the lowering and the other
    keywords the compiled axes (``engine.Build`` docstring); the
    resolved spec keys the compiled-run cache.

    ``retry`` arms the client-retry timers (``engine.RetrySpec``,
    ``engine.Build`` docstring). With ``plan`` it defaults to the
    plan's own policy — ``plan.retry_spec()`` when some army carries a
    ``chaos.RetryPolicy`` — so a policied plan sweeps retried without
    further wiring; pass ``retry=`` explicitly on the ``plan_rows``
    path (pre-compiled rows carry no policy object).

    ``causal=True`` folds exact causal provenance (``engine.Build``
    docstring): the final per-node Lamport clocks return as
    ``report.lam`` (S, N) and — with ``timeline_cap`` — the ring gains
    the ``tl_seq``/``tl_parent``/``tl_lam`` DAG columns, which
    ``obs.causal.causal_slice`` turns into the backward happens-before
    cone of a violation. Derived state only, like every tap here.

    ``device_check`` (a ``check.device.HistoryScreen`` or tuple of
    them) is the device-resident form of ``history_invariant``
    (mutually exclusive with it): the batch detectors run as jnp
    kernels over the still-device-resident history columns, and the
    host receives **packed verdict words** (``report.verdict_words``,
    one bit per seed) plus the *flagged* seeds' full histories
    (``report.flagged_history`` — the exact-checker escalation input)
    instead of every seed's columns. Verdicts are bit-identical to the
    numpy path (``check.device.screens_invariant(screens)`` is the
    reference arm); overflowed seeds are quarantined identically. With
    ``compact=True`` the screen additionally runs at bank time inside
    the compacted program and **prefix-compacts** the banked columns
    (``report.hist_fold`` counts the folded records; flagged seeds
    keep full histories — see ``make_run_compacted``).
    """
    from ..obs.prof import span

    with span("madsim.search") as sp:
        if history_invariant is not None and wl.history is None:
            raise ValueError(
                f"history_invariant needs operation histories, but workload "
                f"{wl.name!r} has Workload.history=None"
            )
        screens = None
        if device_check is not None:
            from ..check.device import as_screens

            screens = as_screens(device_check)
            if wl.history is None:
                raise ValueError(
                    f"device_check judges operation histories, but workload "
                    f"{wl.name!r} has Workload.history=None"
                )
            if history_invariant is not None:
                raise ValueError(
                    "pass device_check OR history_invariant, not both: they "
                    "are the same verdict on two execution paths (compare "
                    "them via check.device.screens_invariant in a test, not "
                    "in one sweep)"
                )
        if invariant is None and history_invariant is None and screens is None:
            raise ValueError(
                "need an invariant, a history_invariant or a device_check"
            )
        if plan is not None and plan_rows is not None:
            raise ValueError("pass plan OR plan_rows, not both")
        if seeds is None:
            seeds = np.arange(seed_base, seed_base + n_seeds, dtype=np.uint64)
        else:
            seeds = np.asarray(seeds, np.uint64)
            if seeds.ndim != 1:
                raise ValueError(f"seeds must be 1-D, got shape {seeds.shape}")
            n_seeds = len(seeds)
        fetch = HostFetch()
        with span("madsim.search.plan"):
            if plan is not None:
                plan_slots = int(plan.slots)
                if dup_rows is None:
                    dup_rows = bool(plan.uses_dup())
                if latency is not None:
                    # a client army whose op-id range exceeds the latency
                    # columns would silently drop every out-of-range marker
                    # (counted in lat_drop, but a whole mis-sized army is a
                    # build error, not a runtime anomaly)
                    for spec in getattr(plan, "specs", ()):
                        ob = getattr(spec, "op_base", None)
                        no = getattr(spec, "n_ops", None)
                        if (ob is not None and no is not None
                                and ob + no > latency.ops):
                            raise ValueError(
                                f"{type(spec).__name__} op ids "
                                f"[{ob}, {ob + no}) exceed LatencySpec.ops="
                                f"{latency.ops}; size the spec to cover every "
                                f"army op id"
                            )
                if cfg.time_limit_ns and hasattr(plan, "validate_windows"):
                    # a fault window opening after the clock cap can never
                    # fire: the sweep would silently certify the unfaulted
                    # protocol (chaos.FaultPlan.validate_windows — warn
                    # loudly here, clamp explicitly via plan.clamped(...))
                    plan.validate_windows(cfg.time_limit_ns)
                rows = plan.compile_batch(seeds, wl=wl)
                if plan_hash is None:
                    plan_hash = plan.hash()
                if retry is None and hasattr(plan, "retry_spec"):
                    retry = plan.retry_spec()
            elif plan_rows is not None:
                rows = plan_rows
                plan_slots = int(fetch(rows.time).shape[1])
                if fetch(rows.time).shape[0] != n_seeds:
                    raise ValueError(
                        f"plan_rows carries {fetch(rows.time).shape[0]} rows "
                        f"for {n_seeds} seeds"
                    )
                dup_rows = bool(dup_rows)
            else:
                rows = None
                plan_slots = 0
                dup_rows = bool(dup_rows)
            build = Build(
                layout=layout, pool_index=pool_index, plan_slots=plan_slots,
                dup_rows=dup_rows, cov_words=cov_words,
                cov_hitcount=cov_hitcount, metrics=metrics,
                timeline_cap=timeline_cap, latency=latency, causal=causal,
                retry=retry,
            ).resolve(wl, cfg)
            if rows is not None and build.time32:
                # the compiled rows land in the int32 offset
                # representation: a plan event past the horizon would
                # silently wrap
                check_plan_horizon(
                    build, cfg, int(fetch(rows.time).max(initial=0))
                )
        with span("madsim.search.init"):
            init, run, _ = _compiled_run(
                wl, cfg, max_steps, build, compact,
                # only the compacted program embeds the screen (bank-time
                # fold); the lockstep path screens via _screen_prog, so its
                # run cache entry must stay shared with unscreened sweeps
                hist_screen=screens if compact else None,
            )
            state0 = init(seeds, rows) if rows is not None else init(seeds)
        with span("madsim.search.run"):
            if compact:
                packed = jax.block_until_ready(run.compute(state0))
            else:
                out, counts = jax.block_until_ready(run(state0))
        verdict_words = None
        with span("madsim.search.fetch"):
            if compact:
                out = run.assemble(packed, fetch)
                paid, live = row_steps(packed, fetch)
                fields = RESULT_FIELDS if screens is None else (
                    RESULT_FIELDS + SCREEN_FIELDS
                )
                view = {f: getattr(out, f) for f in fields}
            else:
                paid, live = (int(x) for x in fetch(counts))
                view = _state_view(
                    out, fetch,
                    keep_device=(
                        ("hist_word", "hist_t") if screens is not None
                        else ()
                    ),
                )
                if screens is not None:
                    # THE history transfer of a device-checked sweep:
                    # ceil(S/32) packed words instead of (S, H, 5) +
                    # (S, H) columns
                    verdict_words = fetch(
                        _screen_prog(screens)(
                            out.hist_word, out.hist_t, out.hist_count,
                            out.hist_drop,
                        )
                    )
        with span("madsim.search.check"):
            if invariant is not None:
                ok = np.asarray(invariant(view), dtype=bool)
                if ok.shape != (n_seeds,):
                    raise ValueError(
                        f"invariant must return a ({n_seeds},) boolean "
                        f"array, got shape {ok.shape}"
                    )
            else:
                ok = np.ones((n_seeds,), dtype=bool)
            pool_overflowed = np.asarray(view["overflow"]) > 0
            overflowed = pool_overflowed
            dev_ok = None
            flagged_idx = None
            flagged_history = None
            if screens is not None:
                from ..check.device import (
                    pack_verdicts_host,
                    unpack_verdicts,
                )
                from ..check.history import BatchHistory

                if compact:
                    # bank-time verdicts (computed on device BEFORE the
                    # fold)
                    dev_ok = np.asarray(view["hist_ok"], bool)
                    verdict_words = pack_verdicts_host(dev_ok)
                else:
                    dev_ok = unpack_verdicts(verdict_words, n_seeds)
                ok = ok & dev_ok
                # escalation: exactly the flagged (and trustworthy) seeds
                # ship their FULL histories to the host — the Wing–Gong
                # confirmation input, the PR-1 cross-check discipline
                hist_drop_np = np.asarray(view["hist_drop"])
                flagged_idx = np.nonzero(~dev_ok & ~(hist_drop_np > 0))[0]
                w, tt = view["hist_word"], view["hist_t"]
                flagged_history = BatchHistory(
                    # device gather + transfer of only the flagged rows on
                    # the lockstep path; plain numpy take on the compact
                    # path (whose columns arrived prefix-compacted, flagged
                    # seeds verbatim-full by construction)
                    word=fetch(w[flagged_idx]),
                    t=fetch(tt[flagged_idx]),
                    count=np.asarray(view["hist_count"])[flagged_idx],
                    drop=hist_drop_np[flagged_idx],
                )
            if history_invariant is not None:
                # imported here: check is a consumer of the engine, not a
                # dependency (engine -> check at module import would be a
                # cycle)
                from ..check.history import BatchHistory

                bh = BatchHistory.from_view(view)
                hist_over = np.asarray(bh.drop) > 0
                if hist_over.any():
                    # overflowed seeds reach the invariant as EMPTY
                    # histories: their verdicts are discarded by the
                    # quarantine below, and a strict per-seed checker
                    # (BatchHistory.ops) must not crash the whole sweep on
                    # a seed it will never judge. The raw truncated
                    # columns stay available on the result view.
                    bh = BatchHistory(
                        word=bh.word, t=bh.t,
                        count=np.where(
                            hist_over, 0, np.asarray(bh.count)
                        ).astype(np.int32),
                        drop=np.zeros_like(np.asarray(bh.drop)),
                    )
                hok = np.asarray(history_invariant(bh), dtype=bool)
                if hok.shape != (n_seeds,):
                    raise ValueError(
                        f"history_invariant must return a ({n_seeds},) "
                        f"boolean array, got shape {hok.shape}"
                    )
                ok = ok & hok
            hist_dropped = None
            if wl.history is not None:
                # dropped history records void the verdict (loud, like
                # pool overflow) whether or not a history predicate ran
                hist_dropped = np.asarray(view["hist_drop"]) > 0
                overflowed = overflowed | hist_dropped
            halted = view["halted"]
            if require_halt:
                ok = ok & halted
            if timeline_cap:
                from types import SimpleNamespace

                tl = SimpleNamespace(**{
                    f: np.asarray(view[f])
                    for f in ("tl_count", "tl_drop", "tl_t", "tl_meta",
                              "tl_args", "tl_pay", "tl_emit")
                    + (("tl_seq", "tl_parent", "tl_lam") if causal else ())
                })
                tl_dropped = tl.tl_drop > 0
            else:
                tl, tl_dropped = None, None
            report = SearchReport(
                workload=wl.name,
                config_hash=cfg.hash(),
                seeds=seeds,
                ok=ok,
                halted=halted,
                overflowed=overflowed,
                traces=view["trace"],
                steps=int(np.asarray(view["step"]).max()),
                plan_hash=plan_hash or "",
                build_wall_s=(
                    init.last_build_s + getattr(run, "last_build_s", 0.0)
                ),
                cov=np.asarray(view["cov"]) if cov_words else None,
                halt_times=np.asarray(view["halt_time"]),
                met=np.asarray(view["met"]) if metrics else None,
                timeline=tl,
                pool_overflowed=pool_overflowed,
                hist_dropped=hist_dropped,
                tl_dropped=tl_dropped,
                lat_hist=(
                    np.asarray(view["lat_hist"]) if latency is not None
                    else None
                ),
                lat_count=(
                    np.asarray(view["lat_count"]) if latency is not None
                    else None
                ),
                lat_dropped=(
                    np.asarray(view["lat_drop"]) > 0 if latency is not None
                    else None
                ),
                screen_ok=dev_ok,
                verdict_words=verdict_words,
                flagged_idx=flagged_idx,
                flagged_history=flagged_history,
                hist_fold=(
                    np.asarray(view["hist_fold"])
                    if screens is not None and compact else None
                ),
                lam=np.asarray(view["lam"]) if causal else None,
                d2h_bytes=fetch.nbytes,
                d2h_transfers=fetch.transfers,
                row_steps_paid=paid,
                row_steps_live=live,
            )
        sp.set_metadata(
            d2h_bytes=report.d2h_bytes,
            d2h_transfers=report.d2h_transfers,
            row_steps_paid=report.row_steps_paid,
            row_steps_live=report.row_steps_live,
        )
    return report
