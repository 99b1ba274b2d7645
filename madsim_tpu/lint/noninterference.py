"""Static non-interference proof for the engine's derived state.

The engine's observability columns (coverage, metrics, timeline,
history — and the disk columns when the sync discipline is off) carry a
contract: *derived state only* — the step may append to them but no
value computed from them may reach a core ``SimState`` column, an RNG
draw, or the trace fold. PRs 1-5 verified this dynamically (off = zero
size + bit-identical traces, sampled per layout). This module proves
it statically, per (workload, config, build flags): trace the step (or
run) function to a jaxpr, taint the derived input leaves with their
``engine.derived_fields`` names, propagate (lint.taint), and require
every CORE output leaf to come back label-free.

The report is machine-readable and cites SimState **field names** — the
same column vocabulary ``obs.explain`` narrates with — so a leak reads
like "``met`` reaches ``step`` via eqns[412]:add", not like an XLA dump.

The dynamic identity tests and this proof are complementary: the tests
catch semantic drift the type system can't see; the proof catches
value-identical-but-data-dependent edges (e.g. ``step + met*0``) that
bit-identity can never witness. :func:`plant_met_leak` builds exactly
that mutant, and the test suite asserts it is caught.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np

import jax

from ..engine.core import (
    Build,
    EngineConfig,
    LatencySpec,
    Workload,
    derived_fields,
    make_init,
    make_run,
    make_step,
    pool_index_eligible,
    time32_eligible,
)
from .taint import analyze_jaxpr

__all__ = [
    "BASE_LOWERING",
    "NonInterferenceReport",
    "check_matrix",
    "check_noninterference",
    "model_matrix",
    "plant_met_leak",
    "BUILD_AXES",
    "CAMPAIGN_AXES",
    "CHECK_AXES",
    "FLIGHT_AXES",
    "LAYOUT_AXES",
]

# Host round-trip primitives: none may appear in a traced sim program.
# The flight recorder / profiler (obs.prof, obs.flight) is host-side
# bookkeeping by design — the matrix proves it stays that way by
# tracing WITH a profiler active and scanning for these. The real rule
# is the substring match (io_callback/pure_callback/debug_callback/...
# all contain it); the set holds only the names that don't.
_CALLBACK_PRIMS = frozenset({"outside_call"})


def _callback_prims(jaxpr, found=None) -> list:
    """Names of host-callback primitives anywhere in a jaxpr tree."""
    if found is None:
        found = set()
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if "callback" in name or name in _CALLBACK_PRIMS:
            found.add(name)
        for key, val in eqn.params.items():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for item in vals:
                inner = getattr(item, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _callback_prims(inner, found)
                elif hasattr(item, "eqns"):
                    _callback_prims(item, found)
    return sorted(found)


def _leaf_names(tree) -> list:
    """SimState leaf names in flatten order (``.field`` -> ``field``)."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(path).lstrip(".") for path, _leaf in leaves]


@dataclasses.dataclass
class NonInterferenceReport:
    """Verdict + isolation frontier of one traced (wl, cfg, flags)."""

    workload: str
    config_hash: str
    entry: str  # "step" or "run"
    flags: dict  # the build flags that shaped the traced program
    derived: tuple  # taint-source field names (engine.derived_fields)
    # field -> sorted source labels, for EVERY tainted output field.
    # Derived fields legitimately appear here (they are read-modify-
    # write); a CORE field appearing is the leak.
    out_taint: dict
    # field -> {labels, chain} for core outputs only — the violations
    leaks: dict
    # tainted equations: [{path, prim, sources, mixes_clean}]
    frontier: list
    n_eqns: int
    # host-callback primitives found anywhere in the traced program —
    # always scanned (cheap); must be empty for sim code. With
    # flags["flight"] the trace itself ran under an active
    # ProgramProfiler, so a nonempty list would mean the flight taps
    # leaked INTO the traced program.
    callback_prims: list = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.leaks and not self.callback_prims

    def to_dict(self) -> dict:
        return {
            "workload": self.workload,
            "config_hash": self.config_hash,
            "entry": self.entry,
            "flags": self.flags,
            "derived": list(self.derived),
            "out_taint": self.out_taint,
            "leaks": self.leaks,
            "frontier": self.frontier,
            "n_eqns": self.n_eqns,
            "callback_prims": self.callback_prims,
            "ok": self.ok,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    def summary(self) -> str:
        what = (
            f"{self.workload} [{self.entry}] flags="
            f"{{{', '.join(f'{k}={v}' for k, v in sorted(self.flags.items()) if v)}}}"
        )
        if self.ok:
            return (
                f"OK   {what}: {len(self.derived)} tainted columns stay "
                f"isolated over {self.n_eqns} equations "
                f"({len(self.frontier)} on the frontier)"
            )
        if self.callback_prims and not self.leaks:
            return (
                f"LEAK {what}: host-callback primitive(s) "
                f"{self.callback_prims} inside the traced program"
            )
        lines = [f"LEAK {what}:"]
        for field, info in self.leaks.items():
            lines.append(
                f"  derived {sorted(info['labels'])} reaches core "
                f"column {field!r}"
            )
            for hop in info["chain"]:
                lines.append(
                    f"    via {hop['path']}:{hop['prim']} "
                    f"(sources {hop['sources']})"
                )
        return "\n".join(lines)


# the historical scatter/int64 program: the lowering a proof walks unless
# it is given another
BASE_LOWERING = Build(layout="scatter", time32=False)


def report_flags(build: Build) -> dict:
    """The JSON-able form of a build for a report's ``flags``."""
    flags = {f.name: getattr(build, f.name) for f in dataclasses.fields(build)}
    lat, rt = build.latency, build.retry
    # the spec's defining triple; the retry spec as its field tuple
    flags["latency"] = (
        (lat.ops, lat.phases, lat.phase_ns) if lat is not None else None
    )
    flags["retry"] = dataclasses.astuple(rt) if rt is not None else None
    return flags


def check_noninterference(
    wl: Workload,
    cfg: EngineConfig,
    build: Build = BASE_LOWERING,
    *,
    entry: str = "step",
    n_steps: int = 4,
    n_seeds: int = 2,
    mutate=None,
    flight: bool = False,
    check: bool = False,
) -> NonInterferenceReport:
    """Prove (or refute) derived-state non-interference for one build.

    ``entry="step"`` traces the single-seed step — the per-equation
    frontier is then readable. ``entry="run"`` traces
    ``make_run(n_steps)`` over a batched state, which routes the whole
    proof through a vmapped ``lax.scan`` body (the loop-carry fixpoint
    path). ``entry="sharded_run"`` traces the same batched run under
    ``shard_map`` across every available device — the multi-chip
    campaign program (explore.run_device's simulate stage), proved
    through the shard_map call boundary (the batch is rounded up to
    the device count). ``mutate`` optionally wraps the traced function (the planted
    leak mutants use it); it receives and returns a
    ``SimState -> SimState`` callable.

    ``flight=True`` performs the whole trace under an ACTIVE
    ``obs.prof.ProgramProfiler`` — the flight-recorder boundary proof:
    the profiler is host-side bookkeeping, so the traced program must
    be unchanged (same equations, no host-callback primitives, taint
    still isolated). Every report also carries ``callback_prims``: any
    host round-trip primitive found in the traced program fails the
    proof regardless of taint.

    ``check=True`` appends the device history detectors
    (``check.device.default_screens`` over the final state's history
    columns) to the traced program — the device-verification boundary
    proof: the detector kernels are traced WITH the sim (through the
    ``shard_map`` boundary under ``entry="sharded_run"``, the
    ``explore.run_device`` history-hunt program shape), and the proof
    obligations are that the taint set is UNCHANGED (the detectors
    read derived history columns and write only the new ``check_ok``
    output — never a core column) and that no host-callback primitive
    appears. Needs a run-shaped entry (the detectors judge batched
    final states).
    """
    flags = report_flags(build)
    if check:
        if entry == "step":
            raise ValueError(
                "check=True traces the batch detectors over a RUN's "
                "final states; use entry='run' or 'sharded_run'"
            )
        from ..check.device import default_screens
        from ..check.device import screen_ok as _screen_ok

        flags["check"] = True
        _screens = default_screens()

        def _with_check(base):
            def checked(st):
                out = base(st)
                return out, _screen_ok(
                    _screens, out.hist_word, out.hist_t, out.hist_count,
                    out.hist_drop,
                )
            return checked
    else:
        def _with_check(base):
            return base
    init = make_init(wl, cfg, build)
    state = init(np.zeros(max(n_seeds, 1), np.uint64))
    if entry == "step":
        fn = make_step(wl, cfg, build)
        template = jax.tree.map(lambda a: a[0], state)
    elif entry == "run":
        fn = _with_check(make_run(wl, cfg, n_steps, build))
        template = state
    elif entry == "sharded_run":
        # the multi-chip campaign program (explore.run_device's simulate
        # stage): the batched run under shard_map across every available
        # device — the proof walks THROUGH the shard_map call boundary
        # (lint.taint) instead of stopping at it. The per-shard body is
        # the same make_run scan, so a leak inside a shard is reported
        # with its nested eqns[..].shard_map.body path.
        from jax.sharding import PartitionSpec as _P

        from .. import parallel as _par

        mesh = _par.make_mesh()
        n_dev = int(mesh.devices.size)
        flags["mesh_devices"] = n_dev
        rows = max(n_seeds, n_dev)
        if rows % n_dev:
            rows += n_dev - rows % n_dev
        state = init(np.zeros(rows, np.uint64))
        run_fn = _with_check(make_run(wl, cfg, n_steps, build))
        spec = _P(mesh.axis_names)
        # the detector (when check=True) is INSIDE the shard_map body:
        # the per-shard program is sim + screen, exactly how
        # explore.run_device composes them
        fn = _par.shard_map_nocheck(
            run_fn, mesh, in_specs=spec, out_specs=spec
        )
        template = state
    else:
        raise ValueError(
            f"unknown entry {entry!r} (step, run, or sharded_run)"
        )
    if mutate is not None:
        fn = mutate(fn)

    if flight:
        # trace with the flight recorder's profiler ACTIVE: the traced
        # program must come out identical to the profiler-off trace
        # (the analysis below proves taint + callback-freedom; the test
        # suite additionally pins equation-count equality)
        from ..obs import prof as _prof

        flags["flight"] = True
        with _prof.profiled():
            closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(
                template
            )
    else:
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(template)
    in_names = _leaf_names(template)
    out_names = _leaf_names(out_shape)
    if check:
        # the checked entry returns (state, verdict): strip the tuple
        # prefix from the state leaves and name the verdict leaf — it
        # is ALLOWED to carry history taint (that is what a verdict
        # is); a core column newly tainted is still the leak
        out_names = [
            "check_ok" if n.startswith("[1]")
            else n.removeprefix("[0]").lstrip(".")
            for n in out_names
        ]
    derived = derived_fields(wl)
    dset = set(derived)
    if check:
        dset.add("check_ok")
    in_taints = [
        frozenset({name}) if name in dset else frozenset()
        for name in in_names
    ]
    result = analyze_jaxpr(closed, in_taints)

    out_taint = {}
    leaks = {}
    for i, (name, labels) in enumerate(zip(out_names, result.out_taint)):
        if not labels:
            continue
        out_taint[name] = sorted(labels)
        if name not in dset:
            leaks[name] = {
                "labels": sorted(labels),
                "chain": result.leak_chain(i),
            }
    return NonInterferenceReport(
        workload=wl.name,
        config_hash=cfg.hash(),
        entry=entry,
        flags=flags,
        derived=derived,
        out_taint=out_taint,
        leaks=leaks,
        frontier=[r.to_dict() for r in result.frontier],
        n_eqns=_count_eqns(closed.jaxpr),
        callback_prims=_callback_prims(closed.jaxpr),
    )


def _count_eqns(jaxpr) -> int:
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for key, val in eqn.params.items():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for item in vals:
                inner = getattr(item, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    n += _count_eqns(inner)
                elif hasattr(item, "eqns"):
                    n += _count_eqns(item)
    return n


# ---------------------------------------------------------------------------
# Planted leak mutants — the positive controls.
# ---------------------------------------------------------------------------


def plant_met_leak(step_fn):
    """Wrap a step so one op reads ``met`` into the RNG cursor.

    ``step + met[MET_SENT] * 0`` is **value-identical** to the clean
    step on every input — no runtime identity test, oracle compare or
    trace hash can ever distinguish them — yet it is a real data edge
    from a derived column into the RNG coordinate, exactly the class of
    bug the static proof exists to catch. Requires ``metrics=True``
    (otherwise ``met`` is zero-size and there is nothing to read).
    """
    import jax.numpy as jnp

    from ..engine.core import MET_SENT

    def mutant(st):
        out = step_fn(st)
        if out.met.ndim != 1:
            raise ValueError(
                "plant_met_leak is a step-entry mutant: it indexes the "
                "(N_METRICS,) met vector of ONE seed; with entry='run' "
                "the batched (S, N_METRICS) axis would be poisoned "
                "along the wrong dimension"
            )
        poison = (out.met[MET_SENT] * jnp.int32(0)).astype(jnp.uint32)
        return dataclasses.replace(out, step=out.step + poison)

    return mutant


# ---------------------------------------------------------------------------
# The certified matrix: four recorded models x build-flag axes.
# ---------------------------------------------------------------------------

# build-flag axes: each turns one derived-column family (or all of
# them) on. History on/off and disk-discipline on/off are MODEL
# variants (record= / durable=), so they live in model_matrix below.
BUILD_AXES = {
    "base": {},
    "metrics": dict(metrics=True),
    "timeline": dict(timeline_cap=8),
    "coverage": dict(cov_words=8),
    "hitcount": dict(cov_words=8, cov_hitcount=True),
    "latency": dict(latency=LatencySpec(ops=8, phases=2)),
    # the causal-provenance columns (ISSUE 19): the per-node Lamport
    # clock, the pool's parent/lam provenance columns and the ring's
    # seq/parent/lam banks. The clock FOLDS across dispatches
    # (lam[dst] = max(lam[dst], lam_at_emit) + 1) — a read-modify-write
    # cycle entirely inside the derived set, which is exactly the shape
    # a leak would take if the fold ever touched the RNG cursor or the
    # pool times, so the row is swept with the timeline on (the ring
    # banks only exist with a ring to write into).
    "causal": dict(causal=True, timeline_cap=8),
    "all": dict(
        metrics=True, timeline_cap=8, cov_words=8, cov_hitcount=True,
        latency=LatencySpec(ops=8, phases=2), causal=True,
    ),
}

# lowering/representation axes: Build lowerings. The scatter int64
# build was the historical matrix; dense and time32 produce the same
# jaxpr SHAPES (masked selects vs gathers, int32 vs int64 pool times)
# but different equation graphs — the proof must hold over all of them,
# and the COMBINED (dense, time32) pair is the exact program an
# accelerator runs (layout and representation both auto-resolve that
# way off-CPU), so it is swept too, not merely each axis alone. The
# placement member sweeps the scatter layout's two pool-write
# lowerings: "rank" is the select-chain program a small-pool CPU run
# compiles (cold-bank appends — history rank-append, timeline/latency
# rows — ride this path), "scatter" the historical .at[].set stores a
# client-army-scale pool still uses; both must keep the derived columns
# isolated, not just the default one. Dense ignores placement (its
# one-hot writes are already rank-matched). An unset placement or pool
# index is the automatic one.
LAYOUT_AXES = (
    Build(layout="scatter", time32=False, placement="rank"),
    Build(layout="scatter", time32=False, placement="scatter"),
    Build(layout="dense", time32=False),
    Build(layout="scatter", time32=True, placement="rank"),
    Build(layout="dense", time32=True),
    # the readiness-partitioned pool: the indexed program an army-scale
    # CPU pool compiles — tile-summary pop, per-tile free search,
    # element-store placement. The tile summary columns are derived BY
    # CONSTRUCTION (rebuilt on restore, excluded from the checkpoint
    # format) but trajectory-coupled, so they sit on the CORE side of
    # this proof: the obligation here is that no obs column ever
    # reaches them (or anything else core) through the index
    # arithmetic; their own value-correctness certificate is the index
    # on/off bit-identity pin (tests/test_pool_index.py,
    # tools/lint_soak.py cert 1c). The time32 pair covers the rebased
    # tile minima.
    Build(layout="scatter", time32=False, pool_index=True),
    Build(layout="scatter", time32=True, pool_index=True),
)

_BUILD_FIELDS = frozenset(f.name for f in dataclasses.fields(Build))


def split_axis(lowering: Build, flags: dict) -> tuple:
    """A build-axis dict applied to a lowering: ``(build, kwargs)``,
    the dict's :class:`Build` fields set on ``lowering`` and the rest
    (the proof's own options, e.g. ``flight``/``check``) left over."""
    build = dataclasses.replace(
        lowering, **{k: v for k, v in flags.items() if k in _BUILD_FIELDS}
    )
    return build, {k: v for k, v in flags.items() if k not in _BUILD_FIELDS}


def lowering_eligible(
    lowering: Build, wl: Workload, cfg: EngineConfig
) -> bool:
    """Whether a (model, lowering) pair can be built: a time32 lowering
    needs a time32-eligible model, a pool-index one a tile-divisible
    pool. Matrices skip ineligible pairs rather than failing them."""
    return not (
        (lowering.time32 and not time32_eligible(wl, cfg))
        or (lowering.pool_index and not pool_index_eligible(cfg))
    )


# The sharded-campaign matrix entry (ROADMAP lint follow-on; required
# before pod-scale campaigns are load-bearing): the device campaign's
# tap set — coverage guidance + fleet metrics + latency sketches, the
# derived columns explore.run_device folds while the simulate stage
# runs under shard_map — proved through the shard_map call boundary
# with entry="sharded_run". Sweep it as
# ``check_matrix(models, CAMPAIGN_AXES, entry="sharded_run")``.
CAMPAIGN_AXES = {
    "sharded-campaign": dict(
        cov_words=8, metrics=True, latency=LatencySpec(ops=8, phases=2),
    ),
    # the causal campaign (ISSUE 19): run_device with causal=True — the
    # Lamport fold + provenance ring traced THROUGH the shard_map call
    # boundary, proving the clock columns stay label-free per shard
    # exactly as they do in the single-chip program.
    "sharded-causal": dict(cov_words=8, causal=True, timeline_cap=8),
}

# The flight-recorder boundary entry (PR 12): the campaign tap set
# traced with an obs.prof.ProgramProfiler ACTIVE — proving the flight
# taps (profiler, heartbeats, device-memory accounting) stay host-side:
# the traced program carries no host-callback primitive and the taint
# proof is unchanged. Sweep as
# ``check_matrix(models, FLIGHT_AXES, entry="sharded_run")`` (the soak)
# or ``entry="run"`` (the tier-1 smoke).
FLIGHT_AXES = {
    "flight-campaign": dict(
        cov_words=8, metrics=True, latency=LatencySpec(ops=8, phases=2),
        flight=True,
    ),
}

# The device-verification entry (ISSUE 14): the history-hunt program
# shape — sim + the check.device detector kernels in ONE traced
# program, proved through the shard_map boundary
# (``check_matrix(models, CHECK_AXES, entry="sharded_run")``; the
# tier-1 smoke uses entry="run"). Obligations: the detectors read the
# derived history columns and write ONLY the new ``check_ok`` verdict
# output (taint set unchanged — no derived value reaches a core
# column through the detector arithmetic), and no host-callback
# primitive joins the program (the detectors are a lowering of the
# numpy checkers, not a host bridge).
CHECK_AXES = {
    "device-check": dict(cov_words=8, metrics=True, check=True),
}

def model_matrix() -> list:
    """(name, workload, config) triples for the six recorded models.

    Each model module owns its tracing entry points
    (``models/<name>.py lint_entries()``): every model appears with
    history recording on AND off, and raftlog additionally with the
    disk discipline on — the {metrics, timeline, coverage, history,
    disk-discipline} axes the acceptance matrix sweeps (build flags
    come from BUILD_AXES).
    """
    from ..models import kvchaos, leasekv, paxos, raft, raftlog, shardkv

    entries = []
    for mod in (raft, kvchaos, paxos, raftlog, leasekv, shardkv):
        for tag, wl, cfg_kw in mod.lint_entries():
            entries.append((tag, wl, EngineConfig(**cfg_kw)))
    return entries


def check_matrix(
    models=None,
    axes=None,
    *,
    entry: str = "step",
    layouts: tuple = (BASE_LOWERING,),
    log=None,
) -> list:
    """Run the proof over a model x build-flag matrix; returns reports.

    Defaults to the full certified matrix (tools/lint_soak.py scale);
    tests pass a slice for the tier-1 smoke. ``layouts`` sweeps
    :class:`Build` lowerings per cell (``LAYOUT_AXES`` is the full set).
    A (model, lowering) pair that cannot be built (:func:`lowering_eligible`)
    is skipped rather than failing the matrix.
    """
    if models is not None and not models:
        # an explicitly empty slice is a caller bug (e.g. a tag filter
        # that matched nothing) — falling back to the full matrix here
        # would silently multiply the gate's cost instead
        raise ValueError("check_matrix: models is empty")
    reports = []
    for name, wl, cfg in (models if models is not None else model_matrix()):
        for lowering in layouts:
            if not lowering_eligible(lowering, wl, cfg):
                continue
            for axis, flags in (axes or BUILD_AXES).items():
                build, kw = split_axis(lowering, flags)
                rep = check_noninterference(wl, cfg, build, entry=entry, **kw)
                rep.flags["axis"] = axis
                if log is not None:
                    log(rep.summary())
                reports.append(rep)
    return reports
