"""Fleet metrics: device-side reduction of the engine's MET_* columns.

The reference surfaces per-run stats through ``tracing`` spans and the
``Stat`` counters (reference madsim/src/sim/net/network.rs:106-111 —
``msg_count``); at engine scale the same information is a column: every
seed folds the MET_* counters into ``SimState.met`` (engine/core.py,
``metrics=True``) and this module reduces the (S, M) batch **on
device** — totals, min/max, log2 histograms, the halt-code
distribution — so a 65k-seed sweep reports fleet-level shape without
ever moving per-seed history or timeline columns to the host. Only the
(M,)- and (M, B)-shaped reductions cross the transfer boundary.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from ..engine.core import (
    Build,
    HALT_DONE,
    HALT_IDLE,
    HALT_RUNNING,
    HALT_TIME_LIMIT,
    MET_HALT_CODE,
    METRIC_NAMES,
    N_METRICS,
)
from ..engine.search import _compiled_run

__all__ = ["FleetMetrics", "fleet_reduce", "fleet_metrics"]

# log2 histogram buckets: bucket 0 = count 0, bucket b in 1..16 = value
# in [2^(b-1), 2^b), bucket 17 = >= 2^16. 18 buckets cover any int32
# counter a realistic step budget can reach while staying readable.
N_BUCKETS = 18

_HALT_LABELS = {
    HALT_RUNNING: "running",
    HALT_DONE: "workload-halt",
    HALT_TIME_LIMIT: "time-limit",
    HALT_IDLE: "idle",
}


@dataclasses.dataclass(frozen=True)
class FleetMetrics:
    """Fleet-level reduction of per-seed MET_* counters.

    Every array is indexed by metric slot (``METRIC_NAMES`` order). The
    MET_HALT_CODE slot is categorical, not a counter — its total/mean
    are meaningless and the ``halt_codes`` distribution is the real
    signal there.
    """

    n_seeds: int
    totals: np.ndarray  # (M,) int64 fleet sums
    mins: np.ndarray  # (M,) int32 per-seed minima
    maxs: np.ndarray  # (M,) int32 per-seed maxima
    hist: np.ndarray  # (M, N_BUCKETS) int64 log2 histograms
    halt_codes: np.ndarray  # (4,) int64 seeds per HALT_* code
    # seeds whose event pool dropped events (the engine overflow
    # contract): their counters undercount, so a nonzero value means
    # the fleet shape includes unreliable rows — loud in format().
    # 0 when the reducer was handed a bare met batch with no overflow
    # column (fleet_metrics always supplies one).
    overflowed: int = 0
    # causal-provenance stats (``fleet_reduce(lam=...)``, causal=True
    # runs): per-seed max Lamport depth — the longest happens-before
    # chain any node folded — reduced to min/max + log2 histogram, and
    # the fleet-mean concurrency width sum(lam)/max(lam) (~how many
    # causal chains advanced in parallel; 1.0 = fully sequential,
    # n_nodes = perfectly concurrent). None without causal columns.
    depth_min: int | None = None
    depth_max: int | None = None
    depth_hist: np.ndarray | None = None  # (N_BUCKETS,) int64
    width_mean: float | None = None

    @property
    def names(self) -> tuple:
        return METRIC_NAMES

    def mean(self, name: str) -> float:
        return float(self.totals[METRIC_NAMES.index(name)]) / self.n_seeds

    def total(self, name: str) -> int:
        return int(self.totals[METRIC_NAMES.index(name)])

    def format(self, histograms: bool = False) -> str:
        """Text table of the fleet shape (the soak-artifact rendering)."""
        lines = [
            f"fleet metrics over {self.n_seeds} seeds:",
            f"  {'metric':<12} {'total':>12} {'mean':>10} "
            f"{'min':>7} {'max':>7}",
        ]
        for m, name in enumerate(METRIC_NAMES):
            if m == MET_HALT_CODE:
                continue
            lines.append(
                f"  {name:<12} {int(self.totals[m]):>12} "
                f"{self.totals[m] / self.n_seeds:>10.1f} "
                f"{int(self.mins[m]):>7} {int(self.maxs[m]):>7}"
            )
            if histograms:
                nz = np.nonzero(self.hist[m])[0]
                if nz.size:
                    buckets = ", ".join(
                        f"{_bucket_label(b)}: {int(self.hist[m, b])}"
                        for b in nz
                    )
                    lines.append(f"      hist {buckets}")
        halt = ", ".join(
            f"{_HALT_LABELS[c]} {int(self.halt_codes[c])}"
            for c in sorted(_HALT_LABELS)
            if self.halt_codes[c]
        )
        lines.append(f"  halt codes: {halt or 'none'}")
        if self.depth_hist is not None:
            lines.append(
                f"  causal: depth min {self.depth_min} max "
                f"{self.depth_max}, mean concurrency width "
                f"{self.width_mean:.2f}"
            )
            if histograms:
                nz = np.nonzero(self.depth_hist)[0]
                if nz.size:
                    buckets = ", ".join(
                        f"{_bucket_label(b)}: {int(self.depth_hist[b])}"
                        for b in nz
                    )
                    lines.append(f"      depth hist {buckets}")
        if self.overflowed:
            lines.append(
                f"  WARNING: {self.overflowed} seed(s) overflowed the "
                f"event pool — their counters undercount (raise "
                f"pool_size and re-sweep)"
            )
        return "\n".join(lines)


def _bucket_label(b: int) -> str:
    if b == 0:
        return "0"
    if b == N_BUCKETS - 1:
        return f">={1 << (b - 1)}"
    lo, hi = 1 << (b - 1), (1 << b) - 1
    return str(lo) if lo == hi else f"{lo}-{hi}"


@jax.jit
def _reduce(met):
    """(S, M) int32 -> all fleet reductions, entirely on device."""
    m64 = met.astype(jnp.int64)
    totals = jnp.sum(m64, axis=0)
    mins = jnp.min(met, axis=0)
    maxs = jnp.max(met, axis=0)
    thresholds = jnp.asarray(
        [1 << b for b in range(N_BUCKETS - 1)], jnp.int64
    )
    bucket = jnp.sum(
        m64[:, :, None] >= thresholds[None, None, :], axis=-1
    )  # (S, M) in 0..N_BUCKETS-1
    hist = jnp.sum(
        (bucket[:, :, None] == jnp.arange(N_BUCKETS)[None, None, :]).astype(
            jnp.int64
        ),
        axis=0,
    )
    codes = met[:, MET_HALT_CODE]
    halt = jnp.sum(
        (codes[:, None] == jnp.arange(4)[None, :]).astype(jnp.int64), axis=0
    )
    return totals, mins, maxs, hist, halt


@jax.jit
def _reduce_lam(lam):
    """(S, N) uint32 Lamport clocks -> fleet causal stats, on device.

    Per-seed depth = max over nodes (the longest happens-before chain
    folded anywhere); per-seed width = sum/max (total causal work over
    the critical path — the classic parallelism ratio). Only the
    scalar/histogram reductions leave the device.
    """
    depth = jnp.max(lam, axis=1).astype(jnp.int64)  # (S,)
    total = jnp.sum(lam.astype(jnp.int64), axis=1)
    width = jnp.where(depth > 0, total / jnp.maximum(depth, 1), 1.0)
    thresholds = jnp.asarray(
        [1 << b for b in range(N_BUCKETS - 1)], jnp.int64
    )
    bucket = jnp.sum(depth[:, None] >= thresholds[None, :], axis=-1)
    hist = jnp.sum(
        (bucket[:, None] == jnp.arange(N_BUCKETS)[None, :]).astype(
            jnp.int64
        ),
        axis=0,
    )
    return jnp.min(depth), jnp.max(depth), hist, jnp.mean(width)


def fleet_reduce(met, overflow=None, lam=None) -> FleetMetrics:
    """Reduce an (S, N_METRICS) per-seed metric batch to fleet shape.

    ``met`` may be the device-resident ``SimState.met`` batch (the
    metrics-only path: the reduction runs jitted on device and only the
    reduced arrays transfer) or a host copy (``SearchReport.met``) —
    same values either way. Pass the run's ``overflow`` column too when
    available: overflowed seeds' counters undercount (dropped events
    never dispatched), and the reduction surfaces their count loudly.
    ``lam`` is a causal run's (S, N) Lamport-clock batch
    (``SimState.lam`` / ``SearchReport.lam``): the causal depth/width
    stats fold on device the same way.
    """
    mm = jnp.asarray(met)
    if mm.ndim != 2 or mm.shape[1] != N_METRICS:
        raise ValueError(
            f"met must be (S, {N_METRICS}) MET_*-slot columns, got shape "
            f"{mm.shape}"
        )
    totals, mins, maxs, hist, halt = _reduce(mm)
    n_over = 0
    if overflow is not None:
        n_over = int(jax.jit(lambda o: jnp.sum(o > 0))(jnp.asarray(overflow)))
    causal: dict = {}
    if lam is not None and np.prod(np.shape(lam)):
        dmin, dmax, dhist, wmean = _reduce_lam(jnp.asarray(lam))
        causal = dict(
            depth_min=int(dmin),
            depth_max=int(dmax),
            depth_hist=np.asarray(dhist),
            width_mean=float(wmean),
        )
    return FleetMetrics(
        n_seeds=int(mm.shape[0]),
        totals=np.asarray(totals),
        mins=np.asarray(mins),
        maxs=np.asarray(maxs),
        hist=np.asarray(hist),
        halt_codes=np.asarray(halt),
        overflowed=n_over,
        **causal,
    )


def _fleet_run(wl, cfg, build, n_seeds, max_steps, seed_base, seeds, plan):
    """The final batched state of one fleet sweep, device-resident: the
    (init, run) pair comes from ``engine.search``'s compiled-run cache,
    with ``plan``'s slot count and duplication added to ``build``."""
    if seeds is None:
        seeds = np.arange(seed_base, seed_base + n_seeds, dtype=np.uint64)
    else:
        seeds = np.asarray(seeds, np.uint64)
    if plan is not None:
        build = dataclasses.replace(
            build, plan_slots=int(plan.slots), dup_rows=bool(plan.uses_dup())
        )
    init, run, _ = _compiled_run(wl, cfg, max_steps, build)
    if plan is not None:
        state = init(seeds, plan.compile_batch(seeds, wl=wl))
    else:
        state = init(seeds)
    out, _ = run(state)
    return out


def fleet_metrics(
    wl,
    cfg,
    n_seeds: int = 4096,
    max_steps: int = 1000,
    seed_base: int = 0,
    seeds=None,
    plan=None,
    layout: str | None = None,
) -> FleetMetrics:
    """The metrics-only sweep: run ``n_seeds`` schedules and return the
    fleet reduction — nothing per-seed ever reaches the host.

    This is the flight-recorder overview of a seed space: the final
    batched state stays on device, ``fleet_reduce`` consumes its
    ``met`` column jitted, and only the (M,)-/(M, B)-shaped results
    transfer. History and timeline columns are not even allocated
    (their taps stay off), satisfying the metrics-only-path contract.
    ``plan`` follows the ``search_seeds`` contract (a chaos FaultPlan
    compiled per seed).
    """
    out = _fleet_run(
        wl, cfg, Build(layout=layout, metrics=True), n_seeds, max_steps,
        seed_base, seeds, plan,
    )
    return fleet_reduce(out.met, overflow=out.overflow)
