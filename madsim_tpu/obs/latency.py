"""Fleet tail-latency: device-side reduction of the engine's sketches.

The engine folds every completed client-army op into a per-seed
log-linear histogram (``SimState.lat_hist``, engine/core.py
``latency=LatencySpec(...)`` — the ladder lives in ``LAT_EDGES_NS``).
This module reduces the (S, P, B) sketch batch **on device** into the
fleet tail shape — per-window p50/p90/p99/p999 + max — so a 65k-seed
sweep reports its latency distribution without moving any per-seed
column to the host; only the (P, B)-shaped totals cross the transfer
boundary. The sketch is *exactly mergeable*: the fleet histogram equals
the histogram of the concatenated per-op latencies (the property that
matters from t-digest, bought here with a fixed ladder instead of
adaptive centroids so merging is integer addition and bit-exact).

Quantiles read off the ladder are exact to one bucket of rank error:
``quantile(q)`` returns the upper edge of the bucket the q-th completed
op falls in (~19% relative width). That is the resolution an SLO
statement needs; per-op ``lat_inv``/``lat_resp`` columns remain on the
state for forensics when exactness matters.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from ..engine.core import (
    LAT_EDGES_NS,
    N_LAT_BUCKETS,
    Build,
    LatencySpec,
    lat_bucket_hi,
)
from .metrics import _fleet_run

__all__ = [
    "FleetLatency",
    "fleet_latency",
    "latency_reduce",
    "hist_quantile_bucket",
]

_QUANTILES = (0.50, 0.90, 0.99, 0.999)


def hist_quantile_bucket(hist: np.ndarray, q: float) -> np.ndarray:
    """Bucket index holding the ``q``-quantile of a ladder histogram.

    ``hist`` is (..., N_LAT_BUCKETS); returns int64 bucket indices of
    the same leading shape (-1 where the histogram is empty). The rank
    convention is ``ceil(q * total)`` — the smallest bucket whose
    cumulative count reaches it — which is the one place the sketch,
    the SLO detector (check.slo_bounded) and the accuracy tests must
    agree, so they all call this function.
    """
    h = np.asarray(hist, np.int64)
    total = h.sum(axis=-1)
    rank = np.ceil(q * total).astype(np.int64).clip(min=1)
    cum = np.cumsum(h, axis=-1)
    idx = np.argmax(cum >= rank[..., None], axis=-1)
    return np.where(total > 0, idx, -1)


@dataclasses.dataclass(frozen=True)
class FleetLatency:
    """Fleet-level reduction of per-seed latency sketches.

    ``hist`` is the merged (P, B) ladder histogram over every seed's
    completed ops; ``completed`` the total op count folded into it.
    Quantile values are bucket **upper edges** (conservative for an SLO:
    the true quantile is at most the reported value's bucket width
    below it, never above).
    """

    n_seeds: int
    hist: np.ndarray  # (P, B) int64 merged ladder histogram
    completed: int  # total ops folded in
    dropped: int  # markers with out-of-range op ids (fleet sum; loud)
    phase_ns: int  # window width the sketches were cut with

    @property
    def phases(self) -> int:
        return int(self.hist.shape[0])

    def quantile(self, q: float, phase: int | None = None) -> int:
        """q-quantile latency in ns (bucket upper edge); ``phase=None``
        pools every window. -1 when no ops completed there."""
        h = self.hist.sum(axis=0) if phase is None else self.hist[phase]
        b = int(hist_quantile_bucket(h, q))
        return -1 if b < 0 else int(lat_bucket_hi(b))

    def max_ns(self, phase: int | None = None) -> int:
        """Upper edge of the highest occupied bucket (-1 when empty)."""
        h = self.hist.sum(axis=0) if phase is None else self.hist[phase]
        nz = np.nonzero(h)[0]
        return -1 if nz.size == 0 else int(lat_bucket_hi(int(nz[-1])))

    def format(self) -> str:
        """Text table of the fleet tail (the soak-artifact rendering)."""
        lines = [
            f"fleet latency over {self.n_seeds} seeds: "
            f"{self.completed} completed ops"
            + (f", {self.dropped} DROPPED marker(s)" if self.dropped else ""),
            f"  {'window':<10} {'ops':>9} {'p50':>9} {'p90':>9} "
            f"{'p99':>9} {'p999':>9} {'max':>9}",
        ]

        def row(label, h):
            n = int(h.sum())
            cells = []
            for q in _QUANTILES:
                b = int(hist_quantile_bucket(h, q))
                cells.append(
                    "-" if b < 0 else f"{int(lat_bucket_hi(b)) / 1e6:.2f}ms"
                )
            nz = np.nonzero(h)[0]
            mx = "-" if nz.size == 0 else f"{int(lat_bucket_hi(int(nz[-1]))) / 1e6:.2f}ms"
            lines.append(
                f"  {label:<10} {n:>9} " + " ".join(f"{c:>9}" for c in cells)
                + f" {mx:>9}"
            )

        for p in range(self.phases):
            t0 = p * self.phase_ns / 1e6
            row(f"[{t0:.0f}ms..]", self.hist[p])
        if self.phases > 1:
            row("all", self.hist.sum(axis=0))
        return "\n".join(lines)


@jax.jit
def _reduce(lat_hist, lat_count, lat_drop):
    """(S, P, B) int32 -> merged totals, entirely on device."""
    return (
        jnp.sum(lat_hist.astype(jnp.int64), axis=0),
        jnp.sum(lat_count.astype(jnp.int64)),
        jnp.sum(lat_drop.astype(jnp.int64)),
    )


def latency_reduce(
    lat_hist, lat_count=None, lat_drop=None, *, phase_ns: int
) -> FleetLatency:
    """Reduce an (S, P, B) per-seed sketch batch to the fleet tail.

    ``lat_hist`` may be the device-resident ``SimState.lat_hist`` batch
    (the reduction runs jitted on device and only the (P, B) totals
    transfer) or a host copy (``SearchReport.lat_hist``) — same values
    either way, because the sketch merge is integer addition.

    ``phase_ns`` is REQUIRED and must be the ``LatencySpec.phase_ns``
    the sweep ran with: the sketches were cut into windows of that
    width, and a defaulted value would silently mislabel every window
    in the report (pass ``spec.phase_ns``).
    """
    hh = jnp.asarray(lat_hist)
    if hh.ndim != 3 or hh.shape[2] != N_LAT_BUCKETS:
        raise ValueError(
            f"lat_hist must be (S, P, {N_LAT_BUCKETS}) sketch columns, "
            f"got shape {hh.shape}"
        )
    s = hh.shape[0]
    cnt = jnp.zeros((s,), jnp.int32) if lat_count is None else jnp.asarray(lat_count)
    drp = jnp.zeros((s,), jnp.int32) if lat_drop is None else jnp.asarray(lat_drop)
    hist, completed, dropped = _reduce(hh, cnt, drp)
    hist = np.asarray(hist)
    return FleetLatency(
        n_seeds=int(s),
        hist=hist,
        completed=(
            int(completed) if lat_count is not None else int(hist.sum())
        ),
        dropped=int(dropped),
        phase_ns=int(phase_ns),
    )


def fleet_latency(
    wl,
    cfg,
    spec: LatencySpec,
    n_seeds: int = 4096,
    max_steps: int = 1000,
    seed_base: int = 0,
    seeds=None,
    plan=None,
    layout: str | None = None,
) -> FleetLatency:
    """The tail-only sweep: run ``n_seeds`` schedules and return the
    fleet latency reduction — nothing per-seed ever reaches the host.

    The latency analog of ``obs.fleet_metrics``: the final batched
    state stays on device, ``latency_reduce`` consumes its sketch
    columns jitted, and only the (P, B) totals transfer. ``plan``
    follows the ``search_seeds`` contract — for a tail profile it
    normally composes a ``chaos.ClientArmy`` (the load) with fault
    specs (the chaos the tail is measured under).
    """
    out = _fleet_run(
        wl, cfg, Build(layout=layout, latency=spec), n_seeds, max_steps,
        seed_base, seeds, plan,
    )
    return latency_reduce(
        out.lat_hist, out.lat_count, out.lat_drop, phase_ns=spec.phase_ns
    )
