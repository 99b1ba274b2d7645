"""Interval-prover soak: the full absint matrix over the recorded
models. The ABSINT evidence artifact.

Four certificates:

1. **Overflow + lane matrix** — the four recorded models (every
   lint_entries variant, with each model's declared certification
   horizon from ``absint_entries``) x the absint build axes (base /
   dup-shadow-lanes / all-taps) x every ``LAYOUT_AXES`` lowering tuple
   (scatter/int64, dense, time32 where eligible, the readiness-indexed
   pool rows), walked via the single-seed step AND the vmapped
   ``make_run`` scan path: every signed add/sub/mul on a time- or
   counter-tainted value provably fits its dtype within the declared
   horizon, and every live threefry lane resolves into the structured
   ``PURPOSE_LANES`` registry with all sites pairwise disjoint.

2. **Planted positive controls** — the re-created time32
   sentinel-decay mutant (the PR-13 bug class: the carried tile_min
   rebased without the empty-tile re-mask, wrapping once the
   accumulated advance exceeds int32) and the lane-collision mutant
   (a value-identical draw at the engine's first per-emit latency
   lane) are both caught, with cited equation chains / site pairs.

3. **Pragma hygiene** — the ``# lint: allow(absint-*)`` allowlist is
   exercised exactly: every pragma the matrix used is printed, and a
   pragma no traced program exercised is a failure (the
   ``unused-allow`` rule extended to the interval prover).

4. **Lane census** — the live purpose-lane map of the default
   programs (which registry lanes carry draws, at how many sites).

Usage: python tools/absint_soak.py > ABSINT_r10.txt
Exit 0 iff every certificate holds.
"""

import _bootstrap  # noqa: F401  (repo root on sys.path)

import sys
import time

import jax

from madsim_tpu.lint import (
    ABSINT_AXES,
    absint_matrix,
    run_mutant_controls,
    stale_absint_pragmas,
)
from madsim_tpu.engine import Build


def main() -> None:
    failures = []
    t_all = time.monotonic()  # lint: allow(wall-clock)
    print(f"# absint soak: platform={jax.devices()[0].platform}")

    # ---- certificate 1: the full overflow + lane matrix ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 1: interval matrix, model x axis x lowering ==")
    reports = absint_matrix(log=lambda s: print(f"  {s}"))
    run_reports = absint_matrix(
        axes={"all": ABSINT_AXES["all"]},
        layouts=(
            Build(layout="scatter", time32=False),
            Build(layout="scatter", time32=True, pool_index=True),
        ),
        entry="run",
        log=lambda s: print(f"  {s}"),
    )
    reports += run_reports
    bad = [r for r in reports if not r.ok]
    n_eqns = sum(r.n_eqns for r in reports)
    n_ops = sum(r.checked_ops for r in reports)
    print(
        f"  {len(reports)} proofs ({len(run_reports)} run-entry), "
        f"{n_eqns} equations walked, {n_ops} tracked ops certified, "
        f"{len(bad)} failure(s)"
    )
    if bad:
        failures.append("matrix")
        for r in bad:
            print(r.summary())
    print(f"cert1 {'PASS' if not bad else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 2: the planted positive controls ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 2: planted mutants (positive controls) ==")
    controls = run_mutant_controls()
    for name, rep, caught in controls:
        print(f"  {name} (caught={caught}):")
        print("  " + rep.summary().replace("\n", "\n  "))
    if not all(caught for _n, _r, caught in controls):
        failures.append("mutants")
    ok2 = all(caught for _n, _r, caught in controls)
    print(f"cert2 {'PASS' if ok2 else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 3: pragma hygiene ----
    print("== cert 3: absint pragma allowlist exercised exactly ==")
    used = set()
    for r in reports:
        used.update(tuple(u) for u in r.used_pragmas)
    for u in sorted(used):
        print(f"  allow {u[0]}:{u[1]} [{u[2]}]")
    stale = stale_absint_pragmas(used)
    for s in stale:
        print(f"  STALE {s['file']}:{s['line']}: {s['message']}")
    if stale:
        failures.append("stale-pragmas")
    print(f"cert3 {'PASS' if not stale else 'FAIL'} "
          f"({len(used)} pragma(s) in use)")

    # ---- certificate 4: the live lane census ----
    print("== cert 4: live purpose-lane census ==")
    lanes: dict = {}
    sites = 0
    for r in reports:
        sites += len(r.lane_sites)
        for ln in r.lanes:
            lanes[ln] = lanes.get(ln, 0) + 1
    for ln, n in sorted(lanes.items()):
        print(f"  lane {ln}: live in {n} traced program(s)")
    print(f"  {sites} threefry site(s) across the matrix")
    ok4 = sites > 0 and "latency" in lanes and "poll_cost" in lanes
    if not ok4:
        failures.append("lane-census")
    print(f"cert4 {'PASS' if ok4 else 'FAIL'}")

    print(f"# done in {time.monotonic() - t_all:.0f}s wall")  # lint: allow(wall-clock)
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all certificates PASS")


if __name__ == "__main__":
    main()
