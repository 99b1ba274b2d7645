"""``python -m madsim_tpu.lint`` — the ``make lint`` entry point.

Runs the repo-wide nondeterminism-leak linter (fails on any finding)
and, with ``--jaxpr``, a non-interference smoke over a small slice of
the model matrix; with ``--absint``, the interval-prover smoke
(overflow + lane disjointness on one model across the lowering sweep,
plus the absint pragma staleness check). The full matrices live in
tools/lint_soak.py and tools/absint_soak.py. Exit status 0 = clean,
1 = findings, the usual linter contract.

``--format json`` emits one machine-readable object (findings, the
allowlist inventory, every proof report) for CI gating; ``--json`` is
the legacy spelling of the same thing.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m madsim_tpu.lint",
        description="static determinism analysis (madsim_tpu.lint)",
    )
    ap.add_argument(
        "paths",
        nargs="*",
        help="files/directories to lint (default: the repo surface)",
    )
    ap.add_argument(
        "--jaxpr",
        action="store_true",
        help="also run the non-interference smoke (raft + raftlog/durable)",
    )
    ap.add_argument(
        "--absint",
        action="store_true",
        help=(
            "also run the interval-prover smoke: overflow + threefry-lane "
            "proofs on raft/record across the lowering sweep, plus the "
            "absint pragma staleness check"
        ),
    )
    ap.add_argument(
        "--format",
        choices=("text", "json"),
        default="text",
        help="output format (json = one machine-readable object for CI)",
    )
    ap.add_argument(
        "--json",
        action="store_true",
        help="legacy alias for --format json",
    )
    ap.add_argument(
        "--show-allowed",
        action="store_true",
        help="print the checked allowlist (pragma inventory)",
    )
    args = ap.parse_args(argv)
    as_json = args.json or args.format == "json"

    from .rules import lint_paths, lint_repo

    result = lint_paths(args.paths) if args.paths else lint_repo()

    reports = []
    if args.jaxpr:
        from .noninterference import (
            BUILD_AXES,
            CHECK_AXES,
            LAYOUT_AXES,
            check_matrix,
            model_matrix,
        )

        want = ("raft/record", "raftlog/durable", "kvchaos/army")
        models = [m for m in model_matrix() if m[0] in want]
        if len(models) != len(want):
            # fail LOUDLY on tag drift: a silent miss would either
            # shrink the smoke or (via the empty-filter fallback) trace
            # the full model matrix inside the tier-1 budget
            raise SystemExit(
                f"lint --jaxpr: expected tags {want} in model_matrix(), "
                f"found {[m[0] for m in models]} — update the smoke "
                f"filter to match models/*.py lint_entries()"
            )
        # the same 'all' axis the soak matrix certifies — a new build
        # flag added there is automatically smoked here too — over
        # every lowering pair (scatter/int64, dense, time32): the TPU
        # runs exactly the dense/time32 programs the historical smoke
        # never traced
        reports = check_matrix(
            models, {"all": BUILD_AXES["all"]}, layouts=LAYOUT_AXES
        )
        # the device-verification boundary smoke (ISSUE 14): the
        # history-recording models with the check.device detector
        # kernels traced WITH the sim through the shard_map boundary —
        # taint set unchanged, verdict output only, no callback prims
        # (the full matrix row runs in tools/lint_soak.py)
        check_models = [m for m in models if m[0] in ("raft/record",)]
        reports += check_matrix(
            check_models, CHECK_AXES, entry="sharded_run"
        )

    absint_reports = []
    absint_stale = []
    if args.absint:
        from .absint import (
            ABSINT_AXES,
            absint_matrix,
            absint_model_matrix,
            stale_absint_pragmas,
        )

        models = [m for m in absint_model_matrix() if m[0] == "raft/record"]
        if not models:
            raise SystemExit(
                "lint --absint: tag raft/record missing from "
                "absint_model_matrix() — update the smoke filter to "
                "match models/*.py absint_entries()"
            )
        # one model, the FULL lowering sweep: the time32 and
        # readiness-indexed rows are what exercise the stale-slot
        # rebase pragmas, so the staleness check below stays honest
        absint_reports = absint_matrix(
            models, {"all": ABSINT_AXES["all"], "dup": ABSINT_AXES["dup"]},
        )
        used = set()
        for r in absint_reports:
            used.update(tuple(u) for u in r.used_pragmas)
        # staleness at smoke scale is judged over the files the smoke
        # provably traced: engine/core.py (every step build walks it)
        # plus any file a used pragma named. A legitimate pragma at a
        # site only the full matrix exercises (another model's path)
        # must not fail every `make lint` — tools/absint_soak.py
        # judges the whole surface against the whole matrix. core.py
        # stays in the set even with ZERO used pragmas, so an
        # allowlist that has gone entirely stale still fails here.
        from pathlib import Path as _Path

        from .absint import _REPO_ROOT as _AROOT

        smoke_files = sorted(
            {u[0] for u in used} | {"madsim_tpu/engine/core.py"}
        )
        absint_stale = stale_absint_pragmas(
            used, paths=[_Path(_AROOT) / f for f in smoke_files],
            root=_AROOT,
        )

    if as_json:
        print(
            json.dumps(
                {
                    "findings": [f.to_dict() for f in result.findings],
                    "allowed": [f.to_dict() for f in result.allowed],
                    "n_files": result.n_files,
                    "noninterference": [r.to_dict() for r in reports],
                    "absint": [r.to_dict() for r in absint_reports],
                    "absint_stale_pragmas": absint_stale,
                },
                sort_keys=True,
            )
        )
    else:
        for f in result.findings:
            print(str(f))
            if f.snippet:
                print(f"    {f.snippet}")
        if args.show_allowed:
            for f in result.allowed:
                print(f"ALLOWED {f}")
        for r in reports:
            print(r.summary())
        for r in absint_reports:
            print(r.summary())
        for s in absint_stale:
            print(
                f"{s['file']}:{s['line']}: [unused-allow] {s['message']}"
            )
        print(
            f"lint: {result.n_files} files, {len(result.findings)} "
            f"finding(s), {len(result.allowed)} allowlisted site(s)"
            + (f", {len(reports)} non-interference proofs" if reports else "")
            + (
                f", {len(absint_reports)} range proofs"
                if absint_reports else ""
            )
        )

    bad = (
        bool(result.findings)
        or any(not r.ok for r in reports)
        or any(not r.ok for r in absint_reports)
        or bool(absint_stale)
    )
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
