"""The reference's parts found by name: ``reference/<folder>/<name>.py``
of a benchmark directory (workloads, history checks, plan specs), so that
a new one is a new file."""

from __future__ import annotations

import functools
import os

from harness import BENCH, BenchError, _load_module


def path(folder: str, name: str, ext: str = ".py", bench: str = BENCH) -> str:
    return os.path.join(bench, "reference", folder, name + ext)


@functools.lru_cache(maxsize=None)
def load(folder: str, name: str, bench: str = BENCH):
    """The module ``reference/<folder>/<name>.py``; BenchError if none."""
    p = path(folder, name, bench=bench)
    if not os.path.isfile(p):
        raise BenchError(f"no {folder} entry {name!r}: missing file {p}")
    return _load_module(p, f"bench_ref_{folder}_{name}")


def entry(e) -> tuple[str, dict]:
    """(name, args) of a list entry given as a name or as
    ``{"name": ..., "args": {...}}``."""
    return (e, {}) if isinstance(e, str) else (e["name"], e.get("args", {}))
