"""The program's own spans and counters in a traced window.

The program (``madsim_tpu.obs.prof``) opens ``madsim.*`` host spans on
the profiler's clock and carries its counters as their metadata:
``madsim.search`` the ``SearchReport`` counters (``d2h_bytes``,
``row_steps_paid``, ``row_steps_live``), ``madsim.explore.sync`` a
generation's row-steps. ``tracing.events_from_xplane`` keeps only the
benchmark's ``bench.*`` spans, so the readers of the program's metrics
read them here, from the run's own trace: the newest ``*.xplane.pb``
under ``.out/`` whose ``bench.window`` starts where the reduced window
does. On a program without the spans, or with ``--trace 0``, there is
nothing to read and every reader returns None.
"""

from __future__ import annotations

import glob
import os

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".out")
KEY = "program_spans"  # where a record keeps what was read


def spans(rec) -> list | None:
    """[[name, start_ns, end_ns, metadata], ...] of the program spans
    inside the traced window, by start; None when the record has no
    traced window, [] when the trace holds no program span."""
    if rec.get("trace") is None:
        return None
    if KEY not in rec:
        rec[KEY] = _read(rec["trace"]["spans"]["bench.window"][0])
    return rec[KEY]


def _read(window) -> list | None:
    w0, w1 = window
    paths = sorted(glob.glob(os.path.join(OUT, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    for path in reversed(paths):
        found = _host_spans(path)
        if any(n == "bench.window" and s == w0 for n, s, _, _ in found):
            return sorted(([n, s, e, m] for n, s, e, m in found
                           if n.startswith("madsim.") and w0 <= s < w1),
                          key=lambda x: x[1])
    return None


def _host_spans(path) -> list:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("bench.window", "madsim.")):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns),
                                {k: v for k, v in e.stats}))
    return out


def per_unit(rec, name: str, unit: str) -> float | None:
    """Mean milliseconds of span ``name`` per ``unit`` span (bench.batch
    or bench.campaign) of the window; None without such spans."""
    found = spans(rec)
    units = len(rec["trace"]["spans"].get(unit, [])) if found else 0
    hits = [e - s for n, s, e, _ in found or () if n == name]
    if not hits or not units:
        return None
    return sum(hits) / 1e6 / units


def counter_sum(rec, span_name: str, key: str) -> int | None:
    """Sum of metadata ``key`` over the window's ``span_name`` spans."""
    vals = [m[key] for n, _, _, m in spans(rec) or ()
            if n == span_name and key in m]
    return sum(vals) if vals else None


def efficiency(rec, span_name: str) -> float | None:
    """live / paid row-steps, in %, over the window's ``span_name``."""
    paid = counter_sum(rec, span_name, "row_steps_paid")
    live = counter_sum(rec, span_name, "row_steps_live")
    if not paid:
        return None
    return 100.0 * live / paid
