"""The readers of the program's own spans and counters (program_trace):
on hand-built spans, on a real CPU trace, and silent where the program
has none."""

from __future__ import annotations

import glob
import gzip
import json
import os

import numpy as np
import pytest

import cpu  # noqa: F401  (puts the benchmark on sys.path)
import harness
import program_trace
import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000
NEW = ["init_ms.sweep", "plan_ms.sweep", "fetch_ms.sweep", "fetch_mb.sweep",
       "check_ms.sweep", "lockstep_eff.sweep", "lockstep_eff.campaign",
       "edge_ms.campaign"]


def _sweep_rec():
    """Two 50 ms batches; each runs search_seeds with its five stages."""
    spans = []
    for b, meta in ((0, (3_000_000, 400, 100)), (50, (1_000_000, 600, 500))):
        t = b * MS
        d2h, paid, live = meta
        spans += [
            ["madsim.search", t + 1 * MS, t + 49 * MS,
             {"d2h_bytes": d2h, "row_steps_paid": paid,
              "row_steps_live": live}],
            ["madsim.search.plan", t + 1 * MS, t + 3 * MS, {}],
            ["madsim.search.init", t + 3 * MS, t + 7 * MS, {}],
            ["madsim.search.run", t + 7 * MS, t + 30 * MS, {}],
            ["madsim.search.fetch", t + 30 * MS, t + 40 * MS, {}],
            ["madsim.search.check", t + 40 * MS, t + 49 * MS, {}],
        ]
    trace = {"spans": {"bench.window": [[0, 100 * MS]],
                       "bench.batch": [[0, 50 * MS], [50 * MS, 100 * MS]]}}
    return {"kind": "sweep", "trace": trace, "units": [],
            program_trace.KEY: spans}


def _campaign_rec():
    """One campaign of two generations, with its two edges."""
    spans = [
        ["madsim.explore.start", 0, 40 * MS, {}],
        ["madsim.explore.generation", 40 * MS, 60 * MS, {"g": 0}],
        ["madsim.explore.sync", 58 * MS, 60 * MS,
         {"row_steps_paid": 1000, "row_steps_live": 250}],
        ["madsim.explore.generation", 60 * MS, 80 * MS, {"g": 1}],
        ["madsim.explore.sync", 78 * MS, 80 * MS,
         {"row_steps_paid": 3000, "row_steps_live": 1750}],
        ["madsim.explore.materialize", 80 * MS, 95 * MS, {}],
    ]
    trace = {"spans": {"bench.window": [[0, 100 * MS]],
                       "bench.campaign": [[0, 100 * MS]]}}
    return {"kind": "campaign", "trace": trace, "units": [],
            program_trace.KEY: spans}


def _read(name, rec):
    return harness.load_reader(name).read(rec)


def test_sweep_readers_on_hand_built_spans():
    rec = _sweep_rec()
    assert _read("init_ms.sweep", rec) == pytest.approx(4.0)
    assert _read("plan_ms.sweep", rec) == pytest.approx(2.0)
    assert _read("fetch_ms.sweep", rec) == pytest.approx(10.0)
    assert _read("check_ms.sweep", rec) == pytest.approx(9.0)
    assert _read("fetch_mb.sweep", rec) == pytest.approx(2.0)  # 4 MB / 2
    assert _read("lockstep_eff.sweep", rec) == pytest.approx(60.0)  # 600/1000
    assert _read("lockstep_eff.campaign", rec) is None
    assert _read("edge_ms.campaign", rec) is None


def test_campaign_readers_on_hand_built_spans():
    rec = _campaign_rec()
    assert _read("edge_ms.campaign", rec) == pytest.approx(55.0)
    assert _read("lockstep_eff.campaign", rec) == pytest.approx(50.0)
    assert _read("init_ms.sweep", rec) is None


def test_a_gap_is_named_by_the_innermost_program_span():
    """Were the program spans among the reduction's host spans (kept
    beside bench.*), the idle gap in a batch's fetch would be named by
    madsim.search.fetch, not by the bench.batch around it."""
    rec = _sweep_rec()
    host = [["bench.window", 0, 100 * MS], ["bench.batch", 0, 50 * MS],
            ["bench.batch", 50 * MS, 50 * MS]]
    host += [[n, s, e - s] for n, s, e, _ in rec[program_trace.KEY]]
    ev = {"host": host, "device": {"/device:TPU:0": {
        "busy": [[5 * MS, 25 * MS], [45 * MS, 99 * MS]], "ops": {"w": 1},
        "modules": []}}}
    name, secs = tracing.reduce(ev)["idle_gaps"][0]  # 25-45 ms
    assert name == "madsim.search.fetch" and secs == pytest.approx(0.02)


def test_readers_are_silent_without_program_spans(tmp_path, monkeypatch):
    """The parent program opens no madsim.* span: a traced run's record
    finds none and each new reader leaves its metric out; so does a run
    without a trace."""
    monkeypatch.setattr(program_trace, "OUT", str(tmp_path))
    for rec in (dict(_sweep_rec(), **{program_trace.KEY: []}),
                dict(_campaign_rec(), **{program_trace.KEY: []}),
                {"kind": "sweep", "trace": None, "units": []},
                {"kind": "campaign", "trace": None, "units": []}):
        for name in NEW:
            assert _read(name, rec) is None, name
    # a recorded chip trace of the parent: no trace file to read either
    for path in glob.glob(os.path.join(DATA, "*.json.gz")):
        if path.endswith("reference_pins.json.gz"):
            continue
        with gzip.open(path, "rt") as f:
            red = tracing.reduce(json.load(f)["events"])
        rec = {"kind": "sweep", "trace": red, "units": [], "chips": 1}
        for name in NEW:
            assert _read(name, rec) is None, name


def test_readers_find_the_spans_in_the_runs_trace(tmp_path, monkeypatch):
    """A real profiler trace (CPU) around a small sweep: the reader
    finds the file by its bench.window and reads the program's spans and
    counters from it."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    from madsim_tpu.engine import EngineConfig, search_seeds
    from madsim_tpu.models import make_raft

    wl, cfg = make_raft(), EngineConfig(pool_size=40)
    seeds = np.arange(32, dtype=np.uint64)

    def batch():
        return search_seeds(wl, cfg, lambda v: v["halted"], seeds=seeds,
                            max_steps=600, compact=True)

    batch()  # compile outside the trace
    out = tmp_path / "cell"
    jax.profiler.start_trace(str(out))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.batch"):
            rep = batch()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    s = int(e.start_ns)
                    spans[e.name] = [[s, s + int(e.duration_ns)]]
    rec = {"kind": "sweep", "trace": {"spans": spans}, "units": []}
    monkeypatch.setattr(program_trace, "OUT", str(tmp_path))
    assert _read("fetch_mb.sweep", rec) == pytest.approx(rep.d2h_bytes / 1e6)
    assert _read("lockstep_eff.sweep", rec) == pytest.approx(
        100 * rep.row_steps_live / rep.row_steps_paid)
    stages = [_read(f"{s}_ms.sweep", rec)
              for s in ("plan", "init", "fetch", "check")]
    assert all(x is not None and x >= 0 for x in stages)
    assert sum(stages) < (spans["bench.batch"][0][1]
                          - spans["bench.batch"][0][0]) / 1e6
