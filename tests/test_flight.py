"""Campaign flight recorder (obs/flight.py + obs/prof.py) — the
telemetry schema, the profiler, and the generation-program cache.

Pins, per the round's contract: every generation record carries the
full wall-split keys (compile split OUT of dispatch on both drivers);
heartbeats are monotone and interleave with generation records; the
campaign Perfetto export has exactly one generation span per
generation and monotone counter tracks; the profiler retrace counter
pins (same cache key across campaigns -> no retrace; changed space ->
exactly one); and the flight-recorder on/off bit-identity across both
drivers. Soak-scale certificates live in tools/flight_soak.py
(FLIGHT_r08.txt)."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from madsim_tpu import explore, obs
from madsim_tpu.chaos import FaultPlan, GrayFailure, PauseStorm
from madsim_tpu.engine import Build, EngineConfig, search_seeds
from madsim_tpu.explore import device as _device
from madsim_tpu.models import make_raft
from madsim_tpu.obs import prof

NODES = (0, 1, 2, 3, 4)
CFG = EngineConfig(pool_size=64, loss_p=0.02)
PLAN = FaultPlan((
    PauseStorm(targets=NODES, n=1, t_min_ns=20_000_000,
               t_max_ns=300_000_000, down_min_ns=50_000_000,
               down_max_ns=200_000_000),
    GrayFailure(targets=NODES, n_links=1),
), name="flight-test")


def _halt_inv(view):
    return view["halted"]


# ONE workload + invariant object across the module: program caches key
# on identity (the engine.search rule), which is also what the
# multi-campaign retrace pin needs
WL = make_raft()
KW = dict(generations=3, batch=16, root_seed=11, max_steps=200,
          cov_words=8, invariant=_halt_inv)

DEVICE_WALL_KEYS = ("dispatch_wall_s", "compile_wall_s", "sync_wall_s",
                    "queue_wall_s", "idle_wall_s")
HOST_WALL_KEYS = ("dispatch_wall_s", "compile_wall_s", "mutate_wall_s",
                  "admit_wall_s", "host_wall_s",
                  "queue_wall_s", "idle_wall_s")


def _fp(rep):
    return (
        [(e.id, e.generation, e.parent, e.seed, e.plan.hash(), e.trace,
          e.new_bits) for e in rep.corpus],
        rep.cov_map.tolist(),
        [(e.seed, e.trace) for e in rep.violations],
        rep.curve,
        rep.viol_curve,
    )


# lazily computed shared results (tier-1 wall is a budgeted resource):
# the baseline device/host campaigns with no telemetry, and one flight-
# recorded device campaign (records captured in-memory)
_SHARED: dict = {}


def _rep_off(driver):
    key = f"off-{driver}"
    if key not in _SHARED:
        runner = explore.run_device if driver == "device" else explore.run
        _SHARED[key] = runner(WL, CFG, PLAN, **KW)
    return _SHARED[key]


def _flight_records():
    """One flight-recorded device campaign from a COLD program cache
    (so compile events are present), records captured in-memory."""
    if "records" not in _SHARED:
        _device._GEN_CACHE.clear()
        records = []
        with obs.FlightRecorder(records.append, heartbeat_s=0.0) as fr:
            _SHARED["rep-flight"] = explore.run_device(
                WL, CFG, PLAN, telemetry=fr, **KW
            )
        _SHARED["records"] = records
    return _SHARED["records"]


# ---------------------------------------------------------------------------
# obs.prof units
# ---------------------------------------------------------------------------


def test_aot_program_build_and_retrace_counting():
    p = prof.AotProgram("t.unit", ("k", 1), lambda x: (x * 2).sum())
    with prof.profiled() as session:
        out = p(jnp.ones((8, 8)))
        assert float(out) == 128.0
        assert p.builds == 1 and p.last_build_s > 0
        out2 = p(jnp.ones((8, 8)))  # warm: same signature
        assert float(out2) == 128.0
        assert p.builds == 1 and p.last_build_s == 0.0
        p(jnp.ones((4, 4)))  # new signature -> counted retrace
        assert p.builds == 2
        rec = session.programs[("t.unit", p.key)]
        assert rec.traces == 2 and rec.calls == 3
        assert rec.compile_wall_s > 0 and rec.execute_wall_s > 0
        # cost analysis + memory footprint landed at build time
        assert rec.flops > 0 and rec.arg_bytes > 0
        events = session.pop_events()
        assert len(events) == 2 and events[0]["program"] == "t.unit"
        assert session.pop_events() == []
        assert "t.unit" in session.report()
    assert prof.current() is None  # profiled() restored the state


def test_aot_program_matches_jit_bit_exact():
    fn = lambda x: jnp.sin(x).sum()  # noqa: E731
    x = jnp.linspace(0.0, 5.0, 257)
    aot = prof.AotProgram("t.bit", "k", fn)(x)
    assert np.asarray(aot) == np.asarray(jax.jit(fn)(x))


def test_device_memory_accounting():
    keep = jnp.arange(1024, dtype=jnp.int32)  # a buffer we know is live
    mem = prof.device_memory()
    assert mem["live_buffers"] >= 1
    assert mem["live_buffer_bytes"] >= keep.nbytes


def test_search_report_build_wall_split():
    wl = make_raft()  # fresh identity: guaranteed cold run cache
    inv = lambda v: np.ones(np.asarray(v["halted"]).shape[0], bool)  # noqa: E731
    r1 = search_seeds(wl, CFG, inv, n_seeds=8, max_steps=64)
    r2 = search_seeds(wl, CFG, inv, n_seeds=8, max_steps=64)
    assert r1.build_wall_s > 0.0  # cold: trace+lower+compile measured
    assert r2.build_wall_s == 0.0  # warm: pure execution
    assert np.array_equal(r1.traces, r2.traces)


# ---------------------------------------------------------------------------
# the generation-program cache (the tentpole pin)
# ---------------------------------------------------------------------------


def test_retraces_once_across_three_campaigns():
    _device._GEN_CACHE.clear()
    with prof.profiled() as p:
        reps = [
            explore.run_device(WL, CFG, PLAN, **{**KW, "root_seed": rs})
            for rs in (11, 12, 13)
        ]
    retr = p.retraces("explore.device")
    # one uniform + one breed program, each traced EXACTLY once for the
    # whole session (was: one full rebuild per campaign)
    assert sorted(k[0] for k in retr) == [
        "explore.device.breed", "explore.device.uniform",
    ]
    assert all(v == 1 for v in retr.values())
    assert reps[0].wall_compile_s > 0.0
    assert reps[1].wall_compile_s == 0.0
    assert reps[2].wall_compile_s == 0.0
    # root seed is a runtime argument, not a baked constant: different
    # roots through one program still diverge
    assert _fp(reps[0]) != _fp(reps[1])
    _SHARED["off-device"] = reps[0]  # root 11 == KW's campaign


def test_changed_space_retraces_exactly_once():
    plan2 = FaultPlan((
        PauseStorm(targets=NODES, n=1, t_min_ns=20_000_000,
                   t_max_ns=300_000_000, down_min_ns=50_000_000,
                   down_max_ns=200_000_000),
    ), name="flight-test-2")
    explore.run_device(WL, CFG, PLAN, **KW)  # warm the original key
    with prof.profiled() as p:
        explore.run_device(WL, CFG, PLAN, **KW)  # cache hit: no build
        explore.run_device(
            WL, CFG, plan2, **{**KW, "generations": 1}
        )  # new space hash -> exactly one uniform build
    retr = p.retraces("explore.device")
    # the cache-hit campaign executed through existing programs
    # (records with traces == 0); only the new space hash built — and
    # exactly once, its uniform program (generations=1 never breeds)
    assert sum(retr.values()) == 1
    built = [k[0] for k, v in retr.items() if v > 0]
    assert built == ["explore.device.uniform"]


def test_flight_on_off_bit_identity_device():
    _flight_records()  # the flight-recorded campaign (profiler armed)
    assert _fp(_rep_off("device")) == _fp(_SHARED["rep-flight"])


def test_flight_on_off_bit_identity_host(tmp_path):
    off = _rep_off("host")
    path = tmp_path / "host.jsonl"
    with obs.FlightRecorder(str(path), heartbeat_s=0.0) as fr:
        on = explore.run(WL, CFG, PLAN, telemetry=fr, **KW)
    assert _fp(off) == _fp(on)
    _SHARED["host-jsonl"] = [
        json.loads(line) for line in path.read_text().splitlines()
    ]


# ---------------------------------------------------------------------------
# telemetry schema + heartbeats
# ---------------------------------------------------------------------------


def test_device_generation_records_carry_wall_split():
    recs = _flight_records()
    gens = [r for r in recs if r["event"] == "generation"]
    assert len(gens) == KW["generations"]
    for g in gens:
        for k in DEVICE_WALL_KEYS:
            assert k in g, f"missing {k}"
        assert g["host_syncs"] == 1
        # the pipeline split exists on BOTH drivers; blocking emits 0s
        assert g["queue_wall_s"] == 0.0 and g["idle_wall_s"] == 0.0
    # the cold generation paid the build; warm generations are
    # compile-free — the split the old accounting hid inside dispatch
    assert gens[0]["compile_wall_s"] > 0
    assert gens[-1]["compile_wall_s"] == 0.0
    end = next(r for r in recs if r["event"] == "campaign_end")
    assert {"wall_dispatch_s", "wall_compile_s", "wall_sync_s",
            "wall_queue_s", "wall_idle_s"} <= set(end)
    assert end["wall_queue_s"] == 0.0 and end["wall_idle_s"] == 0.0


def test_host_generation_records_carry_wall_split(tmp_path):
    if "host-jsonl" not in _SHARED:
        test_flight_on_off_bit_identity_host(tmp_path)
    gens = [
        r for r in _SHARED["host-jsonl"] if r["event"] == "generation"
    ]
    assert len(gens) == KW["generations"]
    for g in gens:
        for k in HOST_WALL_KEYS:
            assert k in g, f"missing {k}"


def test_heartbeats_monotone_and_interleaved():
    recs = _flight_records()
    assert [r["seq"] for r in recs] == list(range(len(recs)))
    hbs = [r for r in recs if r["event"] == "heartbeat"]
    assert len(hbs) == KW["generations"]  # heartbeat_s=0: one per gen
    done = [h["generations_done"] for h in hbs]
    assert done == sorted(done) == [1, 2, 3]
    ts = [r["t_s"] for r in recs]
    assert ts == sorted(ts)
    # interleave: each heartbeat lands directly after its generation
    events = [r["event"] for r in recs]
    for i, ev in enumerate(events):
        if ev == "heartbeat":
            assert events[i - 1] == "generation"
    assert hbs[0]["gens_per_s"] > 0
    assert hbs[0]["live_buffer_bytes"] > 0  # the memory tap
    # compile events (profiler builds) precede the generation they
    # delayed, and the summary closes the log
    assert events[-1] == "flight_summary"
    summary = recs[-1]
    names = {p["name"] for p in summary["programs"]}
    assert "explore.device.uniform" in names
    assert "memory" in summary


# ---------------------------------------------------------------------------
# campaign Perfetto
# ---------------------------------------------------------------------------


def test_campaign_perfetto_spans_and_counters(tmp_path):
    recs = _flight_records()
    doc = obs.campaign_perfetto(recs)
    spans = [
        e for e in doc["traceEvents"] if e.get("cat") == "generation"
    ]
    assert len(spans) == KW["generations"]  # span count == generations
    assert doc["otherData"]["generations"] == KW["generations"]
    for name in ("cov_bits", "violations", "corpus_size"):
        track = [
            e["args"][name] for e in doc["traceEvents"]
            if e.get("ph") == "C" and e.get("name") == name
        ]
        assert len(track) == KW["generations"]
        assert track == sorted(track), f"{name} track not monotone"
    assert any(e.get("cat") == "compile" for e in doc["traceEvents"])
    assert any(e.get("name") == "live_buffer_bytes"
               for e in doc["traceEvents"])
    # sub-spans stay inside their generation span
    phases = [e for e in doc["traceEvents"] if e.get("cat") == "phase"]
    assert phases
    for ph in phases:
        parent = next(
            s for s in spans
            if s["ts"] - 1 <= ph["ts"]
            and ph["ts"] + ph["dur"] <= s["ts"] + s["dur"] + 1
        )
        assert parent is not None
    # the file path form (incl. a torn last line) reads identically
    path = tmp_path / "c.jsonl"
    with open(path, "w") as fh:
        for r in recs:
            fh.write(json.dumps(r) + "\n")
        fh.write('{"event": "generation", "torn')  # crashed mid-write
    doc2 = obs.campaign_perfetto(str(path))
    assert doc2["otherData"]["generations"] == KW["generations"]


def test_jsonl_sink_flushes_and_fsyncs(tmp_path):
    path = tmp_path / "s.jsonl"
    sink = obs.JsonlSink(str(path), fsync=True)
    sink({"event": "generation", "generation": 0})
    # readable BEFORE close: per-record flush is the crash contract
    assert json.loads(path.read_text())["generation"] == 0
    sink({"event": "campaign_end"})
    assert len(path.read_text().splitlines()) == 2
    sink.close()


# ---------------------------------------------------------------------------
# the flight boundary (lint matrix entry)
# ---------------------------------------------------------------------------


def test_flight_taps_never_enter_traced_code():
    from madsim_tpu.lint.noninterference import (
        FLIGHT_AXES,
        check_noninterference,
    )

    flags = dict(FLIGHT_AXES["flight-campaign"])
    assert flags.pop("flight") is True
    base = check_noninterference(
        WL, CFG, Build(layout="scatter", time32=False, **flags), entry="run",
    )
    armed = check_noninterference(
        WL, CFG, Build(layout="scatter", time32=False, **flags), entry="run",
        flight=True,
    )
    assert base.ok and armed.ok
    assert armed.callback_prims == []
    assert armed.flags["flight"] is True
    # profiler active vs not: the traced program is THE SAME program
    assert armed.n_eqns == base.n_eqns


# ---------------------------------------------------------------------------
# campaign_top
# ---------------------------------------------------------------------------


def test_campaign_top_renders_live_and_finished():
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    try:
        import campaign_top
    finally:
        sys.path.pop(0)
    recs = _flight_records()
    frame = campaign_top.render(recs, "x.jsonl")
    assert "raft" in frame and "3/3 generations" in frame
    assert "coverage" in frame and "violations" in frame
    assert "compile" in frame  # the wall split made it to the screen
    assert "programs (flight summary):" in frame
    # a live (mid-campaign, no end record) log still renders
    live = [r for r in recs if r["event"] not in
            ("campaign_end", "flight_summary")][:3]
    frame2 = campaign_top.render(live)
    assert "running" in frame2
    # and the file reader tolerates a torn tail
    assert campaign_top.read_records("/nonexistent.jsonl") == []
