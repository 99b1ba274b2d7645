"""The trace reduction, on a hand-built trace and on a recorded one."""

from __future__ import annotations

import gzip
import json
import os

import pytest

import cpu  # noqa: F401  (puts the benchmark on sys.path)
import harness
import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


def _events():
    ms = 1_000_000
    host = [["bench.window", 0, 100 * ms],
            ["bench.batch", 0, 50 * ms], ["bench.invariant", 40 * ms, 10 * ms],
            ["bench.batch", 50 * ms, 50 * ms]]
    ops = [_Ev("fusion.1 = f32[8] fusion(...)", 5 * ms, 20 * ms),
           _Ev("fusion.2", 20 * ms, 10 * ms),
           _Ev("all-reduce.3", 60 * ms, 5 * ms), _Ev("fusion.1", 70 * ms, 20 * ms),
           _Ev("fusion.9", 120 * ms, 5 * ms)]  # after the window
    busy, op_ns = tracing._fold_ops(ops, 0, 100 * ms)
    chip = {"busy": busy, "ops": op_ns,
            "modules": [["jit_compiled(7)", 5 * ms, 25 * ms],
                        ["jit_compiled(7)", 60 * ms, 30 * ms]]}
    return {"device": {"/device:TPU:0": chip}, "host": host}


def test_reduce_hand_built_trace():
    red = tracing.reduce(_events())
    assert red["window_s"] == pytest.approx(0.1)
    # busy: [5, 30) + [60, 65) + [70, 90) ms = 50 ms
    assert red["busy_s"] == pytest.approx(0.05)
    assert red["collective_s"] == pytest.approx(0.005)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.04)]
    gaps = {(n, round(s, 4)) for n, s in red["idle_gaps"]}
    # the 30-60 ms gap's midpoint (45 ms) lies inside bench.invariant
    assert ("bench.invariant", 0.03) in gaps
    assert ("bench.batch", 0.01) in gaps  # 90-100 ms, second batch
    sec, count = tracing.module_time(red, "jit_compiled")
    assert (sec, count) == (pytest.approx(0.055), 2)
    sec, count = tracing.module_time(red, "jit_compiled", 50_000_000, 100_000_000)
    assert (sec, count) == (pytest.approx(0.03), 1)


def test_readers_on_hand_built_trace():
    red = tracing.reduce(_events())
    rec = {"kind": "sweep", "trace": red, "units": [], "chips": 1}
    idle = harness.load_reader("idle_share.sweep").read(rec)
    assert idle == pytest.approx(50.0)
    assert harness.load_reader("runner_device_ms.sweep").read(rec) == \
        pytest.approx(27.5)
    # batch 1: 50 - 25 ms, batch 2: 50 - 30 ms
    assert harness.load_reader("host_ms_per_batch.sweep").read(rec) == \
        pytest.approx(22.5)
    assert harness.load_reader("idle_share.campaign").read(rec) is None


def test_trace_without_a_window_is_refused():
    ev = _events()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        tracing.reduce(ev)


@pytest.mark.parametrize("name", sorted(
    f for f in (os.listdir(DATA) if os.path.isdir(DATA) else [])
    if f.endswith(".json.gz") and f != "reference_pins.json.gz"))
def test_recorded_chip_trace(name):
    """A trimmed copy of a traced chip run and the numbers that run's
    reduction gave (kept beside it)."""
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        rec = json.load(f)
    red = tracing.reduce(rec["events"])
    for key, want in rec["expect"].items():
        assert red[key] == pytest.approx(want, rel=1e-9), key
    # the readers find the runner program by its name in a chip trace
    run = {"kind": "sweep", "trace": red, "units": [], "chips": 1}
    runner = harness.load_reader("runner_device_ms.sweep").read(run)
    host = harness.load_reader("host_ms_per_batch.sweep").read(run)
    assert runner > 0 and host > 0
    assert 0 < harness.load_reader("idle_share.sweep").read(run) < 100
