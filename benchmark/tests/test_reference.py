"""The plain reference: its answers pinned schedule by schedule, its
workloads, history checks and plan specs found by name and added by new
files alone, and unknown names refused."""

from __future__ import annotations

import gzip
import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from cpu import BENCH, ROOT  # noqa: F401  (puts the benchmark on sys.path)

import harness  # noqa: E402
from harness import BenchError  # noqa: E402

PINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "reference_pins.json.gz")


def _sha(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()[:16]


def _config(name: str) -> dict:
    files = {c["name"]: c["file"] for c in harness.load_spec()["configs"]}
    with open(os.path.join(ROOT, files[name])) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pins():
    with gzip.open(PINS, "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("quantum", [1, 1000])
@pytest.mark.parametrize("name", ["raft5", "kvchaos-r4"])
def test_reference_reproduces_the_pinned_answers(pins, name, quantum):
    """Every answer the reference gave before it was split into an engine
    and one file per workload (2,000 raft5 seeds; 500 kvchaos-r4 seeds
    with their CrashStorm rows), exact and at the control's quantum."""
    from checking import reference_answer
    from reference.oracle import Reference
    from reference.plan import compile_plan

    config = _config(name)
    ent = pins["configs"][name]
    ref = Reference(config, time_quantum=quantum)
    fields = pins["fields"]
    for seed, rows_sha, want in zip(ent["seeds"], ent["rows_sha"],
                                    ent["answers"][str(quantum)]):
        rows = compile_plan(config["plan"], seed) if config["plan"] else None
        assert _sha(rows) == rows_sha, seed
        r = ref.run(seed, rows)
        got = dict(r, hist_count=len(r["hist"]), hist_sha=_sha(r["hist"]),
                   violating=reference_answer(ref, config, seed,
                                              rows)["violating"])
        assert [got[k] for k in fields] == want, seed


def _digests(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha256(fh.read()).hexdigest()
    return out


NOOP_STORM = '''"""n disabled NOP rows: they take plan slots and change nothing."""

from reference.plan import KIND_NOP, Stream


def rows(spec, seed, slot):
    st = Stream(seed, slot)
    return [(st.uniform(0, 10**9, i), KIND_NOP, 0, 0, 0, False)
            for i in range(spec["n"])]


def slots(spec):
    return spec["n"]
'''


def _program_raft5_answers(n: int) -> list:
    """The program's answers on n raft5 seeds, as the sweep keeps them."""
    import system
    from madsim_tpu.engine import search_seeds

    s = system.build(_config("raft5"))
    seeds = np.arange(2**40, 2**40 + n, dtype=np.uint64)
    cap = {}

    def invariant(view):
        cap.update(now=np.asarray(view["now"]),
                   halted=np.asarray(view["halted"]))
        return np.ones(n, bool)

    rep = search_seeds(s.wl, s.cfg, invariant, seeds=seeds,
                       max_steps=s.max_steps, compact=True,
                       require_halt=s.require_halt)
    return [{"seed": int(seeds[i]), "trace": int(rep.traces[i]),
             "now": int(cap["now"][i]), "halted": bool(cap["halted"][i]),
             "overflowed": bool(rep.overflowed[i])} for i in range(n)]


def _bench_copy(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".jax_cache", ".build", ".out", "__pycache__"))
    return bench


def test_workload_check_and_plan_spec_added_by_files_alone(tmp_path):
    """A copy of raft registered under another name, a history check and
    a plan spec are added to a copy of the benchmark as new files, with a
    configuration naming all three; no existing file changes, the build
    takes the new workload in, and its answers are the program's."""
    import checking
    from reference.oracle import Reference
    from reference.plan import compile_plan

    bench = _bench_copy(tmp_path)
    before = _digests(bench)
    raft5 = _config("raft5")
    Reference(raft5, bench=str(bench))  # a build without the new files

    ref = bench / "reference"
    (ref / "workloads" / "raftcopy.cpp").write_text(
        (ref / "workloads" / "raft.cpp").read_text().replace(
            'register_workload("raft"', 'register_workload("raftcopy"'))
    shutil.copy(ref / "workloads" / "raft.py", ref / "workloads" / "raftcopy.py")
    (ref / "checks" / "never_clean.py").write_text(
        "def violated(hist):\n    return True\n")
    (ref / "plans" / "NoopStorm.py").write_text(NOOP_STORM)
    conf = dict(raft5, name="raftcopy5",
                plan={"name": "noop", "specs": [{"type": "NoopStorm", "n": 3}]},
                reference=dict(raft5["reference"], workload="raftcopy",
                               checks=["never_clean"]))
    (bench / "configs" / "raftcopy5.json").write_text(json.dumps(conf))

    after = _digests(bench)
    assert {k: after[k] for k in before} == before  # nothing edited

    rows = compile_plan(conf["plan"], 7, bench=str(bench))
    assert rows["kind"] == [7, 7, 7] and rows["valid"] == [False] * 3

    sched = [dict(s, rows=compile_plan(conf["plan"], s["seed"], bench=str(bench)))
             for s in _program_raft5_answers(32)]
    bad, details = checking.compare(conf, sched, bench=str(bench))
    assert bad == 0, details
    r = Reference(conf, bench=str(bench))
    assert all(checking.reference_answer(r, conf, s["seed"], s["rows"])["violating"]
               for s in sched)
    # the parent's own raft, in the original tree, gives the same answers
    orig = Reference(raft5)
    assert [r.run(s["seed"], s["rows"]) for s in sched[:4]] == \
        [orig.run(s["seed"]) for s in sched[:4]]


KEEPER_CPP = """// one node, three columns; bit c of the one param makes column c
// durable. Each init records every column, then writes 7, 8, 9 to them;
// the init after a restart halts.
#include "../sim.h"

namespace benchref {
namespace {
int64_t g_mask = 0;

void keeper(int32_t, const Ctx& ctx, int32_t* ns, Effects* eff) {
  for (int32_t u = 0; u < 3; u++) {
    eff->recs.push_back(Rec{true, 1, u, ctx.state[u], 1});
    ns[u] = 7 + u;
  }
  eff->emits.push_back(mk_after(0, KIND_HALT, 0, 0, ctx.now > 0));
}

bool set_params(const int64_t* p, int64_t n) {
  if (n != 1) return false;
  g_mask = p[0];
  return true;
}

Workload make() {
  Workload wl{1, 3, 1, 1, keeper};
  for (int32_t c = 0; c < 8; c++)
    if ((g_mask >> c) & 1) wl.durable_cols.push_back(c);
  return wl;
}

const bool kRegistered = register_workload("keeper", &set_params, &make);
}  // namespace
}  // namespace benchref
"""

KEEPER_PY = """def pack(params):
    return [params["durable_mask"]]
"""


@pytest.mark.parametrize("mask", [0b000, 0b001, 0b101, 0b111, 0b1000])
def test_durable_columns_survive_a_restart(tmp_path, mask):
    """A workload added as new files declares its durable columns: after
    a kill and a restart the node comes back with those columns as it
    left them and every other column at its initial row (zeros); a
    column outside the state row is refused."""
    from reference.oracle import Reference

    bench = _bench_copy(tmp_path)
    (bench / "reference" / "workloads" / "keeper.cpp").write_text(KEEPER_CPP)
    (bench / "reference" / "workloads" / "keeper.py").write_text(KEEPER_PY)
    raft5 = _config("raft5")
    conf = dict(raft5, max_steps=20, reference={
        "workload": "keeper", "params": {"durable_mask": mask},
        "hist_capacity": 8})
    kill_restart = {"time": [1_000_000, 2_000_000], "kind": [0, 1],
                    "node": [0, 0], "a0": [0, 0], "a1": [0, 0],
                    "valid": [True, True]}
    ref = Reference(conf, bench=str(bench))
    if mask >> 3:
        with pytest.raises(BenchError, match="durable column 3 lies outside"):
            ref.run(5, kill_restart)
        return
    r = ref.run(5, kill_restart)
    assert r["halted"] and r["hist_drop"] == 0
    kept = [7 + u if (mask >> u) & 1 else 0 for u in range(3)]
    assert r["hist"] == ([[1, u, 0, 0, 1] for u in range(3)]
                         + [[1, u, kept[u], 0, 1] for u in range(3)])


@pytest.mark.parametrize("n_writes", [64, 65])
def test_payload_width_up_to_the_cap(n_writes):
    """raftlog carries its whole log in each payload: 64 words run, 65
    are refused with a BenchError that names the width."""
    from reference.oracle import Reference

    params = dict(n_nodes=5, n_writes=n_writes, timeout_min_ns=150_000_000,
                  timeout_max_ns=300_000_000, propose_ns=20_000_000,
                  retx_ns=60_000_000, chaos=True, durable=True, record=False)
    conf = dict(_config("raft5"), max_steps=4000,
                reference={"workload": "raftlog", "params": params})
    ref = Reference(conf)
    if n_writes > 64:
        with pytest.raises(BenchError, match="payload of 65 words is wider "
                                             "than the 64 words"):
            ref.run(3)
    else:
        r = ref.run(3)
        assert r["now"] > 0 and r["msg_count"] > 0


@pytest.mark.parametrize("what", ["workload", "params", "check",
                                  "lease_safety", "plan_spec", "screen"])
def test_unknown_names_are_refused(what):
    """An unknown workload, check, plan spec or screen name is a
    BenchError; so is a check that exists only as a device screen, which
    no floor check may stand in for."""
    from reference.history import clean
    from reference.oracle import Reference
    from reference.plan import compile_plan

    raft5 = _config("raft5")
    with pytest.raises(BenchError) as e:
        if what == "workload":
            Reference(dict(raft5, reference=dict(raft5["reference"],
                                                 workload="nosuch")))
        elif what == "params":
            ref = Reference(raft5)
            ref.params = ref.params[:2]
            ref.run(1)
        elif what == "check":
            clean([], ["stale_reads", "no_such_check"])
        elif what == "lease_safety":
            clean([[1, 0, 1, 0, 1]], ["lease_safety"])
        elif what == "plan_spec":
            compile_plan({"specs": [{"type": "NoSuchStorm", "n": 1}]}, 1)
        else:
            import system
            system.build(dict(raft5, screens=["no_such_screen"]))
    if what == "workload":
        assert "nosuch.cpp" in str(e.value) and "nosuch.py" in str(e.value)


def test_screen_with_arguments():
    """A ``screens`` entry may give a screen's arguments."""
    import system
    from madsim_tpu.check import device as dc

    s = system.build(dict(_config("raft5"), screens=[
        {"name": "election_safety", "args": {"elect_op": 3}},
        "stale_reads"]))
    assert s.screens == (dc.election_safety(3), dc.stale_reads())
