"""Program spans and host-path counters.

``obs.prof.span`` puts ``madsim.*`` spans on the profiler's clock at the
layer boundaries of ``search_seeds`` and the device campaign drivers;
``SearchReport`` / ``ExploreReport`` carry the counters beside them:
``d2h_bytes`` and ``d2h_transfers`` (what crossed device->host) and
``row_steps_paid`` / ``row_steps_live`` (rows x loop iterations the
runner paid, and those whose row had not yet halted). These tests read
a real profiler trace back, recount the row-steps on the host from
per-iteration halted masks, spy on every device->host copy, and pin
that neither the spans nor the counters change a trace, a verdict or a
corpus entry.
"""

import glob

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from madsim_tpu import explore
from madsim_tpu.chaos import CrashStorm, FaultPlan, GrayFailure, PauseStorm
from madsim_tpu.check import device as dc
from madsim_tpu.engine import (
    Build,
    EngineConfig,
    make_init,
    make_run_compacted,
    make_run_while,
    search_seeds,
)
from madsim_tpu.engine.compact import _phase_sizes, row_steps
from madsim_tpu.engine.core import make_step
from madsim_tpu.models import make_kvchaos, make_raft
from madsim_tpu.parallel import make_mesh

RAFT_CFG = EngineConfig(pool_size=64, loss_p=0.02)
RAFT_PLAN = FaultPlan((
    PauseStorm(targets=(0, 1, 2, 3, 4), n=1, t_min_ns=20_000_000,
               t_max_ns=300_000_000, down_min_ns=50_000_000,
               down_max_ns=200_000_000),
    GrayFailure(targets=(0, 1, 2, 3, 4), n_links=1),
), name="spans-test")
KV_CFG = EngineConfig(pool_size=40, loss_p=0.02,
                      clog_backoff_max_ns=2_000_000_000)
KV_PLAN = FaultPlan((CrashStorm(targets=(1, 2, 3, 4), n=2),), name="kv")
KV_SCREENS = (dc.stale_reads(), dc.read_your_writes())
KV_WL = make_kvchaos(writes=5, record=True, bug=True)
RAFT_WL = make_raft()
CAMPAIGN = dict(generations=2, batch=16, root_seed=23, max_steps=600,
                cov_words=16, invariant=lambda v: v["halted"])


# ---------------------------------------------------------------------------
# host recount of the row-steps
# ---------------------------------------------------------------------------


def _lives(wl, cfg, seeds, max_steps, rows=None, dup_rows=False):
    """Rows not yet halted before each iteration of a lockstep loop over
    ``seeds``, stepped one iteration at a time on the host's loop."""
    slots = 0 if rows is None else np.asarray(rows.time).shape[1]
    init = make_init(wl, cfg, Build(plan_slots=slots))
    st = init(seeds) if rows is None else init(seeds, rows)
    step = jax.jit(jax.vmap(make_step(wl, cfg, Build(dup_rows=dup_rows))))
    lives = []
    while len(lives) < max_steps:
        live = int((~np.asarray(st.halted)).sum())
        if live == 0:
            break
        lives.append(live)
        st = step(st)
    return lives


def _lockstep_count(lives, n_rows):
    return n_rows * len(lives), sum(lives)


def _compacted_count(lives, sizes, max_steps):
    """The compacted runner's phases replayed over the live counts:
    phase k steps at its size while more rows live than the next."""
    paid = live = i = 0
    for size, nxt in zip(sizes, sizes[1:] + [0]):
        while i < max_steps and (lives[i] if i < len(lives) else 0) > nxt:
            paid += size
            live += lives[i]
            i += 1
    return paid, live


@pytest.mark.parametrize("max_steps", [600, 9], ids=["halting", "capped"])
@pytest.mark.parametrize("runner", ["lockstep", "compacted",
                                    "search_lockstep", "search_compact"])
def test_row_steps_equal_host_recount(runner, max_steps):
    seeds = np.arange(64, dtype=np.uint64)
    lives = _lives(RAFT_WL, RAFT_CFG, seeds, max_steps)
    assert lives[0] == 64 and len(lives) > 1
    if runner == "lockstep":
        _, counts = jax.jit(make_run_while(RAFT_WL, RAFT_CFG, max_steps)
                            .counted)(make_init(RAFT_WL, RAFT_CFG)(seeds))
        got = tuple(int(x) for x in counts)
        want = _lockstep_count(lives, 64)
    elif runner == "compacted":
        run = make_run_compacted(RAFT_WL, RAFT_CFG, max_steps, shrink=2,
                                 min_size=8)
        got = row_steps(run.compute(make_init(RAFT_WL, RAFT_CFG)(seeds)))
        want = _compacted_count(lives, _phase_sizes(64, 2, 8), max_steps)
    else:
        rep = search_seeds(RAFT_WL, RAFT_CFG, lambda v: v["halted"],
                           seeds=seeds, max_steps=max_steps,
                           compact=runner == "search_compact")
        got = (rep.row_steps_paid, rep.row_steps_live)
        want = _lockstep_count(lives, 64)  # 64 rows: a single phase
    assert got == want
    assert 0 < got[1] <= got[0]


def _campaign_children(monkeypatch, **kw):
    """(seeds, plan rows) of every generation, as the host driver — whose
    campaigns are bit-identical to the device driver's — sweeps them."""
    from madsim_tpu.explore import driver

    seen = []
    real = driver.search_seeds

    def spy(*a, **k):
        seen.append((np.asarray(k["seeds"]), k["plan_rows"]))
        return real(*a, **k)

    monkeypatch.setattr(driver, "search_seeds", spy)
    host = explore.run(RAFT_WL, RAFT_CFG, RAFT_PLAN, **kw)
    monkeypatch.undo()
    return host, seen


def test_mesh_campaign_row_steps_equal_host_recount(monkeypatch):
    """On a 2-device mesh each shard loops until its own rows halt: the
    campaign's counters are the per-shard recounts summed over shards
    and generations, and the host driver's own counters (one lockstep
    loop over the whole batch) recount the same way."""
    host, children = _campaign_children(monkeypatch, **CAMPAIGN)
    mesh = make_mesh(jax.devices()[:2])
    dev = explore.run_device(RAFT_WL, RAFT_CFG, RAFT_PLAN, mesh=mesh,
                             **CAMPAIGN)
    assert len(children) == CAMPAIGN["generations"]
    half = CAMPAIGN["batch"] // 2
    shard = [0, 0]
    whole = [0, 0]
    for seeds, rows in children:
        for lo in (0, half):
            part = jax.tree.map(lambda a: np.asarray(a)[lo:lo + half], rows)
            lives = _lives(RAFT_WL, RAFT_CFG, seeds[lo:lo + half], 600,
                           rows=part, dup_rows=RAFT_PLAN.uses_dup())
            for i, x in enumerate(_lockstep_count(lives, half)):
                shard[i] += x
        lives = _lives(RAFT_WL, RAFT_CFG, seeds, 600, rows=rows,
                       dup_rows=RAFT_PLAN.uses_dup())
        for i, x in enumerate(_lockstep_count(lives, len(seeds))):
            whole[i] += x
    assert (dev.row_steps_paid, dev.row_steps_live) == tuple(shard)
    assert (host.row_steps_paid, host.row_steps_live) == tuple(whole)
    # a row is live for the same iterations however the batch is split
    assert dev.row_steps_live == host.row_steps_live
    assert 0 < dev.row_steps_live <= dev.row_steps_paid <= host.row_steps_paid


# ---------------------------------------------------------------------------
# bytes device -> host
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["raft_compact", "kv_compact_screened",
                                  "kv_lockstep_screened"])
def test_d2h_bytes_equal_what_crossed(case, monkeypatch):
    """Every device->host copy goes through ``jax.Array._value`` once
    the CPU backend's zero-copy buffer export is off (as device memory
    has none); the spy sums each copied array's nbytes once (a fetched
    array keeps its host copy), which is what the report must count."""
    from jax._src.array import ArrayImpl

    crossed = {}
    real = ArrayImpl._value

    def spy(self):
        crossed[id(self)] = (self, int(self.nbytes))  # held: ids unique
        return real.fget(self)

    def no_buffer(self, flags):
        raise BufferError("device memory exports no buffer")

    seeds = np.arange(32, dtype=np.uint64)
    if case == "raft_compact":
        kw = dict(compact=True)
        wl, cfg, inv = RAFT_WL, RAFT_CFG, (lambda v: v["halted"])
    else:
        kw = dict(plan=KV_PLAN, device_check=KV_SCREENS,
                  compact=case == "kv_compact_screened")
        wl, cfg, inv = KV_WL, KV_CFG, None
    search_seeds(wl, cfg, inv, seeds=seeds, max_steps=600, **kw)  # warm
    monkeypatch.setattr(ArrayImpl, "_value", property(spy))
    monkeypatch.setattr(ArrayImpl, "__buffer__", no_buffer)
    rep = search_seeds(wl, cfg, inv, seeds=seeds, max_steps=600, **kw)
    monkeypatch.undo()
    assert rep.d2h_bytes == sum(n for _, n in crossed.values()) > 0
    if case != "raft_compact":
        assert len(rep.flagged_idx), "the planted bug must flag seeds"


@pytest.mark.parametrize("case, most", [("raft_compact", 1),
                                        ("kv_compact_screened", 3)])
def test_compacted_results_cross_in_one_buffer(case, most, monkeypatch):
    """A compacted sweep's results cross packed in one buffer (plus,
    under screens, the two trimmed history columns): ``d2h_transfers``
    counts each device array the spy saw copied."""
    from jax._src.array import ArrayImpl

    crossed = {}
    real = ArrayImpl._value

    def spy(self):
        crossed[id(self)] = self  # held: ids unique
        return real.fget(self)

    def no_buffer(self, flags):
        raise BufferError("device memory exports no buffer")

    if case == "raft_compact":
        wl, cfg, inv, kw = RAFT_WL, RAFT_CFG, (lambda v: v["halted"]), {}
    else:
        wl, cfg, inv = KV_WL, KV_CFG, None
        kw = dict(plan=KV_PLAN, device_check=KV_SCREENS)
    run = dict(seeds=np.arange(64, dtype=np.uint64), max_steps=600,
               compact=True, **kw)
    search_seeds(wl, cfg, inv, **run)  # warm
    monkeypatch.setattr(ArrayImpl, "_value", property(spy))
    monkeypatch.setattr(ArrayImpl, "__buffer__", no_buffer)
    rep = search_seeds(wl, cfg, inv, **run)
    monkeypatch.undo()
    assert rep.d2h_transfers == len(crossed) <= most


# ---------------------------------------------------------------------------
# spans in a real trace
# ---------------------------------------------------------------------------

_RUNS: dict = {}


def _runs(tmp_path_factory):
    """One compacted kvchaos search and one 2-generation raft campaign,
    each run untraced and then inside a profiler trace."""
    if _RUNS:
        return _RUNS
    seeds = np.arange(64, dtype=np.uint64)

    def search():
        return search_seeds(KV_WL, KV_CFG, None, seeds=seeds, max_steps=600,
                            compact=True, plan=KV_PLAN,
                            device_check=KV_SCREENS)

    def campaign():
        return explore.run_device(RAFT_WL, RAFT_CFG, RAFT_PLAN, **CAMPAIGN)

    _RUNS["plain"] = (search(), campaign())
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        _RUNS["traced"] = (search(), campaign())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith("madsim.")]
    _RUNS["spans"] = sorted(spans, key=lambda s: s[1])
    return _RUNS


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_search_spans_nest_and_carry_the_counters(tmp_path_factory):
    runs = _runs(tmp_path_factory)
    spans, rep = runs["spans"], runs["traced"][0]
    (top,) = _named(spans, "madsim.search")
    kids = [s for s in spans
            if s[0].startswith("madsim.search.") and _inside(s, top)]
    assert [s[0] for s in kids] == [
        "madsim.search.plan", "madsim.search.init", "madsim.search.run",
        "madsim.search.fetch", "madsim.search.check",
    ]
    assert len([s for s in spans if s[0].startswith("madsim.search")]) == 6
    assert top[3] == {"d2h_bytes": rep.d2h_bytes,
                      "d2h_transfers": rep.d2h_transfers,
                      "row_steps_paid": rep.row_steps_paid,
                      "row_steps_live": rep.row_steps_live}
    assert 0 < rep.row_steps_live <= rep.row_steps_paid


def test_campaign_spans_nest_and_carry_the_counters(tmp_path_factory):
    runs = _runs(tmp_path_factory)
    spans, rep = runs["spans"], runs["traced"][1]
    assert len(_named(spans, "madsim.explore.start")) == 1
    assert len(_named(spans, "madsim.explore.materialize")) == 1
    gens = _named(spans, "madsim.explore.generation")
    syncs = _named(spans, "madsim.explore.sync")
    assert [g[3]["g"] for g in gens] == [0, 1]
    assert len(syncs) == 2
    assert all(_inside(s, g) for s, g in zip(syncs, gens))
    (start,), (mat,) = (_named(spans, "madsim.explore.start"),
                        _named(spans, "madsim.explore.materialize"))
    assert start[2] <= gens[0][1] and gens[-1][2] <= mat[1]
    assert sum(s[3]["row_steps_paid"] for s in syncs) == rep.row_steps_paid
    assert sum(s[3]["row_steps_live"] for s in syncs) == rep.row_steps_live


def test_materialize_span_counts_entries_and_builds_none(tmp_path_factory):
    runs = _runs(tmp_path_factory)
    spans, rep = runs["spans"], runs["traced"][1]
    (mat,) = _named(spans, "madsim.explore.materialize")
    assert mat[3] == {"entries": len(rep.corpus) + len(rep.violations),
                      "built": 0}
    assert rep.corpus.built == 0 and len(rep.corpus) > 0


def test_checkpoint_materialize_builds_the_checkpointed_entries(
        tmp_path_factory):
    """Each per-generation checkpoint builds its new entries inside its
    materialize span; the report after them reuses every one."""
    d = tmp_path_factory.mktemp("ckpt")
    records = []
    jax.profiler.start_trace(str(d / "trace"))
    try:
        rep = explore.run_device(
            RAFT_WL, RAFT_CFG, RAFT_PLAN, telemetry=records.append,
            checkpoint_path=str(d / "camp.npz"),
            **dict(CAMPAIGN, invariant=lambda v: (v["trace"] & 7) != 0))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{d}/trace/**/*.xplane.pb", recursive=True)
    mats = sorted(
        (e.start_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name == "madsim.explore.materialize")
    gens = [r for r in records if r["event"] == "generation"]
    assert len(mats) == len(gens) + 1 == 3
    assert [m["entries"] for _, m in mats[:-1]] == [
        r["corpus_size"] + r["violations"] for r in gens]
    assert rep.violations, "the invariant must flag seeds"
    entries = {id(e) for e in list(rep.corpus) + list(rep.violations)}
    assert sum(m["built"] for _, m in mats) == len(entries)
    assert mats[0][1]["built"] > 0
    assert mats[-1][1] == {"entries": len(rep.corpus) + len(rep.violations),
                           "built": 0}


def test_search_is_bit_identical_under_the_profiler(tmp_path_factory):
    runs = _runs(tmp_path_factory)
    plain, traced = runs["plain"][0], runs["traced"][0]
    for f in ("traces", "ok", "halted", "overflowed", "screen_ok",
              "flagged_idx", "halt_times"):
        np.testing.assert_array_equal(getattr(plain, f), getattr(traced, f),
                                      err_msg=f)
    assert (plain.d2h_bytes, plain.row_steps_paid, plain.row_steps_live) \
        == (traced.d2h_bytes, traced.row_steps_paid, traced.row_steps_live)


def test_campaign_is_bit_identical_under_the_profiler(tmp_path_factory):
    runs = _runs(tmp_path_factory)
    plain, traced = runs["plain"][1], runs["traced"][1]

    def fp(rep):
        return ([(e.id, e.seed, e.trace, e.violating, e.plan.hash(),
                  e.new_bits) for e in rep.corpus],
                rep.cov_map.tolist(), rep.curve,
                [(e.seed, e.trace) for e in rep.violations],
                rep.row_steps_paid, rep.row_steps_live)

    assert fp(plain) == fp(traced)
