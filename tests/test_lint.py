"""madsim_tpu.lint: the jaxpr taint walker, the non-interference proof
over the engine, and the AST nondeterminism-leak linter."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from madsim_tpu.engine import (
    Build,
    DERIVED_STATE_FIELDS,
    STORAGE_STATE_FIELDS,
    EngineConfig,
    Workload,
    core_fields,
    derived_fields,
    make_init,
    make_run_while,
    user_kind,
)
from madsim_tpu.engine.core import MET_SYNC, MET_SYNC_LOST, KIND_SYNC_OK
from madsim_tpu.lint import (
    analyze_jaxpr,
    check_noninterference,
    lint_repo,
    lint_source,
    model_matrix,
    plant_met_leak,
)
from madsim_tpu.models import make_raft, make_raftlog

CFG = EngineConfig(pool_size=40, loss_p=0.02, clog_backoff_max_ns=2_000_000_000)
CFG_RL = EngineConfig(pool_size=64, loss_p=0.02, clog_backoff_max_ns=2_000_000_000)


def _taints(closed, **by_index):
    n = len(closed.jaxpr.invars)
    out = [frozenset() for _ in range(n)]
    for i, label in by_index.items():
        out[int(i)] = frozenset({label})
    return out


class TestTaintWalker:
    """The walker on hand-built jaxprs — every control construct the
    engine's step/run functions route taint through."""

    def test_straight_line_chain(self):
        def f(x, y):
            return x + 1.0, y * 2.0, x * y

        closed = jax.make_jaxpr(f)(1.0, 2.0)
        res = analyze_jaxpr(closed, [frozenset({"x"}), frozenset()])
        assert res.out_taint[0] == {"x"}
        assert res.out_taint[1] == frozenset()
        assert res.out_taint[2] == {"x"}
        # every tainted equation is on the frontier; x*y mixes clean
        assert any(r.mixes_clean for r in res.frontier)

    def test_multiply_by_zero_still_flows(self):
        # the planted-mutant shape: value-identical, data-dependent —
        # the edge bit-identity tests can never see
        def f(x, y):
            return y + x * 0.0

        closed = jax.make_jaxpr(f)(1.0, 2.0)
        res = analyze_jaxpr(closed, [frozenset({"x"}), frozenset()])
        assert res.out_taint[0] == {"x"}

    def test_scan_carried_taint(self):
        # taint enters the carry from xs on iteration 1 and must stick:
        # only the fixpoint sees it
        def f(c, xs):
            def body(carry, x):
                return carry + x, carry

            return lax.scan(body, c, xs)

        closed = jax.make_jaxpr(f)(0.0, jnp.arange(3.0))
        res = analyze_jaxpr(closed, _taints(closed, **{"1": "xs"}))
        out_c, out_ys = res.out_taint
        assert out_c == {"xs"}
        assert out_ys == {"xs"}  # ys emit the carry, tainted from iter 2
        # clean xs, tainted init carry: both outputs taste the carry
        res2 = analyze_jaxpr(closed, _taints(closed, **{"0": "c0"}))
        assert res2.out_taint[0] == {"c0"}
        assert res2.out_taint[1] == {"c0"}

    def test_cond_branch_join(self):
        def f(p, a, b):
            return lax.cond(p, lambda o: o[0] + 1.0, lambda o: o[1], (a, b))

        closed = jax.make_jaxpr(f)(True, 1.0, 2.0)
        # taint only the UNTAKEN-in-spirit branch operand: joins anyway
        res = analyze_jaxpr(closed, _taints(closed, **{"2": "b"}))
        assert res.out_taint[0] == {"b"}
        # implicit flow: a tainted predicate taints every output
        res2 = analyze_jaxpr(closed, _taints(closed, **{"0": "pred"}))
        assert "pred" in res2.out_taint[0]

    def test_while_implicit_flow(self):
        # the loop bound is tainted: the iteration count observes it,
        # so the carried value is tainted even though no arithmetic
        # touches the bound
        def f(n, x):
            def cond(c):
                return c[0] < n

            def body(c):
                return (c[0] + 1, c[1] * 2.0)

            return lax.while_loop(cond, body, (0, x))

        closed = jax.make_jaxpr(f)(3, 1.0)
        res = analyze_jaxpr(closed, _taints(closed, **{"0": "n"}))
        assert "n" in res.out_taint[1]

    def test_jit_boundary(self):
        @jax.jit
        def inner(a, b):
            return a + b, b - 1.0

        def f(a, b):
            return inner(a, b)

        closed = jax.make_jaxpr(f)(1.0, 2.0)
        assert any(e.primitive.name == "jit" for e in closed.jaxpr.eqns)
        res = analyze_jaxpr(closed, _taints(closed, **{"0": "a"}))
        assert res.out_taint[0] == {"a"}
        assert res.out_taint[1] == frozenset()
        # the frontier path names the nested location
        assert any("jit" in r.path for r in res.frontier)

    def test_shard_map_boundary(self):
        # the multi-chip call boundary: labels must cross POSITIONALLY
        # (output 1 stays clean), not smear conservatively over every
        # output, and the frontier path names the nested location
        from jax.sharding import PartitionSpec as P

        from madsim_tpu.parallel import make_mesh, shard_map_nocheck

        mesh = make_mesh()
        ax = mesh.axis_names

        def body(a, b):
            return a + b, b * 2.0

        f = shard_map_nocheck(
            body, mesh, in_specs=(P(ax), P(ax)), out_specs=(P(ax), P(ax))
        )
        closed = jax.make_jaxpr(f)(jnp.zeros(8), jnp.ones(8))
        assert any(
            e.primitive.name == "shard_map" for e in closed.jaxpr.eqns
        )
        res = analyze_jaxpr(closed, _taints(closed, **{"0": "a"}))
        assert res.out_taint[0] == {"a"}
        assert res.out_taint[1] == frozenset()
        assert any("shard_map" in r.path for r in res.frontier)

        # a collective inside the mapped body propagates like any
        # first-order equation: psum over a tainted shard taints the
        # (replicated) result
        def body2(a, b):
            return b + jax.lax.psum(a, ax)

        f2 = shard_map_nocheck(
            body2, mesh, in_specs=(P(ax), P(ax)), out_specs=P(ax)
        )
        closed2 = jax.make_jaxpr(f2)(jnp.zeros(8), jnp.ones(8))
        res2 = analyze_jaxpr(closed2, _taints(closed2, **{"0": "a"}))
        assert res2.out_taint[0] == {"a"}


class TestNonInterference:
    """The proof over the real engine step/run programs."""

    def test_manifest(self):
        wl = make_raft()
        d = derived_fields(wl)
        assert set(DERIVED_STATE_FIELDS) <= set(d)
        assert set(STORAGE_STATE_FIELDS) <= set(d)  # discipline off
        wl_d = make_raftlog(durable=True)
        assert set(STORAGE_STATE_FIELDS) & set(derived_fields(wl_d)) == set()
        assert set(STORAGE_STATE_FIELDS) <= set(core_fields(wl_d))

    def test_step_all_taps(self):
        rep = check_noninterference(
            make_raft(record=True), CFG,
            Build(
                layout="scatter", time32=False, metrics=True, timeline_cap=8,
                cov_words=8, cov_hitcount=True,
            ),
        )
        assert rep.ok, rep.summary()
        # the derived columns themselves are legitimately tainted
        # (read-modify-write) and the frontier is non-empty
        assert "met" in rep.out_taint and "cov" in rep.out_taint
        assert rep.frontier
        # report cites SimState field names (the obs.explain vocabulary)
        assert set(rep.derived) == set(derived_fields(make_raft()))

    def test_run_entry_scan_path(self):
        rep = check_noninterference(
            make_raft(record=True), CFG,
            Build(layout="scatter", time32=False, metrics=True, cov_words=8),
            entry="run", n_steps=3,
        )
        assert rep.ok, rep.summary()
        assert any(
            "scan" in r["path"] or "body" in r["path"] for r in rep.frontier
        )

    def test_sharded_run_entry(self):
        """entry="sharded_run" proves the multi-chip campaign program
        (explore.run_device's simulate stage) THROUGH the shard_map
        boundary — with the campaign tap set on."""
        from madsim_tpu.lint import CAMPAIGN_AXES

        flags = dict(CAMPAIGN_AXES["sharded-campaign"])
        rep = check_noninterference(
            make_raft(record=True), CFG,
            Build(layout="scatter", time32=False, **flags),
            entry="sharded_run", n_seeds=4, n_steps=3,
        )
        assert rep.ok, rep.summary()
        assert rep.flags["mesh_devices"] == jax.device_count()
        assert "cov" in rep.out_taint and "met" in rep.out_taint
        # the proof walked INTO the mapped body, not around it
        assert any("shard_map" in r["path"] for r in rep.frontier)

    def test_check_axes_device_verification_row(self):
        """check=True traces the check.device detector kernels WITH the
        sim through shard_map (the history-hunt program shape): taint
        set unchanged, the verdict output carries ONLY history taint,
        no callback prims, and step entries are rejected."""
        from madsim_tpu.lint import CHECK_AXES
        from madsim_tpu.lint.noninterference import BASE_LOWERING, split_axis

        build, kw = split_axis(BASE_LOWERING, CHECK_AXES["device-check"])
        assert kw == {"check": True}
        rep = check_noninterference(
            make_raft(record=True), CFG, build,
            entry="sharded_run", n_seeds=4, n_steps=3, **kw,
        )
        assert rep.ok, rep.summary()
        assert rep.flags["check"] is True
        # the verdict is tainted by the history columns and nothing else
        assert set(rep.out_taint["check_ok"]) <= {
            "hist_word", "hist_t", "hist_count", "hist_drop"
        }
        assert not rep.callback_prims
        with pytest.raises(ValueError, match="entry"):
            check_noninterference(
                make_raft(record=True), CFG, entry="step", check=True,
            )

    def test_sharded_run_planted_leak_is_caught(self):
        # the positive control crosses the call boundary: met comes out
        # of the shard_map'd run and leaks into the RNG cursor — the
        # labels must survive the crossing for the walker to see it
        # (plant_met_leak is step-entry-only, so plant the batched form)
        import dataclasses

        from madsim_tpu.engine.core import MET_SENT

        def batched_met_leak(run_fn):
            def mutant(st):
                out = run_fn(st)
                poison = (out.met[:, MET_SENT] * jnp.int32(0)).astype(
                    jnp.uint32
                )
                return dataclasses.replace(out, step=out.step + poison)

            return mutant

        rep = check_noninterference(
            make_raft(record=True), CFG,
            Build(layout="scatter", time32=False, metrics=True),
            entry="sharded_run", n_seeds=4, n_steps=3, mutate=batched_met_leak,
        )
        assert not rep.ok
        assert "met" in rep.leaks["step"]["labels"]

    @pytest.mark.slow
    def test_sharded_campaign_matrix(self):
        # the pod-scale acceptance row: every recorded model under the
        # campaign tap set, proved through the shard_map boundary —
        # tools/lint_soak.py runs the same sweep for the artifact
        from madsim_tpu.lint import CAMPAIGN_AXES, check_matrix

        reports = check_matrix(axes=CAMPAIGN_AXES, entry="sharded_run")
        assert len(reports) == len(model_matrix())
        bad = [r.summary() for r in reports if not r.ok]
        assert not bad, "\n".join(bad)

    def test_durable_discipline_reclassifies(self):
        rep = check_noninterference(
            make_raftlog(durable=True, record=True), CFG_RL,
            Build(
                layout="scatter", time32=False, metrics=True, timeline_cap=8,
                cov_words=8,
            ),
        )
        assert rep.ok, rep.summary()
        assert "disk" not in rep.derived

    def test_planted_met_leak_is_caught(self):
        rep = check_noninterference(
            make_raft(record=True), CFG,
            Build(layout="scatter", time32=False, metrics=True),
            mutate=plant_met_leak,
        )
        assert not rep.ok
        # the RNG cursor is the leaked core column, met among sources
        assert "step" in rep.leaks
        assert "met" in rep.leaks["step"]["labels"]
        # the offending equation chain is reported, ending in the add
        chain = rep.leaks["step"]["chain"]
        assert chain and chain[-1]["prim"] == "add"
        assert "met" in chain[-1]["sources"]
        assert "reaches core column 'step'" in rep.summary()

    def test_report_is_machine_readable(self):
        rep = check_noninterference(
            make_raft(), CFG,
            Build(layout="scatter", time32=False, metrics=True),
        )
        d = rep.to_dict()
        assert d["ok"] and isinstance(d["frontier"], list)
        assert all(
            {"path", "prim", "sources", "mixes_clean"} <= set(r)
            for r in d["frontier"]
        )
        rep.to_json()  # must serialize

    def test_latency_columns_isolated_dense_and_time32(self):
        """The new lowering axes of the matrix (dense one-hot writes,
        int32 pool times) and the latency-marker path: the army
        model's lat_* columns (and the emit-time sidecar) prove
        isolated in the exact programs a TPU runs. The full sweep is
        the slow matrix / make lint --jaxpr."""
        from madsim_tpu.engine import LatencySpec
        from madsim_tpu.models.kvchaos import make_kvchaos

        wl = make_kvchaos(army=True)
        spec = LatencySpec(ops=8, phases=2)
        for layout, t32 in (("dense", False), ("dense", True)):
            rep = check_noninterference(
                wl, CFG,
                Build(
                    layout=layout, time32=t32, latency=spec, timeline_cap=8,
                    cov_words=8,
                ),
            )
            assert rep.ok, rep.summary()
            assert "lat_hist" in rep.out_taint
            rep.to_json()  # LatencySpec flags stay JSON-able

    def test_cold_bank_isolated_under_rank_placement(self):
        """The PR-8 placement axis: the cold-bank columns (history,
        timeline, coverage, latency) prove derived-state-isolated in
        BOTH scatter-layout pool-write lowerings — the rank
        select-chain program (the new small-pool CPU default, whose
        select chains the cold-bank appends ride) and the historical
        scatter stores. The full sweep is the slow matrix."""
        from madsim_tpu.engine import LatencySpec
        from madsim_tpu.models.raftlog import make_raftlog

        wl = make_raftlog(army=True)
        spec = LatencySpec(ops=8, phases=2)
        for place in ("rank", "scatter"):
            rep = check_noninterference(
                wl, CFG,
                Build(
                    layout="scatter", time32=False, placement=place,
                    latency=spec, timeline_cap=8, cov_words=8, metrics=True,
                ),
            )
            assert rep.ok, rep.summary()
            assert rep.flags["placement"] == place
            for col in ("hist_word", "tl_t", "cov", "lat_hist", "met"):
                assert col in rep.out_taint, (place, col)

    def test_layout_axes_sweep_and_time32_skip(self):
        from madsim_tpu.lint import check_matrix
        from madsim_tpu.lint.noninterference import LAYOUT_AXES

        assert Build(layout="dense", time32=False) in LAYOUT_AXES
        assert Build(layout="scatter", time32=True, placement="rank") \
            in LAYOUT_AXES
        # the combined pair is the exact program an accelerator runs
        assert Build(layout="dense", time32=True) in LAYOUT_AXES
        # BOTH scatter-layout pool-write lowerings: the rank
        # select-chain program and the historical .at[].set stores
        for place in ("rank", "scatter"):
            assert Build(layout="scatter", time32=False, placement=place) \
                in LAYOUT_AXES
        # a non-eligible (workload, config) is skipped for time32
        # pairs instead of failing the matrix
        wl = make_raft()
        wl = type(wl)(**{
            **{f.name: getattr(wl, f.name) for f in
               __import__("dataclasses").fields(wl)},
            "delay_bound_ns": None,
        })
        reps = check_matrix(
            [("raft/unbounded", wl, CFG)], {"base": {}},
            layouts=(Build(layout="scatter", time32=True),),
        )
        assert reps == []

    @pytest.mark.slow
    def test_full_matrix(self):
        # the acceptance sweep: four recorded models (plus the durable
        # variant) x every build axis — tools/lint_soak.py runs the
        # same matrix for the evidence artifact
        from madsim_tpu.lint import check_matrix

        reports = check_matrix()
        assert len(reports) >= 9 * 6
        bad = [r.summary() for r in reports if not r.ok]
        assert not bad, "\n".join(bad)


SIM = dict(sim_code=True)


class TestLintRules:
    """Each rule has (at least) one negative fixture it catches."""

    def _rules(self, src, **kw):
        return [f.rule for f in lint_source(src, "fx.py", **kw).findings]

    def test_wall_clock(self):
        assert "wall-clock" in self._rules(
            "import time\nseed = int(time.time_ns())\n"
        )
        assert "wall-clock" in self._rules(
            "from datetime import datetime\nx = datetime.now()\n"
        )
        assert "wall-clock" in self._rules(
            "import time as t\nx = t.perf_counter()\n"
        )

    def test_ambient_entropy(self):
        assert "ambient-entropy" in self._rules(
            "import os\nx = os.urandom(8)\n"
        )
        assert "ambient-entropy" in self._rules(
            "import secrets\nx = secrets.token_bytes(4)\n"
        )

    def test_submodule_import_keeps_root_rules_live(self):
        # `import os.path` binds the local name `os` to the ROOT
        # module; the alias map must not remap it to os.path and
        # silently disable the entropy/clock rules on that root
        assert "ambient-entropy" in self._rules(
            "import os.path\nx = os.urandom(8)\n"
        )
        assert "wall-clock" in self._rules(
            "import xml.etree\nimport time\nt = time.time()\n"
        )

    def test_unparseable_file_reports_parse_error_rule(self):
        assert self._rules("def f(:\n") == ["parse-error"]

    def test_uuid(self):
        assert "uuid-entropy" in self._rules("import uuid\nu = uuid.uuid4()\n")
        assert "uuid-entropy" not in self._rules(
            "import uuid\nu = uuid.uuid5(uuid.NAMESPACE_DNS, 'x')\n"
        )

    def test_np_random(self):
        assert "np-random" in self._rules(
            "import numpy as np\nx = np.random.rand(3)\n"
        )
        # an explicitly seeded generator is a deterministic construction
        assert "np-random" not in self._rules(
            "import numpy as np\ng = np.random.default_rng(7)\n"
        )
        assert "np-random" in self._rules(
            "import numpy as np\ng = np.random.default_rng()\n"
        )

    def test_unordered_iter(self):
        assert "unordered-iter" in self._rules(
            "for x in set([1, 2]):\n    pass\n"
        )
        assert "unordered-iter" in self._rules("xs = list({1, 2} | {3})\n")
        assert "unordered-iter" in self._rules(
            "xs = [y for y in frozenset((1, 2))]\n"
        )
        # sorted() launders the order; dict is insertion-ordered
        assert "unordered-iter" not in self._rules(
            "xs = sorted(set([3, 1]))\n"
        )
        assert "unordered-iter" not in self._rules(
            "for k in {'a': 1}:\n    pass\n"
        )

    def test_id_hash_branch(self):
        assert "id-hash-branch" in self._rules(
            "def f(a, b):\n    if id(a) < id(b):\n        return a\n"
        )
        assert "id-hash-branch" in self._rules(
            "x = 1 if hash('k') % 2 else 2\n"
        )
        # id() outside a branch condition is not flagged
        assert "id-hash-branch" not in self._rules("k = id(object())\n")

    def test_fixed_key_scoped_to_sim_code(self):
        src = "import jax\nk = jax.random.PRNGKey(0)\n"
        hits = [
            f.rule for f in lint_source(src, "m.py", **SIM).findings
        ]
        assert "fixed-key" in hits
        # a derived (non-constant) key is the sanctioned construction
        ok = "import jax\nk = jax.random.PRNGKey(seed)\n"
        assert not lint_source(ok, "m.py", **SIM).findings
        # host-side tools may seed however they like
        assert not lint_source(src, "t.py", sim_code=False).findings
        # the alias + jax.random.key spelling resolves too
        src2 = "from jax import random as jr\nk = jr.key(42)\n"
        assert "fixed-key" in [
            f.rule for f in lint_source(src2, "m.py", **SIM).findings
        ]
        # pragma allowlists an intentional fixed key
        src3 = (
            "import jax\n"
            "k = jax.random.PRNGKey(0)  # lint: allow(fixed-key)\n"
        )
        res = lint_source(src3, "m.py", **SIM)
        assert not res.findings and res.allowed

    def test_host_callback_scoped_to_sim_code(self):
        src = (
            "from jax.experimental import io_callback\n"
            "def f(x):\n    return io_callback(print, None, x)\n"
        )
        assert "host-callback" in self._rules(src, sim_code=True)
        assert "host-callback" not in self._rules(src, sim_code=False)
        assert "host-callback" in self._rules(
            "import jax\njax.debug.print('{}', 1)\n", sim_code=True
        )

    def test_pragma_same_line_and_above(self):
        src = (
            "import time\n"
            "t0 = time.monotonic()  # lint: allow(wall-clock)\n"
            "# lint: allow(wall-clock)\n"
            "t1 = time.monotonic()\n"
        )
        res = lint_source(src, "fx.py")
        assert not res.findings
        assert len(res.allowed) == 2

    def test_unused_pragma_is_a_finding(self):
        res = lint_source("x = 1  # lint: allow(np-random)\n", "fx.py")
        assert [f.rule for f in res.findings] == ["unused-allow"]

    def test_dead_pragma_next_to_live_same_rule_pragma(self):
        # usage is tracked per PRAGMA, not per line: a dead pragma is
        # stale even when the adjacent line legitimately uses the same
        # rule (the drift mode where a timer call is deleted but its
        # annotation survives)
        src = (
            "import time\n"
            "t0 = time.monotonic()  # lint: allow(wall-clock)\n"
            "x = 1  # lint: allow(wall-clock)\n"
        )
        res = lint_source(src, "fx.py")
        assert [f.rule for f in res.findings] == ["unused-allow"]
        assert res.findings[0].line == 3
        assert len(res.allowed) == 1

    def test_pragma_must_name_the_right_rule(self):
        src = "import os\nx = os.urandom(4)  # lint: allow(wall-clock)\n"
        rules = self._rules(src)
        assert "ambient-entropy" in rules and "unused-allow" in rules


class TestRepoClean:
    def test_repo_lints_clean(self):
        # the acceptance gate: the whole default surface is finding-free
        # and every intentional site is enumerated by a live pragma
        res = lint_repo()
        assert res.n_files > 50
        msgs = "\n".join(str(f) for f in res.findings)
        assert res.ok, f"repo lint found:\n{msgs}"
        assert len(res.allowed) > 0  # the checked allowlist is non-empty

    def test_matrix_names_six_recorded_models(self):
        names = {n.split("/")[0] for n, _wl, _cfg in model_matrix()}
        assert names == {"raft", "kvchaos", "paxos", "raftlog",
                         "leasekv", "shardkv"}


class TestSyncEio:
    """The observable fsync-EIO window (EmitBuilder errno surface)."""

    def _probe(self):
        # node 0 ticks every 50 ms, writing durable col 0 and syncing;
        # col 1 counts the ticks that observed ctx.sync_err. An EIO
        # window opens at t=0 and closes at 120 ms.
        def on_init(ctx):
            eb = ctx.emits()
            eb.sync_eio(0, when=ctx.now == 0)
            eb.after(50_000_000, user_kind(1), 0, when=ctx.node == 0)
            eb.after(
                120_000_000, KIND_SYNC_OK, 0, (0,), when=ctx.node == 0
            )
            return ctx.state, eb.build()

        def on_tick(ctx):
            eb = ctx.emits()
            new = ctx.state.at[0].set(ctx.state[0] + 1)
            new = new.at[1].set(
                new[1] + ctx.sync_err.astype(jnp.int32)
            )
            eb.sync()
            eb.after(50_000_000, user_kind(1), 0, when=ctx.state[0] < 3)
            eb.halt(when=ctx.state[0] >= 3)
            return new, eb.build()

        return Workload(
            name="eioprobe", n_nodes=1, state_width=2,
            handlers=(on_init, on_tick), max_emits=4,
            durable_cols=(0,), durable_sync=True,
            delay_bound_ns=200_000_000,
        )

    def test_handler_observes_eio_and_syncs_fail(self):
        wl = self._probe()
        cfg = EngineConfig(pool_size=8)
        init = make_init(wl, cfg, Build(metrics=True))
        run = jax.jit(make_run_while(wl, cfg, 64, Build(metrics=True)))
        out = run(init(np.zeros(2, np.uint64)))
        st = np.asarray(out.node_state)[0, 0]
        met = np.asarray(out.met)[0]
        # ticks at 50/100 ms fall inside the [0, 120) ms window: both
        # observe sync_err and both syncs fail; later ticks commit
        assert int(st[1]) == 2
        assert int(met[MET_SYNC_LOST]) == 2
        assert int(met[MET_SYNC]) >= 1
        # the last committed sync carried the full counter to disk
        assert int(np.asarray(out.disk)[0, 0, 0]) == int(st[0])
        # both seeds identical (the window is plan-shaped, not drawn)
        assert int(np.asarray(out.node_state)[1, 0, 1]) == 2

    def test_diskfault_eio_windows_compile(self):
        from madsim_tpu.chaos import DiskFault
        from madsim_tpu.engine import KIND_SYNC_LOSS

        spec = DiskFault(targets=(0, 1), n_torn=1, n_sync_loss=1, n_eio=2)
        assert spec.slots == 8
        time, kinds, args, _valid, _node = spec.compile_batch(
            np.arange(4, dtype=np.uint64), slot=0
        )
        on = np.asarray(kinds) == KIND_SYNC_LOSS
        # per seed: one lie window (a1=0) and two EIO windows (a1=1)
        assert (on.sum(axis=1) == 3).all()
        eio_on = (np.asarray(args)[..., 1] == 1) & on
        assert eio_on.sum(axis=1).tolist() == [2] * 4
        # growing n_eio appended AFTER the existing windows: the lie
        # window's draws are unchanged (the spec-offset rule)
        base = DiskFault(targets=(0, 1), n_torn=1, n_sync_loss=1)
        time0, *_rest = base.compile_batch(
            np.arange(4, dtype=np.uint64), slot=0
        )
        np.testing.assert_array_equal(np.asarray(time)[:, :4], time0)

    @pytest.mark.slow
    def test_raftlog_survives_eio_storm(self):
        from madsim_tpu.chaos import CrashStorm, DiskFault, FaultPlan
        from madsim_tpu.check import election_safety, recovery_safety
        from madsim_tpu.engine import search_seeds
        from madsim_tpu.models.raftlog import (
            OP_COMMIT, OP_ELECT, OP_RECOVER, OP_SYNCED,
        )

        cfg = EngineConfig(
            pool_size=128, loss_p=0.02, clog_backoff_max_ns=2_000_000_000
        )
        wl = make_raftlog(record=True, chaos=False, durable=True)
        plan = FaultPlan((
            CrashStorm(
                targets=(0, 1, 2, 3, 4), n=2, t_min_ns=150_000_000,
                t_max_ns=500_000_000, down_min_ns=100_000_000,
                down_max_ns=400_000_000,
            ),
            DiskFault(
                targets=(0, 1, 2, 3, 4), n_torn=0, n_sync_loss=0,
                n_eio=3, t_min_ns=10_000_000, t_max_ns=400_000_000,
                dur_min_ns=100_000_000, dur_max_ns=400_000_000,
            ),
        ), name="eio-storm")

        def inv(h):
            return (
                election_safety(h, elect_op=OP_COMMIT)
                & election_safety(h, elect_op=OP_ELECT)
                & recovery_safety(h, sync_op=OP_SYNCED, recover_op=OP_RECOVER)
            )

        rep = search_seeds(
            wl, cfg, None, history_invariant=inv, plan=plan,
            n_seeds=512, max_steps=4000, metrics=True, require_halt=False,
        )
        assert int((~rep.ok).sum()) == 0
        assert int(rep.overflowed.sum()) == 0
        # the windows were genuinely exercised: observable sync
        # failures happened on most seeds
        assert int((rep.met[:, MET_SYNC_LOST] > 0).sum()) > 256
