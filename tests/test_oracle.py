"""Bit-identical trace compare: batched JAX engine vs the C++ oracle.

The strongest determinism check in the framework (SURVEY.md §2.6 row 4,
§7 hard part 2): the C++ oracle (native/oracle.cpp) reimplements the
engine's integer semantics and workloads independently; for any
(workload, seed, config) both must produce the identical uint64 rolling
trace hash, virtual clock, message count and final node state. This is
what licenses trusting a 65k-seed TPU batch — each row provably equals
the reference interpreter.
"""

import shutil

import numpy as np
import pytest

import jax

from madsim_tpu.engine import (
    Build,
    EngineConfig,
    make_init,
    make_run,
    threefry2x32,
)
from madsim_tpu.engine.oracle import oracle_threefry, run_oracle
from madsim_tpu.models import (
    make_broadcast,
    make_kvchaos,
    make_microbench,
    make_pingpong,
    make_raft,
    make_paxos,
    make_raftlog,
    make_twophase,
)

pytestmark = pytest.mark.skipif(
    shutil.which("make") is None or shutil.which("g++") is None,
    reason="native toolchain unavailable",
)

# engine==oracle is layout-independent (the dense/scatter cross is
# itself gated by check_layouts in the default tier), so the default
# gate compares one lowering per family — scatter, the CPU-native one —
# and the dense twin rides the full tier
LAYOUTS = [pytest.param("dense", marks=pytest.mark.slow), "scatter"]


def engine_batch(wl, cfg, seeds, n_steps, layout=None):
    init = make_init(wl, cfg)
    run = jax.jit(make_run(wl, cfg, n_steps, Build(layout=layout)))
    return run(init(np.asarray(seeds, np.uint64)))


def compare(wl, cfg, seeds, n_steps, layout=None, **model_kwargs):
    out = engine_batch(wl, cfg, seeds, n_steps, layout=layout)
    for idx, seed in enumerate(seeds):
        o = run_oracle(wl, cfg, seed, n_steps, **model_kwargs)
        assert int(out.trace[idx]) == o.trace, (
            f"trace diverged for seed {seed}: "
            f"engine={int(out.trace[idx]):x} oracle={o.trace:x}"
        )
        assert int(out.now[idx]) == o.now
        assert int(out.msg_count[idx]) == o.msg_count
        assert bool(out.halted[idx]) == o.halted
        assert int(out.halt_time[idx]) == o.halt_time
        assert int(out.overflow[idx]) == o.overflow
        assert np.array_equal(np.asarray(out.node_state[idx]), o.node_state)


def test_threefry_matches_oracle():
    rng = np.random.RandomState(7)
    for _ in range(100):
        k0, k1, x0, x1 = rng.randint(0, 2**32, size=4, dtype=np.uint32)
        ja, jb = threefry2x32(k0, k1, x0, x1)
        oa, ob = oracle_threefry(int(k0), int(k1), int(x0), int(x1))
        assert (int(np.uint32(ja)), int(np.uint32(jb))) == (oa, ob)


def test_pingpong_traces_bit_identical():
    wl = make_pingpong(rounds=5)
    cfg = EngineConfig(pool_size=64)
    compare(wl, cfg, list(range(16)), 200, rounds=5)


def test_pingpong_with_loss_bit_identical():
    wl = make_pingpong(rounds=3)
    cfg = EngineConfig(pool_size=64, loss_p=0.2)
    compare(wl, cfg, list(range(8)), 150, rounds=3)


def test_microbench_traces_bit_identical():
    wl = make_microbench(rounds=200)
    cfg = EngineConfig(pool_size=16)
    compare(wl, cfg, list(range(8)), 220, rounds=200)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_raft_traces_bit_identical(layout):
    # both lowerings of the step (the TPU dense form and the CPU scatter
    # form) must match the oracle bit-for-bit
    wl = make_raft()
    cfg = EngineConfig(pool_size=128, loss_p=0.05)
    compare(wl, cfg, list(range(16)), 400, layout=layout)


def test_raft_with_time_limit_bit_identical():
    wl = make_raft()
    cfg = EngineConfig(pool_size=128, time_limit_ns=200_000_000)
    compare(wl, cfg, [3, 9, 27], 400)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_broadcast_traces_bit_identical(layout):
    # partition chaos + packet loss: the clog/unclog + retransmit path
    # (the only oracle workload exercising the clogged-reschedule
    # branch, so both lowerings must run it)
    wl = make_broadcast(rounds=3)
    cfg = EngineConfig(pool_size=128, loss_p=0.05)
    compare(wl, cfg, list(range(12)), 400, layout=layout, rounds=3)


def test_broadcast_no_partition_bit_identical():
    wl = make_broadcast(rounds=2, partition=False)
    cfg = EngineConfig(pool_size=128)
    compare(wl, cfg, list(range(6)), 250, rounds=2, partition=False)


def test_kvchaos_traces_bit_identical():
    # kill/restart chaos + loss: epoch gating, restart re-init, rejoin
    wl = make_kvchaos(writes=5)
    cfg = EngineConfig(pool_size=128, loss_p=0.02)
    compare(wl, cfg, list(range(12)), 500, writes=5)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kvchaos_payload_traces_bit_identical(layout):
    # the payload arena: client-drawn value words ride WRITE/REPL events
    # and feed the trace hash — a payload divergence anywhere fails here
    wl = make_kvchaos(writes=5, payload=True)
    cfg = EngineConfig(pool_size=128, loss_p=0.02)
    compare(wl, cfg, list(range(12)), 500, layout=layout, writes=5)


def test_kvchaos_payload_no_chaos_bit_identical():
    wl = make_kvchaos(writes=4, chaos=False, payload=True)
    cfg = EngineConfig(pool_size=128)
    compare(wl, cfg, list(range(6)), 400, writes=4, chaos=False)


def test_big_seed_values():
    # seeds above 2^32 exercise the k1 half of the key
    wl = make_pingpong(rounds=3)
    cfg = EngineConfig(pool_size=64)
    seeds = [2**63 - 1, 2**40 + 17, 123456789012345]
    compare(wl, cfg, seeds, 150, rounds=3)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_twophase_traces_bit_identical(layout):
    # 2PC: stored votes, phase-aware retransmits, participant
    # kill/restart — the sixth oracle-verified protocol family
    wl = make_twophase(txns=4)
    cfg = EngineConfig(pool_size=64, loss_p=0.03)
    compare(wl, cfg, list(range(12)), 500, layout=layout, txns=4)


def test_twophase_no_chaos_bit_identical():
    wl = make_twophase(txns=3, chaos=False)
    cfg = EngineConfig(pool_size=64, loss_p=0.05)
    compare(wl, cfg, list(range(8)), 400, txns=3, chaos=False)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_raftlog_traces_bit_identical(layout):
    # raft log replication + leader crash — the seventh oracle-verified
    # protocol family (payload arena carries the full log in appends)
    wl = make_raftlog()
    cfg = EngineConfig(pool_size=64, loss_p=0.02, clog_backoff_max_ns=2_000_000_000)
    compare(wl, cfg, list(range(12)), 3000, layout=layout)


def test_raftlog_no_chaos_bit_identical():
    wl = make_raftlog(chaos=False, n_writes=3)
    cfg = EngineConfig(pool_size=64, loss_p=0.05)
    compare(wl, cfg, list(range(8)), 2000, chaos=False, n_writes=3)


def test_raftlog_durable_bit_identical():
    # crash-recovery raft: (term, votedFor, log) survive the leader
    # kill/restart via Workload.durable_cols — the restart path restores
    # only the volatile columns, mirrored in the oracle (the durable set
    # is pushed generically by engine/oracle.py, no model flag needed)
    wl = make_raftlog(durable=True)
    cfg = EngineConfig(pool_size=64, loss_p=0.02, clog_backoff_max_ns=2_000_000_000)
    compare(wl, cfg, list(range(10)), 3000)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_paxos_traces_bit_identical(layout):
    # single-decree paxos + proposer crash — the eighth oracle-verified
    # protocol family (dueling proposers, NACK fast-forward)
    wl = make_paxos()
    cfg = EngineConfig(pool_size=64, loss_p=0.02)
    compare(wl, cfg, list(range(12)), 400, layout=layout)


def test_paxos_no_chaos_bit_identical():
    wl = make_paxos(chaos=False, n_acceptors=3, n_proposers=2)
    cfg = EngineConfig(pool_size=64, loss_p=0.05)
    compare(
        wl, cfg, list(range(8)), 400,
        chaos=False, n_acceptors=3, n_proposers=2,
    )


def test_paxos_durable_acceptors_bit_identical():
    # acceptor kills with durable (promised, accepted) columns — the
    # Workload.durable_cols restart path, mirrored in the oracle
    wl = make_paxos(durable_acceptors=True)
    cfg = EngineConfig(pool_size=64, loss_p=0.02)
    compare(wl, cfg, list(range(10)), 400, durable_acceptors=True)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_snapshot_traces_bit_identical(layout):
    from madsim_tpu.models import make_snapshot

    wl = make_snapshot()
    cfg = EngineConfig(pool_size=96)
    compare(wl, cfg, list(range(12)), 400, layout=layout)


def test_snapshot_small_cluster_bit_identical():
    from madsim_tpu.models import make_snapshot

    kw = dict(n_nodes=3, n_sends=4, balance=500, amount_max=50)
    wl = make_snapshot(**kw)
    cfg = EngineConfig(pool_size=64)
    compare(wl, cfg, list(range(8)), 300, **kw)
