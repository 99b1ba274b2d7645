"""The raftlog reference workload against the program's CPU engine, and
the two plain checks its history screens stand on (``election_safety``,
``recovery_safety``) against the program's screens on hand-built
histories."""

from __future__ import annotations

import numpy as np
import pytest

from cpu import BENCH, ROOT  # noqa: F401  (puts the benchmark on sys.path)

# check/history.py OP_USER + 0..3, as models/raftlog.py records them
OP_ELECT, OP_COMMIT, OP_SYNCED, OP_RECOVER = 16, 17, 18, 19
ENGINE_KEYS = ("pool_size", "lat_min_ns", "lat_max_ns", "loss_p",
               "proc_min_ns", "proc_max_ns", "clog_backoff_min_ns",
               "clog_backoff_max_ns", "time_limit_ns")
MAX_STEPS = 4000
N_SEEDS = 128


def _pairs(durable: bool) -> list:
    """(program screen, plain check entry) for each history screen."""
    from madsim_tpu.check import device as dc

    pairs = [(dc.election_safety(op),
              {"name": "election_safety", "args": {"elect_op": op}})
             for op in (OP_ELECT, OP_COMMIT)]
    if durable:
        pairs.append((dc.recovery_safety(OP_SYNCED, OP_RECOVER),
                      {"name": "recovery_safety",
                       "args": {"sync_op": OP_SYNCED,
                                "recover_op": OP_RECOVER}}))
    return pairs


def _screen_ok(screen, words: np.ndarray, counts: np.ndarray) -> list:
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.vmap(screen.seed_kernel)(
        jnp.asarray(words), jnp.asarray(counts))).tolist()


@pytest.mark.parametrize("n_writes,durable,pool", [
    (4, False, 64), (4, True, 64), (16, True, 256), (16, False, 256)])
def test_raftlog_reference_matches_the_program(n_writes, durable, pool):
    """128 chaos schedules with history records on: trace, clock, halt
    flag and time, overflow, history drops and rows, and each screen's
    verdict against the plain check's on the reference's history."""
    from madsim_tpu import models
    from madsim_tpu.engine import EngineConfig, search_seeds
    from reference.history import clean
    from reference.oracle import Reference

    params = dict(n_nodes=5, n_writes=n_writes, timeout_min_ns=150_000_000,
                  timeout_max_ns=300_000_000, propose_ns=20_000_000,
                  retx_ns=60_000_000, chaos=True, durable=durable,
                  record=True)
    wl = models.make_raftlog(**params)
    cfg = EngineConfig(pool_size=pool, loss_p=0.02,
                       clog_backoff_max_ns=2_000_000_000)
    seeds = np.arange(2**40, 2**40 + N_SEEDS, dtype=np.uint64)
    view = {}

    def invariant(v):
        view.update({k: np.asarray(v[k]) for k in (
            "now", "halted", "halt_time", "overflow", "hist_drop",
            "hist_word", "hist_count")})
        return np.ones(N_SEEDS, bool)

    rep = search_seeds(wl, cfg, invariant, seeds=seeds, max_steps=MAX_STEPS,
                       compact=True, require_halt=False)
    config = {"engine": {k: getattr(cfg, k) for k in ENGINE_KEYS},
              "max_steps": MAX_STEPS,
              "reference": {"workload": "raftlog", "params": params,
                            "hist_capacity": wl.history.capacity}}
    ref = Reference(config)
    pairs = _pairs(durable)
    screens = [_screen_ok(s, view["hist_word"], view["hist_count"])
               for s, _ in pairs]
    ops = set()
    for i, seed in enumerate(seeds):
        r = ref.run(int(seed))
        n = int(view["hist_count"][i])
        program = {"trace": int(rep.traces[i]), "now": int(view["now"][i]),
                   "halted": bool(view["halted"][i]),
                   "halt_time": int(view["halt_time"][i]),
                   "overflow": int(view["overflow"][i]),
                   "hist_drop": int(view["hist_drop"][i]),
                   "hist": view["hist_word"][i][:n].tolist(),
                   "verdicts": [ok[i] for ok in screens]}
        reference = dict({k: r[k] for k in program if k in r},
                         verdicts=[clean(r["hist"], [c]) for _, c in pairs])
        assert program == reference, int(seed)
        ops.update(row[0] for row in r["hist"])
    # every schedule ran to its last commit, and each record kind occurs
    assert view["halted"].all() and not view["overflow"].any()
    want = {OP_ELECT, OP_COMMIT} | ({OP_SYNCED, OP_RECOVER} if durable else set())
    assert ops == want


# rows [op, key, arg, client, ok]; ok 1 = a successful record
HISTORIES = {
    "election_safety": [
        ([[OP_ELECT, 1, 2, 2, 1], [OP_ELECT, 2, 3, 3, 1],
          [OP_ELECT, 1, 2, 4, 1]], False),
        ([[OP_ELECT, 1, 2, 2, 1], [OP_ELECT, 1, 3, 3, 1]], True),
        ([[OP_ELECT, 1, 2, 2, 1], [OP_ELECT, 1, 3, 3, 0]], False),
        ([[OP_ELECT, 1, 2, 2, 1], [OP_COMMIT, 1, 3, 3, 1]], False),
        ([[OP_ELECT, 5, 9, 0, 1], [OP_ELECT, 6, 1, 0, 1],
          [OP_ELECT, 5, 8, 0, 1]], True),
        ([], False),
    ],
    "recovery_safety": [
        ([[OP_SYNCED, 0, 3, 1, 1], [OP_RECOVER, 0, 3, 1, 1]], False),
        ([[OP_SYNCED, 0, 3, 1, 1], [OP_RECOVER, 0, 2, 1, 1]], True),
        # the latest sync, not the largest: a truncation re-syncs shorter
        ([[OP_SYNCED, 0, 3, 1, 1], [OP_SYNCED, 0, 1, 1, 1],
          [OP_RECOVER, 0, 1, 1, 1]], False),
        ([[OP_SYNCED, 0, 1, 1, 1], [OP_SYNCED, 0, 3, 1, 1],
          [OP_RECOVER, 0, 2, 1, 1]], True),
        ([[OP_SYNCED, 0, 3, 1, 1], [OP_RECOVER, 0, 0, 2, 1]], False),
        ([[OP_RECOVER, 0, 0, 1, 1], [OP_SYNCED, 0, 3, 1, 1]], False),
        ([[OP_SYNCED, 0, 3, 1, 0], [OP_RECOVER, 0, 0, 1, 1]], False),
        ([[OP_SYNCED, 0, 3, 1, 1], [OP_RECOVER, 0, 2, 1, 0]], False),
        ([[OP_SYNCED, 0, 3, 1, 1], [OP_SYNCED, 0, 4, 2, 1],
          [OP_RECOVER, 0, 3, 2, 1]], True),
    ],
}
ARGS = {"election_safety": {"elect_op": OP_ELECT},
        "recovery_safety": {"sync_op": OP_SYNCED, "recover_op": OP_RECOVER}}


@pytest.mark.parametrize("name,case", [
    (name, i) for name, cases in HISTORIES.items() for i in range(len(cases))])
def test_plain_check_on_built_histories(name, case):
    """Each plain check's verdict on a history built to violate it or not,
    and the program's screen of the same name agrees."""
    from madsim_tpu.check import device as dc
    from reference import named

    hist, bad = HISTORIES[name][case]
    assert named.load("checks", name).violated(hist, **ARGS[name]) is bad
    screen = getattr(dc, name)(**ARGS[name])
    words = np.zeros((1, max(len(hist), 1), 5), np.int32)
    words[0, :len(hist)] = np.reshape(hist, (-1, 5))
    assert _screen_ok(screen, words, np.array([len(hist)], np.int32)) == [not bad]
