"""Device-resident exploration campaigns — one host sync per generation.

The host driver (explore/driver.py) round-trips through numpy every
generation: corpus selection, mutation and admission all run on the
host while the accelerator idles, and the whole per-seed result state
crosses the PCIe boundary each dispatch. This module is the same
campaign loop restated as a device program:

* the **corpus lives in device memory** as fixed-capacity column arrays
  (plan rows, seeds, traces, coverage signatures, ids — one row per
  admitted entry);
* **mutation** is a vectorized jnp kernel (:func:`_mutate_child` under
  ``vmap``) that emulates the host edit script *draw for draw*: the
  same threefry counters, the same modulo reductions, the same
  branch structure as ``HostStream`` + ``mutate_plan`` — so a device
  campaign breeds bit-identical children (the parity test pins it);
* **admission** is one ``lax.scan`` over the generation in batch order
  (popcount-delta against the global map + the (seed, trace) violation
  dedup), with the winners scattered into the corpus arrays;
* the whole generation — derive keys, pick parents, mutate, simulate
  (``engine.make_sweep``), admit — is ONE jitted program per mode
  (uniform / breeding), built once per campaign *shape* and served
  from the generation-program cache (``_GEN_CACHE``, the
  ``engine.search._RUN_CACHE`` discipline): the campaign root seed and
  generation index are runtime arguments, so a multi-campaign session
  re-traces NOTHING (profiler-certified — ``obs.prof`` counts exactly
  one trace per cache key, where each campaign historically re-paid
  the full trace+lower+compile from fresh closures). With a ``mesh``,
  mutation and simulation run under ``shard_map`` across chips (corpus
  replicated, the (seed, plan) batch sharded — the multi-process pjit
  shape); the cross-shard metric/latency folds reuse
  ``parallel.merge_metrics`` / ``merge_latency``, and the admission
  scan consumes the gathered per-seed coverage rows without ever
  leaving the device.

The host sees exactly one synchronization point per generation: the
admission summary (corpus size, new-entry count, coverage bits,
violation count) and — when logging asks for them — the fresh
violation keys. Per-seed state never reaches the host until the final
report (or a checkpoint) copies the corpus and violation stores once;
the report reads them through :class:`EntryView`, which builds each
``CorpusEntry`` when it is first read.

Campaign outcomes are **bit-identical to the host driver** given the
same arguments: same corpus (ids, seeds, plans, traces, new-bit
scores), same coverage map, same violations, same replay keys — the
device path is a lowering, not a fork. ``checkpoint_path`` / ``resume``
interoperate with host-driver checkpoints in both directions.

History hunts go device-resident too: ``history_check`` (a
``check.device.HistoryScreen`` set) traces the vectorized batch
detectors INTO the generation program — the detector's verdict folds
into the violation mask right next to the sweep that recorded the
histories, the screen identity joins the ``_GEN_CACHE`` key, and a
guided hunt over history bugs (lost writes, election safety,
recovery regressions) runs end-to-end without a host round-trip.
Finds replay on the host driver via
``check.device.screens_invariant(screens)`` — bit-identical verdicts,
so the two drivers still agree corpus-for-corpus.

Limitations vs the host driver: the invariant must be a *traceable*
final-state predicate (jnp ops over the state view — it runs inside
the device program; numpy-only predicates and arbitrary host
``history_invariant`` callables beyond the screen set need the host
driver), and ``compact=True`` has no device equivalent (the sweep
runs ``make_run_while``).
"""

from __future__ import annotations

import dataclasses as _dc
import operator
import os as _os
import time as _time
from collections.abc import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P_

from ..chaos.plan import FaultEvent, FaultPlan, LiteralPlan, stack_plan_rows
from ..engine.core import Build, PlanRows, check_plan_horizon
from ..engine.rng import PURPOSE_EXPLORE, threefry2x32
from ..engine.search import make_sweep
from ..obs.prof import span
from .driver import CorpusEntry, ExploreReport, _pad_literal
from .mutate import (
    MODE_NODE,
    MODE_PAIR,
    MODE_RETIME,
    MODE_SKEW,
    MODE_SLOW,
    PlanSpace,
    inherit_threshold,
    mutation_table,
)

__all__ = ["EntryView", "gen_cache_stats", "run_device"]


def _kth_true(mask, k):
    """Index of the (k+1)-th True of ``mask`` — the device form of the
    host's ``index_list[k]`` pick (callers guarantee k < popcount)."""
    cum = jnp.cumsum(mask.astype(jnp.int32))
    return jnp.argmax(mask & (cum == k + 1)).astype(jnp.int32)


def _mk_seeds(k0s, k1s):
    return k0s.astype(jnp.uint64) | (k1s.astype(jnp.uint64) << jnp.uint64(32))


# ---------------------------------------------------------------------------
# the vectorized mutator — HostStream + mutate_plan, draw for draw
# ---------------------------------------------------------------------------


def _make_child_mutator(tb, max_ops: int, inherit_thresh: int):
    """Build ``child(k0, k1, fresh_seed, order, olen, cr) -> dict`` —
    the device form of one batch slot's host edit script:

        st = HostStream(k0, k1, PURPOSE_EXPLORE)
        pid = order[st.bits() % len(order)]          # draw 0
        inherit = st.bits() < inherit_thresh          # draw 1
        child = mutate_plan(parent, space, st, ...)   # draws 2..

    Every draw is ``threefry2x32(k0, k1, j, PURPOSE_EXPLORE)[0]`` at
    the same running counter ``j`` the HostStream would use; branches
    advance ``j`` by exactly the number of draws the host branch
    consumes (``mutate.RETARGET_DRAWS``), so the two edit scripts stay
    aligned no matter which ops fire.
    """
    X1 = jnp.uint32(PURPOSE_EXPLORE)
    t_lo, t_hi = tb["t_lo"], tb["t_hi"]
    mode, rt_d = tb["mode"], tb["rt_draws"]
    tgt, tcnt = tb["tgt"], tb["tcnt"]
    mult_lo, mult_hi = tb["mult_lo"], tb["mult_hi"]
    skew_lo, skew_hi = tb["skew_lo"], tb["skew_hi"]
    p_slots = int(t_lo.shape[0])

    def bits(k0, k1, j):
        a, _ = threefry2x32(k0, k1, j, X1)
        return a

    def child(k0, k1, fresh_seed, order, olen, cr):
        w0 = bits(k0, k1, jnp.uint32(0)).astype(jnp.int64)
        pslot = order[(w0 % olen).astype(jnp.int32)]
        w1 = bits(k0, k1, jnp.uint32(1)).astype(jnp.int64)
        inherit = w1 < jnp.int64(inherit_thresh)
        seed = jnp.where(inherit, cr["cs"][pslot], fresh_seed)
        halt = cr["chalt"][pslot]
        has_h = halt > 0
        w2 = bits(k0, k1, jnp.uint32(2)).astype(jnp.int64)
        n_ops = 1 + (w2 % max(max_ops, 1))

        def retime(sel, told, cw, vw):
            lo = t_lo[sel]
            hi0 = t_hi[sel]
            # the parent's causal window: an event past the halt clock
            # can never change the trajectory (mutate._retime)
            hi = jnp.where(has_h & (lo < halt) & (halt < hi0), halt, hi0)
            delta = jnp.maximum((hi - lo) // 8, 1)
            tf = jnp.clip(told + (-delta + vw % (2 * delta + 1)), lo, hi - 1)
            tc = lo + vw % jnp.maximum(hi - lo, 1)
            return jnp.where((cw % 2) == 0, tf, tc)

        def pick_tgt(sel, w):
            k = (w % jnp.maximum(tcnt[sel].astype(jnp.int64), 1)).astype(
                jnp.int32
            )
            return tgt[sel, k]

        def pick_tgt_ne(sel, a, w):
            # the host's [t for t in targets if t != a] pick: exclusion
            # is by VALUE, order preserved
            row = tgt[sel]
            ok = (jnp.arange(row.shape[0]) < tcnt[sel]) & (row != a)
            cnt = ok.sum().astype(jnp.int64)
            m = (w % jnp.maximum(cnt, 1)).astype(jnp.int32)
            return row[_kth_true(ok, m)]

        def body(it, carry):
            j, t, a0, a1, en = carry
            active = it < n_ops
            wlane, _ = threefry2x32(
                k0, k1, j + jnp.arange(7, dtype=jnp.uint32), X1
            )
            w = wlane.astype(jnp.int64)
            op = w[0] % 8
            n_on = en.sum().astype(jnp.int64)
            n_off = p_slots - n_on
            alive = en & (t < halt)
            n_alive = alive.sum().astype(jnp.int64)
            use_alive = has_h & (n_alive > 0)
            sel_mask = jnp.where(use_alive, alive, en)
            sel_cnt = jnp.where(use_alive, n_alive, n_on)
            # mutate_plan's if/elif chain, one branch per op
            b_add = (op == 0) & (n_off > 0)
            b_drop = (op == 1) & (n_on > 1)
            b_ret = ((op == 2) | (op == 3)) & (n_on > 0)
            b_time = ~(b_add | b_drop | b_ret) & (n_on > 0)
            b_fadd = ~(b_add | b_drop | b_ret | b_time) & (n_off > 0)
            any_add = b_add | b_fadd
            k_off = (w[1] % jnp.maximum(n_off, 1)).astype(jnp.int32)
            k_on = (w[1] % jnp.maximum(sel_cnt, 1)).astype(jnp.int32)
            sel = jnp.where(any_add, _kth_true(~en, k_off),
                            _kth_true(sel_mask, k_on))
            m = mode[sel]
            rd = rt_d[sel].astype(jnp.int64)
            is_fb = m == MODE_RETIME
            t_sel = t[sel]
            # add/force-add and plain-retime both draw (choose, value)
            # at w[2], w[3]; retarget draws start at w[4] after an add's
            # retime, at w[2] otherwise
            t_rt1 = retime(sel, t_sel, w[2], w[3])
            rw0 = jnp.where(any_add, w[4], w[2])
            rw1 = jnp.where(any_add, w[5], w[3])
            rw2 = jnp.where(any_add, w[6], w[4])
            # fallback retarget = a second retime (reading the time the
            # add's first retime just wrote, exactly like the host's
            # in-place event list)
            t_fb = retime(sel, jnp.where(any_add, t_rt1, t_sel), rw0, rw1)
            aa = pick_tgt(sel, rw0)
            bb = pick_tgt_ne(sel, aa, rw1)
            mult = mult_lo[sel] + rw2 % jnp.maximum(
                mult_hi[sel] + 1 - mult_lo[sel], 1
            )
            slow_a1 = ((bb + 1) & 0xFF) | (mult << 8)
            skew = skew_lo[sel] + rw1 % jnp.maximum(
                skew_hi[sel] + 1 - skew_lo[sel], 1
            )
            a0_sel = a0[sel].astype(jnp.int64)
            a1_sel = a1[sel].astype(jnp.int64)
            new_a0 = jnp.select(
                [m == MODE_NODE, m == MODE_PAIR, m == MODE_SLOW,
                 m == MODE_SKEW],
                [aa, aa, aa, aa], a0_sel,
            )
            new_a1 = jnp.select(
                [m == MODE_NODE, m == MODE_PAIR, m == MODE_SLOW,
                 m == MODE_SKEW],
                [a1_sel, bb, slow_a1, skew], a1_sel,
            )
            t_add = jnp.where(is_fb, t_fb, t_rt1)
            t_ret = jnp.where(is_fb, t_fb, t_sel)
            new_t = jnp.where(
                any_add, t_add,
                jnp.where(b_ret, t_ret,
                          jnp.where(b_time, t_rt1, t_sel)),
            )
            write_t = active & (any_add | b_ret | b_time)
            write_a = active & (any_add | b_ret)
            # one-hot selects, not .at[sel].set: on the TPU the batched
            # scatter into the bool slot mask lost its writes (PR 21)
            at_sel = jnp.arange(p_slots) == sel
            t2 = jnp.where(at_sel & write_t, new_t, t)
            a02 = jnp.where(at_sel & write_a, new_a0.astype(jnp.int32), a0)
            a12 = jnp.where(at_sel & write_a, new_a1.astype(jnp.int32), a1)
            en2 = jnp.where(at_sel & active & any_add, True,
                            jnp.where(at_sel & active & b_drop, False, en))
            cost = jnp.where(
                any_add, 4 + rd,
                jnp.where(b_drop, 2,
                          jnp.where(b_ret, 2 + rd,
                                    jnp.where(b_time, 4, 0))),
            )
            j2 = j + jnp.where(active, cost, 0).astype(jnp.uint32)
            return j2, t2, a02, a12, en2

        t0 = cr["ct"][pslot]
        a0_0 = cr["ca"][pslot, :, 0]
        a1_0 = cr["ca"][pslot, :, 1]
        en0 = cr["cv"][pslot]
        _, t, a0, a1, en = lax.fori_loop(
            0, max(max_ops, 1), body, (jnp.uint32(3), t0, a0_0, a1_0, en0)
        )
        return dict(
            seed=seed,
            time=t,
            kind=cr["ck"][pslot],
            args=jnp.stack([a0, a1], axis=-1),
            valid=en,
            node=cr["cn"][pslot],
            parent=cr["cid"][pslot],
        )

    return child


# ---------------------------------------------------------------------------
# carry <-> host state
# ---------------------------------------------------------------------------

_ROW_KEYS = ("time", "kind", "args", "valid", "node")


def _empty_store(cap1, p, cw):
    """One entry store (corpus or violation) of ``cap1`` rows — the
    last row is scatter trash for refused candidates, never read."""
    return dict(
        time=jnp.zeros((cap1, p), jnp.int64),
        kind=jnp.zeros((cap1, p), jnp.int32),
        args=jnp.zeros((cap1, p, 2), jnp.int32),
        valid=jnp.zeros((cap1, p), jnp.bool_),
        node=jnp.zeros((cap1, p), jnp.int32),
        seed=jnp.zeros((cap1,), jnp.uint64),
        trace=jnp.zeros((cap1,), jnp.uint64),
        cov=jnp.zeros((cap1, cw), jnp.uint32),
        new_bits=jnp.zeros((cap1,), jnp.int32),
        id=jnp.full((cap1,), -1, jnp.int32),
        parent=jnp.full((cap1,), -1, jnp.int32),
        gen=jnp.zeros((cap1,), jnp.int32),
        viol=jnp.zeros((cap1,), jnp.bool_),
        halt=jnp.zeros((cap1,), jnp.int64),
        bslot=jnp.full((cap1,), -1, jnp.int32),
    )


def _fill_store(store, entries):
    """Load checkpointed CorpusEntry rows into a device store (slot i =
    entries[i], admission order — ids stay whatever the campaign
    assigned)."""
    if not entries:
        return store
    rows = stack_plan_rows([e.plan for e in entries])
    n = len(entries)
    out = dict(store)
    out["time"] = store["time"].at[:n].set(jnp.asarray(rows.time, jnp.int64))
    out["kind"] = store["kind"].at[:n].set(jnp.asarray(rows.kind, jnp.int32))
    out["args"] = store["args"].at[:n].set(jnp.asarray(rows.args, jnp.int32))
    out["valid"] = store["valid"].at[:n].set(
        jnp.asarray(rows.valid, jnp.bool_)
    )
    out["node"] = store["node"].at[:n].set(jnp.asarray(rows.node, jnp.int32))
    out["seed"] = store["seed"].at[:n].set(
        jnp.asarray([e.seed for e in entries], jnp.uint64)
    )
    out["trace"] = store["trace"].at[:n].set(
        jnp.asarray([e.trace for e in entries], jnp.uint64)
    )
    out["cov"] = store["cov"].at[:n].set(
        jnp.asarray(np.stack([np.asarray(e.cov, np.uint32) for e in entries]))
    )
    out["new_bits"] = store["new_bits"].at[:n].set(
        jnp.asarray([e.new_bits for e in entries], jnp.int32)
    )
    out["id"] = store["id"].at[:n].set(
        jnp.asarray([e.id for e in entries], jnp.int32)
    )
    out["parent"] = store["parent"].at[:n].set(
        jnp.asarray([e.parent for e in entries], jnp.int32)
    )
    out["gen"] = store["gen"].at[:n].set(
        jnp.asarray([e.generation for e in entries], jnp.int32)
    )
    out["viol"] = store["viol"].at[:n].set(
        jnp.asarray([e.violating for e in entries], jnp.bool_)
    )
    out["halt"] = store["halt"].at[:n].set(
        jnp.asarray([e.halt_t for e in entries], jnp.int64)
    )
    return out


def _store_entry(st_np, i, name) -> CorpusEntry:
    """Materialize store row ``i`` back into a CorpusEntry."""
    events = tuple(
        FaultEvent(
            t=int(st_np["time"][i, p]),
            kind=int(st_np["kind"][i, p]),
            a0=int(st_np["args"][i, p, 0]),
            a1=int(st_np["args"][i, p, 1]),
            node=int(st_np["node"][i, p]),
        )
        for p in range(st_np["time"].shape[1])
    )
    return CorpusEntry(
        id=int(st_np["id"][i]),
        generation=int(st_np["gen"][i]),
        parent=int(st_np["parent"][i]),
        seed=int(st_np["seed"][i]),
        plan=LiteralPlan(
            events=events,
            enabled=tuple(bool(x) for x in st_np["valid"][i]),
            name=name,
        ),
        trace=int(st_np["trace"][i]),
        cov=np.asarray(st_np["cov"][i], np.uint32).copy(),
        new_bits=int(st_np["new_bits"][i]),
        violating=bool(st_np["viol"][i]),
        halt_t=int(st_np["halt"][i]),
    )


class EntryView(Sequence):
    """A campaign's corpus or violations: a read-only sequence over the
    host copy of an entry store, whose entry ``i`` is built from store
    row ``i`` (:func:`_store_entry`) the first time it is read.

    ``cache`` is the session's slot -> entry map, shared by every view
    of the store, so an entry is built once and entries loaded on
    resume come back as the same objects. ``shared(i)`` may return
    another view's entry for row ``i`` (a violation that joined the
    corpus), or None. ``built`` counts the entries this view
    constructed."""

    def __init__(self, rows, n: int, cache: dict, name, shared=None):
        self._rows, self._n, self._cache = rows, n, cache
        self._name, self._shared = name, shared
        self.built = 0

    def _entry(self, i: int) -> CorpusEntry:
        e = self._cache.get(i)
        if e is None:
            e = self._shared(i) if self._shared is not None else None
            if e is None:
                e = _store_entry(self._rows, i, self._name(self._rows, i))
                self.built += 1
            self._cache[i] = e
        return e

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self._entry(j) for j in range(*i.indices(self._n))]
        i = operator.index(i)
        if not -self._n <= i < self._n:
            raise IndexError(f"entry {i} of {self._n}")
        return self._entry(i % self._n)

    def __iter__(self):
        return (self._entry(i) for i in range(self._n))

    def __eq__(self, other) -> bool:
        if not isinstance(other, Sequence):
            return NotImplemented
        return len(other) == self._n and all(
            a is b or a == b for a, b in zip(self, other)
        )


# ---------------------------------------------------------------------------
# the generation-program cache
# ---------------------------------------------------------------------------

# generation-program cache, the engine.search._RUN_CACHE discipline at
# campaign scope: run_device historically rebuilt its uniform/breed
# programs from fresh closures EVERY call, so jit's function-identity
# cache missed and every campaign re-paid trace+lower+compile (ROADMAP
# item 1; the flight recorder measured it before this cache killed it).
# Keyed on (workload identity, config, space hash, batch, build flags,
# invariant identity, mesh, seed-corpus literals) — everything baked
# into the traced program. The ROOT SEED is deliberately NOT in the
# key: it enters the programs as a runtime argument, so a multi-
# campaign session over fresh root seeds reuses one compiled program
# per key (profiler-certified: retraces == 1). Entries hold
# obs.prof.AotProgram pairs, so every build is phase-timed and
# retrace-counted. Bounded LRU (compiled executables are not free, and
# a farm time-slicing N tenants in round-robin order would thrash a
# FIFO into evicting exactly the program it is about to need again);
# MADSIM_GEN_CACHE_MAX overrides the bound, evictions are counted
# loudly (gen_cache_stats -> flight_summary). Hold ONE
# workload/invariant object across campaigns to hit the cache, exactly
# like engine.search.
_GEN_CACHE: dict = {}
_GEN_CACHE_MAX = 8
_GEN_CACHE_EVICTIONS = 0


def _gen_cache_max() -> int:
    raw = _os.environ.get("MADSIM_GEN_CACHE_MAX")
    if raw is None:
        return _GEN_CACHE_MAX
    try:
        return max(int(raw), 1)
    except ValueError:
        raise ValueError(
            f"MADSIM_GEN_CACHE_MAX={raw!r} is not an integer"
        ) from None


def gen_cache_stats() -> dict:
    """Generation-program cache accounting: live entries, the effective
    bound (``MADSIM_GEN_CACHE_MAX``) and lifetime evictions. The flight
    recorder folds this into ``flight_summary`` — a growing eviction
    count in a farm session means more tenant shapes than cache slots,
    each switch re-paying trace+lower+compile; raise the knob."""
    return {
        "entries": len(_GEN_CACHE),
        "max": _gen_cache_max(),
        "evictions": _GEN_CACHE_EVICTIONS,
    }


def _mesh_key(mesh):
    """Value identity of a mesh: same devices + axes = same programs
    (mesh OBJECTS are routinely rebuilt between campaigns)."""
    if mesh is None:
        return None
    return (
        tuple(d.id for d in mesh.devices.flat),
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
    )


def _gen_programs(key, builder):
    global _GEN_CACHE_EVICTIONS
    progs = _GEN_CACHE.get(key)
    if progs is None:
        cap = _gen_cache_max()
        while len(_GEN_CACHE) >= cap:
            _GEN_CACHE.pop(next(iter(_GEN_CACHE)))
            _GEN_CACHE_EVICTIONS += 1
        progs = _GEN_CACHE[key] = builder()
    else:
        # LRU touch: re-insertion moves the entry to the back of the
        # eviction order (dicts iterate in insertion order)
        _GEN_CACHE[key] = _GEN_CACHE.pop(key)
    return progs[0], progs[1]


def _build_programs(
    wl, cfg, space, build, *, invariant, batch, max_steps, require_halt,
    select_top, max_corpus, vcap, max_ops, inherit_seed_p, mesh,
    seed_corpus, cache_key, history_check=None,
):
    """Build one cache entry: the (uniform, breed, refs) triple, the
    generations' sweep built from ``build`` (a resolved ``Build``).

    Both programs take ``(carry, g, rk0, rk1)`` — the generation index
    and the campaign root key are runtime arguments (same threefry
    coordinates as the host driver's ``_derive_keys``), so one compiled
    program serves every root seed and every generation. ``refs`` pins
    the objects whose id() participates in the cache key.
    """
    from ..obs.prof import AotProgram

    n_dev = int(mesh.devices.size) if mesh is not None else 1
    b_loc = batch // n_dev
    axes = mesh.axis_names if mesh is not None else None
    p_slots = build.plan_slots
    cmax1 = max_corpus + 1
    vcap1 = vcap + 1
    tb = {k: jnp.asarray(v) for k, v in mutation_table(space).items()}
    mutator = _make_child_mutator(
        tb, max_ops, inherit_threshold(inherit_seed_p)
    )
    sweep = make_sweep(wl, cfg, max_steps, build)
    k_ov = len(seed_corpus)
    if k_ov:
        ov = stack_plan_rows([_pad_literal(lp, p_slots) for lp in seed_corpus])
        ov = {f: jnp.asarray(getattr(ov, f)) for f in _ROW_KEYS}

    def derive_keys(g, jglob, rk0, rk1):
        # driver._derive_keys: x0 = generation, x1 = PURPOSE_EXPLORE+slot
        return threefry2x32(
            rk0, rk1, g, jnp.uint32(PURPOSE_EXPLORE) + jglob.astype(jnp.uint32)
        )

    def run_children(seeds, rows):
        view = sweep(seeds, rows)
        if invariant is not None:
            ok = jnp.asarray(invariant(view), jnp.bool_)
            if ok.shape != seeds.shape:
                raise ValueError(
                    f"invariant must return a {seeds.shape} boolean "
                    f"array, got shape {ok.shape}"
                )
        else:
            ok = jnp.ones(seeds.shape, jnp.bool_)
        if history_check is not None:
            # the device history screen, traced WITH the sweep into the
            # generation program: verdicts fold into the violation mask
            # right where the histories were recorded — per-seed
            # history columns never leave the device
            from ..check.device import screen_ok as _screen_ok

            ok = ok & _screen_ok(
                history_check, view["hist_word"], view["hist_t"],
                view["hist_count"], view["hist_drop"],
            )
        if require_halt:
            ok = ok & view["halted"]
        over = view["overflow"] > 0
        if wl.history is not None:
            over = over | (view["hist_drop"] > 0)
        cols = dict(
            trace=view["trace"],
            halt=view["halt_time"],
            failing=(~ok) & (~over),
            # overflowed seeds are quarantined from guidance too: their
            # trajectories dropped events, so their bitmaps are artifacts
            cov=jnp.where(over[:, None], jnp.uint32(0), view["cov"]),
        )
        if build.metrics:
            cols["met"] = view["met"]
        if build.latency is not None:
            cols["lat_hist"] = view["lat_hist"]
        # this shard's [paid, live] row-steps: on a mesh they stay
        # sharded, (2 x devices,), and the host sums them at the sync
        cols["row_steps"] = view["row_steps"]
        return cols

    def _jglob():
        dev = lax.axis_index(axes) if mesh is not None else 0
        return dev * b_loc + jnp.arange(b_loc)

    def shard_uniform(g, rk0, rk1):
        jglob = _jglob()
        k0s, k1s = derive_keys(g, jglob, rk0, rk1)
        seeds = _mk_seeds(k0s, k1s)
        rows = space.plan.compile_batch(seeds, device=True)
        row_d = {f: jnp.asarray(getattr(rows, f)) for f in _ROW_KEYS}
        if k_ov:
            is_ov = (jglob < k_ov) & (g == jnp.uint32(0))
            gi = jnp.minimum(jglob, k_ov - 1)
            for f in _ROW_KEYS:
                sel = is_ov.reshape((-1,) + (1,) * (row_d[f].ndim - 1))
                row_d[f] = jnp.where(sel, ov[f][gi], row_d[f])
        out = dict(
            seed=seeds,
            parent=jnp.full((b_loc,), -1, jnp.int32),
            bslot=jglob.astype(jnp.int32),
            **row_d,
        )
        out.update(run_children(seeds, PlanRows(**row_d)))
        return out

    def shard_breed(cr, g, rk0, rk1):
        jglob = _jglob()
        k0s, k1s = derive_keys(g, jglob, rk0, rk1)
        fresh = _mk_seeds(k0s, k1s)
        # frontier-first parent order: violating entries before clean
        # ones, newest (largest slot == largest id) first — computed
        # replicated on every device from the replicated corpus
        slot = jnp.arange(cmax1)
        valid = slot < cr["count"]
        nv = (~cr["c"]["viol"]).astype(jnp.int64)
        key = jnp.where(
            valid,
            nv * jnp.int64(2 * cmax1)
            + (cr["count"].astype(jnp.int64) - slot),
            jnp.int64(1) << 60,
        )
        order = jnp.argsort(key)
        olen = jnp.minimum(
            jnp.int64(select_top), cr["count"].astype(jnp.int64)
        )
        crm = dict(
            ct=cr["c"]["time"], ck=cr["c"]["kind"], ca=cr["c"]["args"],
            cv=cr["c"]["valid"], cn=cr["c"]["node"], cs=cr["c"]["seed"],
            chalt=cr["c"]["halt"], cid=cr["c"]["id"],
        )
        ch = jax.vmap(
            lambda a, b, c: mutator(a, b, c, order, olen, crm)
        )(k0s, k1s, fresh)
        out = dict(
            seed=ch["seed"],
            parent=ch["parent"],
            bslot=jglob.astype(jnp.int32),
            **{f: ch[f] for f in _ROW_KEYS},
        )
        out.update(
            run_children(ch["seed"], PlanRows(**{f: ch[f] for f in _ROW_KEYS}))
        )
        return out

    if mesh is not None:
        from ..parallel import shard_map_nocheck

        spec_b = P_(axes)
        sm_uniform = shard_map_nocheck(
            shard_uniform, mesh, in_specs=(P_(), P_(), P_()),
            out_specs=spec_b,
        )
        sm_breed = shard_map_nocheck(
            shard_breed, mesh, in_specs=(P_(), P_(), P_(), P_()),
            out_specs=spec_b,
        )
    else:
        sm_uniform, sm_breed = shard_uniform, shard_breed

    def admission(cr, g, out):
        varange = jnp.arange(vcap1)

        def body(acc, x):
            gm, cnt, nid, vc, vs, vt, over = acc
            row, fail, seed, trace = x
            fresh_bits = (
                lax.population_count(row & ~gm).sum().astype(jnp.int32)
            )
            gm2 = gm | row
            # a violation is counted once per distinct (seed, trace)
            # trajectory (driver seen_viol) — the store IS the set
            dup_v = jnp.any((vs == seed) & (vt == trace) & (varange < vc))
            fresh_viol = fail & ~dup_v
            qualify = (fresh_bits > 0) | fresh_viol
            idj = jnp.where(qualify, nid, -1)
            vslot = jnp.where(fresh_viol, jnp.minimum(vc, vcap), -1)
            wv = jnp.minimum(vc, vcap)
            vs2 = vs.at[wv].set(jnp.where(fresh_viol, seed, vs[wv]))
            vt2 = vt.at[wv].set(jnp.where(fresh_viol, trace, vt[wv]))
            over2 = over | (fresh_viol & (vc >= vcap))
            cslot = jnp.where(qualify & (cnt < max_corpus), cnt, -1)
            acc2 = (
                gm2,
                cnt + (qualify & (cnt < max_corpus)).astype(jnp.int32),
                nid + qualify.astype(jnp.int32),
                vc + fresh_viol.astype(jnp.int32),
                vs2, vt2, over2,
            )
            return acc2, (fresh_bits, idj, cslot, vslot)

        (gm2, cnt2, nid2, vc2, _, _, over2), ys = lax.scan(
            body,
            (
                cr["gmap"], cr["count"], cr["next_id"], cr["vcount"],
                cr["v"]["seed"], cr["v"]["trace"], cr["over"],
            ),
            (out["cov"], out["failing"], out["seed"], out["trace"]),
        )
        fresh_bits, ids, cslot, vslot = ys
        gen_col = jnp.full((batch,), g.astype(jnp.int32))

        def scatter(store, slots, trash):
            idx = jnp.where(slots >= 0, slots, trash)
            s2 = dict(store)
            for f in _ROW_KEYS:
                s2[f] = store[f].at[idx].set(out[f])
            s2["seed"] = store["seed"].at[idx].set(out["seed"])
            s2["trace"] = store["trace"].at[idx].set(out["trace"])
            s2["cov"] = store["cov"].at[idx].set(out["cov"])
            s2["new_bits"] = store["new_bits"].at[idx].set(fresh_bits)
            s2["id"] = store["id"].at[idx].set(ids)
            s2["parent"] = store["parent"].at[idx].set(out["parent"])
            s2["gen"] = store["gen"].at[idx].set(gen_col)
            s2["viol"] = store["viol"].at[idx].set(out["failing"])
            s2["halt"] = store["halt"].at[idx].set(out["halt"])
            s2["bslot"] = store["bslot"].at[idx].set(out["bslot"])
            return s2

        cr2 = dict(
            c=scatter(cr["c"], cslot, max_corpus),
            v=scatter(cr["v"], vslot, vcap),
            gmap=gm2,
            count=cnt2,
            next_id=nid2,
            vcount=vc2,
            over=over2,
        )
        summary = dict(
            count=cnt2,
            next_id=nid2,
            vcount=vc2,
            admitted=(cslot >= 0).sum().astype(jnp.int32),
            cov_bits=lax.population_count(gm2).sum().astype(jnp.int32),
            over=over2,
        )
        return cr2, summary

    def prog(cr, g, rk0, rk1, breed: bool):
        out = (
            sm_breed(cr, g, rk0, rk1) if breed
            else sm_uniform(g, rk0, rk1)
        )
        rep = NamedSharding(mesh, P_()) if mesh is not None else None
        if mesh is not None:
            # gather the generation's per-seed rows onto every device
            # before the admission scan: the scan is inherently
            # sequential (batch-order semantics), and scanning over
            # batch-sharded xs trips the SPMD partitioner (mixed-width
            # index arithmetic in the per-iteration slices). One
            # all-gather of (batch, slots) rows per generation — still
            # device-resident, never the host. The met/lat_hist tap
            # columns stay SHARDED: the admission scan never reads
            # them, and merge_metrics/merge_latency fold them as
            # per-device local sums (D rows to the host, no gather).
            out = {
                k: (v if k in ("met", "lat_hist", "row_steps")
                    else lax.with_sharding_constraint(v, rep))
                for k, v in out.items()
            }
        cr2, summary = admission(cr, g, out)
        summary["row_steps"] = out["row_steps"]
        if mesh is not None:
            # pin the carry's output shardings to replicated — the
            # compiled program's carry feeds straight back in next
            # generation, and an AOT executable (unlike jit) does not
            # silently recompile on a sharding drift
            cr2 = jax.tree.map(
                lambda a: lax.with_sharding_constraint(a, rep), cr2
            )
        extras = {
            k: out[k] for k in ("met", "lat_hist") if k in out
        }
        return cr2, summary, extras

    refs = (wl, invariant, mesh, space)
    return (
        AotProgram(
            "explore.device.uniform", (cache_key, "uniform"),
            lambda cr, g, rk0, rk1: prog(cr, g, rk0, rk1, False),
        ),
        AotProgram(
            "explore.device.breed", (cache_key, "breed"),
            lambda cr, g, rk0, rk1: prog(cr, g, rk0, rk1, True),
        ),
        refs,
    )


# ---------------------------------------------------------------------------
# the campaign
# ---------------------------------------------------------------------------


class _CampaignSession:
    """Everything a device campaign shares between schedules.

    ``run_device`` below and the pipelined driver
    (``madsim_tpu.farm.pipeline.run_pipelined``) are the SAME campaign
    — argument validation, checkpoint resume, the device carry, the
    cached generation programs, host mirrors, telemetry and report
    assembly all live here; the two drivers differ only in *when* they
    block on a generation's admission summary. Keeping the semantics in
    one place is what makes the pipelined schedule a scheduling change
    rather than a semantic fork (the bit-identity tests lean on it).
    """

    def __init__(
        self, wl, cfg, space, build, *, invariant, generations, batch,
        root_seed, max_steps, require_halt, seed_corpus, select_top,
        max_corpus, max_ops, inherit_seed_p, log, telemetry, resume,
        checkpoint_path, mesh, viol_cap, history_check,
    ):
        if isinstance(space, FaultPlan):
            space = PlanSpace(space)
        if history_check is not None:
            from ..check.device import as_screens

            history_check = as_screens(history_check)
            if wl.history is None:
                raise ValueError(
                    f"history_check judges operation histories, but workload "
                    f"{wl.name!r} has Workload.history=None"
                )
        if invariant is None and history_check is None:
            raise ValueError(
                "run_device needs a traceable final-state invariant and/or a "
                "history_check screen set (both run inside the device "
                "program); arbitrary host-side history_invariant callables "
                "need the host driver — use explore.run for those hunts"
            )
        cov_words, cov_hitcount = build.cov_words, build.cov_hitcount
        if cov_words < 1:
            raise ValueError(
                "exploration needs cov_words >= 1 (the guidance)"
            )
        if generations < 1 or batch < 1:
            raise ValueError("need generations >= 1 and batch >= 1")
        if len(seed_corpus) > batch:
            raise ValueError(
                f"{len(seed_corpus)} seed-corpus plans exceed batch={batch}"
            )
        n_dev = int(mesh.devices.size) if mesh is not None else 1
        if batch % n_dev:
            raise ValueError(
                f"batch={batch} does not split over {n_dev} mesh devices"
            )
        vcap = int(viol_cap) if viol_cap is not None else int(max_corpus)
        # the sweep's plan axes come from the space: its slot count,
        # duplication, and the retry flag derived from its ClientArmy
        # policy (explore.run's rule; LiteralPlan spaces have no
        # retry_spec and run fire-and-forget)
        build = _dc.replace(
            build, plan_slots=space.slots, dup_rows=space.uses_dup(),
            retry=(
                space.plan.retry_spec()
                if hasattr(space.plan, "retry_spec") else None
            ),
        ).resolve(wl, cfg)
        p_slots = space.slots
        cmax1 = int(max_corpus) + 1
        vcap1 = vcap + 1

        # host-side validations the host driver gets from search_seeds:
        # plan targets/user kinds against the workload, and the time32
        # horizon (checked statically over the template windows —
        # mutation and compilation both stay inside them)
        space.plan.compile_batch(np.zeros(1, np.uint64), wl=wl)
        if build.time32:
            worst = int(mutation_table(space)["t_hi"].max(initial=1)) - 1
            if seed_corpus:
                worst = max(
                    worst,
                    max(e.t for lp in seed_corpus for e in lp.events),
                )
            check_plan_horizon(build, cfg, worst, space=True)

        # ---- resumed / fresh host mirrors ----
        loaded_corpus: list = []
        loaded_viol: list = []
        if resume is not None:
            from .persist import resolve_resume

            st = resolve_resume(resume, wl, space, cfg, root_seed, batch,
                                cov_words, cov_hitcount)
            if len(st.corpus) > max_corpus:
                raise ValueError(
                    f"checkpoint carries {len(st.corpus)} corpus entries; "
                    f"max_corpus={max_corpus} cannot hold them"
                )
            if len(st.violations) > vcap:
                raise ValueError(
                    f"checkpoint carries {len(st.violations)} violations; "
                    f"raise viol_cap (now {vcap})"
                )
            loaded_corpus = list(st.corpus)
            loaded_viol = list(st.violations)
            gmap0 = np.asarray(st.cov_map, np.uint32)
            self.curve = list(st.curve)
            self.viol_curve = list(st.viol_curve)
            next_id0 = st.next_id
            self.sims = st.sims
            self.g_start = st.generations_done
        else:
            gmap0 = np.zeros((cov_words,), np.uint32)
            self.curve = []
            self.viol_curve = []
            next_id0 = 0
            self.sims = 0
            self.g_start = 0
        # row-step counters of the generations this session runs (like
        # the wall split, a resumed campaign counts only its own)
        self.row_steps_paid = 0
        self.row_steps_live = 0

        carry = dict(
            c=_fill_store(
                _empty_store(cmax1, p_slots, cov_words), loaded_corpus
            ),
            v=_fill_store(
                _empty_store(vcap1, p_slots, cov_words), loaded_viol
            ),
            gmap=jnp.asarray(gmap0),
            count=jnp.int32(len(loaded_corpus)),
            next_id=jnp.int32(next_id0),
            vcount=jnp.int32(len(loaded_viol)),
            over=jnp.bool_(False),
        )
        if mesh is not None:
            # commit the carry replicated up front: the cached generation
            # programs are AOT executables pinned to their input shardings
            # (obs.prof.AotProgram), and their outputs are constrained
            # replicated to match — input placement must agree from the
            # first call
            carry = jax.device_put(carry, NamedSharding(mesh, P_()))
        self.carry = carry
        self.count = len(loaded_corpus)  # host mirror (uniform vs breed)

        # materialized-entry caches: slot -> CorpusEntry, shared by every
        # EntryView of the session. Loaded entries are returned as the
        # same objects (names and identity survive resume); a new slot
        # is built the first time any view reads it, and reused after.
        self._c_cache = {i: e for i, e in enumerate(loaded_corpus)}
        self._v_cache = {i: e for i, e in enumerate(loaded_viol)}

        # ---- the device programs (built once per cache key) ----
        k_ov = len(seed_corpus)
        key = (
            id(wl), id(invariant), cfg.hash(), space.hash(), batch,
            max_steps, build, require_halt, select_top, int(max_corpus),
            vcap, max_ops, float(inherit_seed_p), _mesh_key(mesh),
            tuple(lp.hash() for lp in seed_corpus),
            # invariant identity of the device history screen: screens
            # are value-hashable literals, so equal screen sets share
            # programs across campaigns (the ROADMAP "invariant
            # identity" key component)
            history_check,
        )
        self.prog_uniform, self.prog_breed = _gen_programs(
            key,
            lambda: _build_programs(
                wl, cfg, space, build, invariant=invariant, batch=batch,
                max_steps=max_steps, require_halt=require_halt,
                select_top=select_top, max_corpus=int(max_corpus),
                vcap=vcap, max_ops=max_ops, inherit_seed_p=inherit_seed_p,
                mesh=mesh, seed_corpus=seed_corpus, cache_key=key,
                history_check=history_check,
            ),
        )

        self.wl = wl
        self.cfg = cfg
        self.space = space
        self.generations = generations
        self.batch = batch
        self.root_seed = int(root_seed)
        self.max_steps = max_steps
        self.cov_words = cov_words
        self.cov_hitcount = cov_hitcount
        self.log = log
        self.telemetry = telemetry
        self.checkpoint_path = checkpoint_path
        self.mesh = mesh
        self.n_dev = n_dev
        self.vcap = vcap
        self.seed_corpus = seed_corpus
        self.k_ov = k_ov
        self.next_id = next_id0  # host mirror for snapshots
        self.vcount_host = len(loaded_viol)
        self.log_label = "device"
        # the campaign root key enters the cached programs as a RUNTIME
        # argument (same threefry coordinates as driver._derive_keys),
        # so one compiled program serves every root seed
        self.rk0 = jnp.uint32(self.root_seed & 0xFFFFFFFF)
        self.rk1 = jnp.uint32((self.root_seed >> 32) & 0xFFFFFFFF)

    # ---- scheduling primitives -----------------------------------------
    def runner(self, breed: bool):
        return self.prog_breed if breed else self.prog_uniform

    def fleet(self, extras) -> dict:
        """Fold a generation's sharded tap columns into fleet totals."""
        fleet: dict = {}
        if extras:
            from .. import parallel as _par

            if "met" in extras:
                fleet["met_total"] = [
                    int(x)
                    for x in _par.merge_metrics(extras["met"], self.mesh)
                ]
            if "lat_hist" in extras:
                fleet["lat_total_ops"] = int(
                    _par.merge_latency(extras["lat_hist"], self.mesh).sum()
                )
        return fleet

    def consume(self, g: int, s, fleet: dict, walls: dict,
                carry=None) -> tuple[int, int]:
        """Fold generation ``g``'s admission summary into the host
        mirrors: curve/corpus-count/violation bookkeeping, the
        row-step counters, the generation telemetry record (``walls``
        carries the driver's wall split), the log line, and the
        per-generation checkpoint. ``carry`` is the carry AS OF after
        ``g`` — the pipelined driver passes it explicitly because its
        ``self.carry`` has already speculated ahead. Returns the
        generation's (paid, live) row-steps, summed over shards."""
        paid, live = (
            int(x) for x in np.asarray(s["row_steps"]).reshape(-1, 2).sum(0)
        )
        self.row_steps_paid += paid
        self.row_steps_live += live
        if bool(s["over"]):
            raise RuntimeError(
                f"device violation store overflowed (viol_cap={self.vcap}) "
                f"at generation {g}: the (seed, trace) dedup can no longer "
                f"match the host driver — raise viol_cap"
            )
        self.sims += self.batch
        self.count = int(s["count"])
        self.next_id = int(s["next_id"])
        new_viol = int(s["vcount"]) - self.vcount_host
        self.vcount_host = int(s["vcount"])
        self.curve.append(int(s["cov_bits"]))
        self.viol_curve.append(self.vcount_host)
        if self.log is not None:
            self.log(
                f"explore[{self.log_label}] g{g}: {self.curve[-1]} "
                f"coverage bits (+{int(s['admitted'])} corpus entries, "
                f"corpus {self.count}), {self.vcount_host} violations"
            )
        self.emit({
            "event": "generation", "generation": g, "sims": self.sims,
            "cov_bits": self.curve[-1], "new_entries": int(s["admitted"]),
            "corpus_size": self.count, "violations": self.vcount_host,
            "new_violations": new_viol, **walls, "host_syncs": 1, **fleet,
        })
        if self.checkpoint_path is not None:
            self.snapshot(g + 1, carry=carry).save(self.checkpoint_path)
        return paid, live

    # ---- materialization ------------------------------------------------
    def _entry_name(self, rows, i: int) -> str:
        gen, parent = int(rows["gen"][i]), int(rows["parent"][i])
        if parent >= 0:
            return f"g{gen}p{parent}"
        bslot = int(rows["bslot"][i])
        if gen == 0 and 0 <= bslot < self.k_ov:
            return self.seed_corpus[bslot].name
        return f"{self.space.plan.name}@{int(rows['seed'][i])}"

    def _materialize(self, carry, *, build: bool = False):
        """The corpus and violations as of ``carry``: one host copy of
        both stores, read through :class:`EntryView`s; ``build=True``
        builds every entry inside the span and returns lists. The span
        carries ``entries`` (corpus plus violations) and ``built`` (the
        entries constructed inside it)."""
        with span("madsim.explore.materialize") as sp:
            corpus, violations, gm = self._views(jax.device_get(carry))
            views = (corpus, violations)
            if build:
                corpus, violations = list(corpus), list(violations)
            sp.set_metadata(entries=len(corpus) + len(violations),
                            built=sum(v.built for v in views))
        return corpus, violations, gm

    def _views(self, carry_host):
        cn, vn = carry_host["c"], carry_host["v"]
        n_c = int(carry_host["count"])
        n_v = min(int(carry_host["vcount"]), self.vcap)
        corpus = EntryView(cn, n_c, self._c_cache, self._entry_name)
        # a violating entry that also joined the corpus is the SAME
        # object in both sequences (the host driver's sharing)
        slot_of = {e: i for i, e in enumerate(cn["id"][:n_c].tolist())}

        def shared(i):
            slot = slot_of.get(int(vn["id"][i]))
            return None if slot is None else corpus[slot]

        violations = EntryView(vn, n_v, self._v_cache, self._entry_name,
                               shared)
        return corpus, violations, np.asarray(carry_host["gmap"], np.uint32)

    def snapshot(self, gens_done: int, carry=None):
        from .persist import CampaignState

        corpus, violations, gm = self._materialize(
            self.carry if carry is None else carry, build=True
        )
        return CampaignState(
            workload=self.wl.name, config_hash=self.cfg.hash(),
            plan_hash=self.space.hash(), root_seed=self.root_seed,
            batch=self.batch, cov_words=self.cov_words,
            cov_hitcount=self.cov_hitcount, generations_done=gens_done,
            next_id=self.next_id, sims=self.sims, curve=list(self.curve),
            viol_curve=list(self.viol_curve), cov_map=gm.copy(),
            corpus=corpus, violations=violations,
        )

    # ---- telemetry + report ---------------------------------------------
    def emit(self, record: dict) -> None:
        if self.telemetry is not None:
            self.telemetry(record)

    def start(self, driver: str, **extra) -> None:
        self.emit({
            "event": "campaign_start", "workload": self.wl.name,
            "config_hash": self.cfg.hash(), "plan_hash": self.space.hash(),
            "root_seed": self.root_seed, "batch": self.batch,
            "generations": self.generations, "cov_words": self.cov_words,
            "cov_hitcount": self.cov_hitcount,
            "resumed_at_generation": self.g_start,
            "driver": driver, "mesh_devices": self.n_dev, **extra,
        })

    def report(self, *, wall_dispatch, wall_sync, wall_compile, host_syncs,
               wall_queue=0.0, wall_idle=0.0) -> ExploreReport:
        corpus, violations, gm = self._materialize(self.carry)
        return ExploreReport(
            workload=self.wl.name,
            config_hash=self.cfg.hash(),
            plan_hash=self.space.hash(),
            root_seed=self.root_seed,
            generations=self.g_start + self.generations,
            batch=self.batch,
            max_steps=self.max_steps,
            cov_words=self.cov_words,
            sims=self.sims,
            corpus=corpus,
            violations=violations,
            cov_map=gm,
            curve=self.curve,
            viol_curve=self.viol_curve,
            next_id=self.next_id,
            cov_hitcount=self.cov_hitcount,
            wall_dispatch_s=wall_dispatch,
            wall_host_s=wall_sync,
            wall_compile_s=wall_compile,
            host_syncs=host_syncs,
            wall_gens=self.generations,
            wall_queue_s=wall_queue,
            wall_idle_s=wall_idle,
            row_steps_paid=self.row_steps_paid,
            row_steps_live=self.row_steps_live,
        )


def run_device(
    wl,
    cfg,
    space,
    *,
    invariant,
    generations: int = 8,
    batch: int = 256,
    root_seed: int = 0,
    max_steps: int = 1000,
    cov_words: int = 32,
    layout: str | None = None,
    require_halt: bool = False,
    seed_corpus=(),
    select_top: int = 32,
    max_corpus: int = 4096,
    max_ops: int = 3,
    inherit_seed_p: float = 0.75,
    log=None,
    cov_hitcount: bool = False,
    telemetry=None,
    resume=None,
    checkpoint_path: str | None = None,
    latency=None,
    metrics: bool = False,
    mesh=None,
    viol_cap: int | None = None,
    pool_index: bool | None = None,
    history_check=None,
    causal: bool = False,
) -> ExploreReport:
    """Run one exploration campaign with every generation device-resident.

    Same contract and bit-identical outcomes as :func:`explore.run`
    (module docstring), with these differences:

    * ``invariant`` must be jnp-traceable over the final state view
      (``{field: array} -> (S,) bool``) — it runs inside the device
      program. ``history_check`` (a ``check.device.HistoryScreen`` or
      tuple) is the device form of a ``history_invariant`` hunt: the
      batch detectors trace into the cached generation program (the
      screen tuple is a ``_GEN_CACHE`` key component) and their
      verdicts mark violations exactly like the host driver running
      ``check.device.screens_invariant(history_check)`` — the two
      campaigns are bit-identical, and a device find replays/shrinks
      on the host driver through that same invariant. At least one of
      the two must be given; arbitrary host-side ``history_invariant``
      callables still need the host driver.
    * ``mesh`` (a ``parallel.make_mesh`` Mesh) shards mutation and the
      sweep across chips with ``shard_map``; ``batch`` must divide over
      the device count. Sharded and unsharded campaigns are identical.
    * ``metrics=True`` folds per-generation fleet-metric totals into the
      telemetry records (``parallel.merge_metrics`` — per-device sums,
      device-count rows to the host); ``latency`` likewise folds fleet
      sketches via ``parallel.merge_latency``. Both are derived state:
      campaign outcomes are unchanged.
    * ``causal=True`` runs the generations with the engine's causal
      columns on (``explore.run`` docstring): the causal-depth/width
      coverage feature class joins the guidance, at the cost of the
      per-seed provenance columns riding the sweep. The flag is a
      ``_GEN_CACHE`` key component — on/off campaigns never share a
      compiled program.
    * ``viol_cap`` bounds the device violation store (default
      ``max_corpus``); a campaign that finds more raises instead of
      silently breaking the (seed, trace) dedup.
    * ``checkpoint_path`` materializes the corpus to the host after
      every generation (that is what a checkpoint IS) — set it only
      when resumability is worth the extra transfer.

    The per-generation host sync transfers only the admission summary
    (corpus size, new entries, coverage bits, violation count) and the
    fresh violation keys; telemetry records carry the
    dispatch/compile/sync wall split and ``host_syncs: 1`` so the
    claim is checkable from the artifact. ``compile_wall_s`` is
    nonzero only when the generation-program cache was cold for this
    campaign shape — hold one workload/invariant object across
    campaigns (the ``engine.search`` rule) and every later campaign
    runs compile-free.
    """
    with span("madsim.explore.start"):
        sess = _CampaignSession(
            wl, cfg, space,
            Build(
                layout=layout, pool_index=pool_index, cov_words=cov_words,
                cov_hitcount=cov_hitcount, metrics=metrics, latency=latency,
                causal=causal,
            ),
            invariant=invariant, generations=generations, batch=batch,
            root_seed=root_seed, max_steps=max_steps,
            require_halt=require_halt, seed_corpus=seed_corpus,
            select_top=select_top, max_corpus=max_corpus, max_ops=max_ops,
            inherit_seed_p=inherit_seed_p, log=log, telemetry=telemetry,
            resume=resume, checkpoint_path=checkpoint_path, mesh=mesh,
            viol_cap=viol_cap, history_check=history_check,
        )
        sess.start("device")

    wall_dispatch = 0.0
    wall_sync = 0.0
    wall_compile = 0.0
    host_syncs = 0

    for g in range(sess.g_start, sess.g_start + generations):
        with span("madsim.explore.generation", g=g):
            t0 = _time.monotonic()  # lint: allow(wall-clock)
            breed = g > 0 and sess.count > 0
            runner = sess.runner(breed)
            sess.carry, summary, extras = runner(
                sess.carry, jnp.uint32(g), sess.rk0, sess.rk1
            )
            jax.block_until_ready(summary)
            t1 = _time.monotonic()  # lint: allow(wall-clock)
            # trace/lower/compile share of this generation (0.0 on a
            # warm program cache — the certified steady state), split
            # out of dispatch so warm-vs-cold comparisons compare like
            # with like
            compile_wall = runner.last_build_s
            with span("madsim.explore.sync") as sp:
                # THE host sync: admission summary + banner counters
                # only — per-seed state stays on device
                s = jax.device_get(summary)
                host_syncs += 1
                fleet = sess.fleet(extras)
                t2 = _time.monotonic()  # lint: allow(wall-clock)
                wall_dispatch += (t1 - t0) - compile_wall
                wall_sync += t2 - t1
                wall_compile += compile_wall
                paid, live = sess.consume(g, s, fleet, {
                    "dispatch_wall_s": round((t1 - t0) - compile_wall, 3),
                    "compile_wall_s": round(compile_wall, 3),
                    "sync_wall_s": round(t2 - t1, 3),
                    # the pipeline wall split, zero by construction on
                    # the blocking schedule (the driver never enqueues
                    # ahead)
                    "queue_wall_s": 0.0,
                    "idle_wall_s": 0.0,
                })
                sp.set_metadata(row_steps_paid=paid, row_steps_live=live)

    sess.emit({
        "event": "campaign_end", "generations": sess.g_start + generations,
        "generations_run": generations,
        "sims": sess.sims,
        "cov_bits": sess.curve[-1] if sess.curve else 0,
        "corpus_size": sess.count, "violations": sess.vcount_host,
        "wall_dispatch_s": round(wall_dispatch, 3),
        "wall_sync_s": round(wall_sync, 3),
        "wall_compile_s": round(wall_compile, 3),
        "wall_queue_s": 0.0,
        "wall_idle_s": 0.0,
        "host_syncs": host_syncs,
    })
    return sess.report(
        wall_dispatch=wall_dispatch, wall_sync=wall_sync,
        wall_compile=wall_compile, host_syncs=host_syncs,
    )
