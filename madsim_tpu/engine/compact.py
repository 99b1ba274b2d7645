"""Seed-compaction runner: stop paying lockstep steps for halted seeds.

The plain batched loop (``make_run_while``) advances every seed until the
*slowest* seed halts. Halting workloads have long tails — measured raft
(8,192 seeds, CPU): median seed halts in 15 steps, p99 in 25, the last
straggler at ~50, so lockstep wastes >3x of the step budget on rows that
are already frozen. The reference never pays this cost because each seed
runs on its own OS thread and simply exits (reference
madsim/src/sim/runtime/builder.rs:110-148, one thread per seed); this
module is the batched analog of "finished seeds stop consuming CPU".

Mechanism: seeds are independent rows under ``vmap`` (no cross-seed ops
anywhere in the engine), so a run can be split into *phases* of static,
shrinking batch sizes inside one jitted program:

    phase 0: while_loop at S rows      until live <= S/shrink
    compact: stable-partition live rows to the front (argsort + gather),
             hand the halted tail back as a banked output
    phase 1: while_loop at S/shrink rows ...
    ...
    final phase: run until every row halts (or the step cap)

Every shape is static (XLA requirement); the *schedule* of sizes is
fixed at trace time and each phase's while_loop exits exactly when the
live count fits the next size. Banked rows leave the hot loop, so the
tail of stragglers runs at 1/shrink^k of the full-batch step cost.

Exactness: a row's trajectory depends only on its own state row (seed,
RNG step coordinate, event pool, node arrays, clog matrix), so
reordering and slicing rows never changes any row's values — the
per-seed (now, trace, node_state, ...) results are bit-identical to the
uncompacted loop, which tests/test_compact.py asserts. The single
intentional divergence is ``SimState.step``: lockstep increments it for
halted rows too, while compaction stops counting once a row is banked.
The counter is the RNG coordinate (engine/rng.py) and halted rows make
no further draws, so nothing downstream can observe the difference.

The total-step cap is shared across phases (one counter threaded through
all while_loops), so ``max_steps`` means the same thing as in
``make_run_while``: rows still live when the cap hits are frozen
mid-flight exactly like the lockstep loop would leave them.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .core import EngineConfig, SimState, Workload, make_step

__all__ = ["make_run_compacted"]

# SimState fields reported per original seed. 'step' is excluded from
# equality guarantees (see module docstring) but still banked so callers
# can inspect it. The history columns ride along so the check package
# works on compacted results; with Workload.history=None they are
# zero-size arrays and cost nothing.
RESULT_FIELDS = (
    "seed",
    "now",
    "step",
    "halted",
    "halt_time",
    "trace",
    "overflow",
    "msg_count",
    "node_state",
    # synced durable image (Workload.durable_sync): zero-size when the
    # sync discipline is off; banked so recovery-state invariants can
    # compare buffered vs committed durable columns on compacted runs
    "disk",
    "hist_count",
    "hist_drop",
    "hist_word",
    "hist_t",
    # coverage bitmap (madsim_tpu.explore): zero-size with cov_words=0
    "cov",
    # observability columns (madsim_tpu.obs): all zero-size when the
    # metrics/timeline taps are off. cov_hits is deliberately NOT banked
    # — guidance consumes only the bitmap, and the counters would add
    # CW*32 bytes/seed of transfer for nothing.
    "met",
    "tl_count",
    "tl_drop",
    "tl_t",
    "tl_meta",
    "tl_args",
    "tl_pay",
    "tl_emit",
    # causal provenance (causal=True): the final Lamport clocks and the
    # ring's DAG columns bank; the pool-side ev_parent/ev_lam sidecars
    # do NOT — they are live-pool forensics only readable against a
    # pool the bank deliberately drops (the lat_inv/lat_resp rule).
    "lam",
    "tl_seq",
    "tl_parent",
    "tl_lam",
    # tail-latency columns (madsim_tpu.obs latency): the sketch and its
    # counters bank (SLO invariants read lat_hist on compacted runs);
    # the per-op lat_inv/lat_resp clocks do NOT — they are the heavy
    # (C,)-wide forensics columns, and banked sweeps consume only the
    # sketch (the cov_hits rule applied again).
    "lat_hist",
    "lat_count",
    "lat_drop",
)

# extra banked outputs of a ``hist_screen`` run (not SimState fields):
# the per-seed device verdict and the prefix-compaction fold counter
SCREEN_FIELDS = ("hist_ok", "hist_fold")


class HostFetch:
    """``np.asarray`` that counts the bytes crossing device->host: each
    device array once (a fetched array keeps its host copy), host
    arrays free. ``nbytes`` is the running total."""

    def __init__(self):
        self.nbytes = 0
        self._fetched: list = []

    def __call__(self, x) -> np.ndarray:
        if isinstance(x, jax.Array) and not any(
            x is y for y in self._fetched
        ):
            self._fetched.append(x)
            self.nbytes += int(x.nbytes)
        return np.asarray(x)


def row_steps(banked, fetch=np.asarray) -> tuple[int, int]:
    """(paid, live) row-steps of one phase-program result: rows x loop
    iterations, each phase at its own size, and those in which the row
    had not yet halted — summed over the shards of a mesh run."""
    paid, live = fetch(banked[-1]["_row_steps"]).reshape(-1, 2).sum(0)
    return int(paid), int(live)


def _phase_sizes(s0: int, shrink: int, min_size: int) -> list[int]:
    sizes = [s0]
    while sizes[-1] // shrink >= min_size:
        sizes.append(sizes[-1] // shrink)
    return sizes


def make_run_compacted(
    wl: Workload,
    cfg: EngineConfig,
    max_steps: int,
    layout: str | None = None,
    time32: bool | None = None,
    shrink: int = 4,
    min_size: int = 2048,
    fields: tuple = RESULT_FIELDS,
    dup_rows: bool = False,
    cov_words: int = 0,
    metrics: bool = False,
    timeline_cap: int = 0,
    cov_hitcount: bool = False,
    latency=None,
    placement: str | None = None,
    pool_index: bool | None = None,
    rank_place_max_pool: int | None = None,
    hist_screen=None,
    causal: bool = False,
    retry=None,
):
    """Build ``run(state) -> SimpleNamespace`` of per-original-seed results.

    The returned callable takes the batched :class:`SimState` from
    ``make_init`` and returns numpy arrays (one per name in ``fields``,
    leading axis = the original seed order) — the same fields bench.py
    and the verify tools read off a ``SimState``, minus the live event
    pool (which only straggler rows still meaningfully own).

    ``shrink``/``min_size`` set the static phase schedule; with
    ``min_size >= n_seeds`` the program degenerates to exactly one
    while_loop — the plain ``make_run_while``.

    ``hist_screen`` (a ``check.device.HistoryScreen`` or tuple of them)
    turns on device-resident verification with history
    **prefix-compaction**: the moment a bank of halted rows leaves the
    hot loop, the screen kernels judge their histories ON DEVICE and —
    for seeds the screen passed — responded (invoke, response) pairs
    fold out of the banked columns (``check.device.fold_verified``),
    so the device→host transfer carries only still-pending invokes
    plus the *flagged* seeds' full histories. Two extra result fields
    appear: ``hist_ok`` (the per-seed verdict, computed BEFORE the
    fold) and ``hist_fold`` (records folded — loud, ``hist_drop``-
    style accounting: original count == hist_count + hist_fold).
    Flagged and overflowed seeds keep every record verbatim, so the
    exact-checker escalation (Wing–Gong over flagged seeds) is
    unaffected by construction. Requires ``wl.history``.
    """
    step = jax.vmap(make_step(
        wl, cfg, layout, time32, dup_rows, cov_words,
        metrics, timeline_cap, cov_hitcount, latency, placement,
        pool_index, rank_place_max_pool, causal, retry=retry,
    ))
    all_names = [f.name for f in dataclasses.fields(SimState)]
    for f in fields:
        if f not in all_names:
            raise ValueError(f"unknown SimState field {f!r}")
    if shrink < 2:
        raise ValueError(f"shrink must be >= 2, got {shrink}")
    if min_size < 1:
        raise ValueError(f"min_size must be >= 1, got {min_size}")
    if hist_screen is not None:
        # imported here: check.device is a consumer of the engine
        from ..check.device import as_screens, fold_verified, screen_ok

        if wl.history is None:
            raise ValueError(
                f"hist_screen judges operation histories, but workload "
                f"{wl.name!r} has Workload.history=None"
            )
        screens = as_screens(hist_screen)
        hist_fields = ("hist_word", "hist_t", "hist_count", "hist_drop")
        missing = [f for f in hist_fields if f not in fields]
        if missing:
            raise ValueError(
                f"hist_screen needs the history columns banked; "
                f"fields is missing {missing}"
            )
    else:
        screens = None

    def _bank(st: SimState, idx: jnp.ndarray) -> dict:
        out = {f: getattr(st, f) for f in fields}
        if screens is not None:
            # bank-time device verification + prefix-compaction: the
            # verdict judges the FULL history (identical to screening
            # the uncompacted run), then clean seeds' responded pairs
            # fold out of what ships to the host
            ok = screen_ok(
                screens, st.hist_word, st.hist_t, st.hist_count,
                st.hist_drop,
            )
            w2, t2, c2, fold = fold_verified(
                st.hist_word, st.hist_t, st.hist_count, st.hist_drop, ok
            )
            out["hist_word"], out["hist_t"], out["hist_count"] = w2, t2, c2
            out["hist_ok"], out["hist_fold"] = ok, fold
        out["_idx"] = idx
        return out

    def compiled(state: SimState, idx_offset=0):
        """The phase program. Shapes are static per input size, so the
        same traced function serves the full batch (make_run_compacted)
        or one device's shard (parallel.shard_run_compacted, which
        passes the shard's global row offset as ``idx_offset``)."""
        s0 = state.seed.shape[0]
        sizes = _phase_sizes(s0, shrink, min_size)
        idx = jnp.arange(s0, dtype=jnp.int32) + jnp.asarray(idx_offset, jnp.int32)
        steps = jnp.int64(0)
        st = state
        banked = []
        # row-step counters: rows x iterations paid at each phase's size,
        # and the rows of each iteration not yet halted when it ran. The
        # live count the loop exits on is carried from the body, so the
        # counters add no reduction per iteration
        live = jnp.sum(~st.halted).astype(jnp.int64)
        paid = live_steps = jnp.int64(0)

        for size, next_size in zip(sizes, list(sizes[1:]) + [0]):

            def cond(carry, _n=next_size):
                s, i, n_live, acc = carry
                return (i < max_steps) & (n_live > _n)

            def body(carry):
                s, i, n_live, acc = carry
                s = step(s)
                n_live2 = jnp.sum(~s.halted).astype(jnp.int64)
                return s, i + 1, n_live2, acc + n_live

            steps0 = steps
            st, steps, live, live_steps = lax.while_loop(
                cond, body, (st, steps, live, live_steps)
            )
            paid = paid + (steps - steps0) * size
            if next_size == 0:
                banked.append(_bank(st, idx))
                banked[-1]["_row_steps"] = jnp.stack([paid, live_steps])
                break
            # stable partition: live rows first, halted tail banked.
            # Stability keeps the relative order of live rows, so the
            # kept prefix is a pure row-subset of the lockstep batch.
            order = jnp.argsort(st.halted, stable=True)
            tail = order[next_size:]
            banked.append(_bank(jax.tree.map(lambda a: a[tail], st), idx[tail]))
            head = order[:next_size]
            st = jax.tree.map(lambda a: a[head], st)
            idx = idx[head]

        return banked

    # no donate_argnums: banked phase-0 rows alias the input buffers, so
    # XLA can't actually reuse them (it would only warn); the one extra
    # input-sized allocation is cheap next to the loop carries
    jitted = jax.jit(compiled)

    out_fields = fields if screens is None else fields + SCREEN_FIELDS

    def assemble(banked, fetch=None) -> SimpleNamespace:
        """Device->host transfer + scatter back into original seed order.

        Under a ``hist_screen``, the folded history columns transfer
        only up to the longest surviving record count across the banks
        (fetched first — one tiny counter read): the fold's whole point
        is that the big (rows, H, ...) column transfer shrinks to the
        pending-invoke prefix plus the flagged seeds' full histories.

        ``fetch`` (a :class:`HostFetch`) counts the bytes copied.
        """
        fetch = HostFetch() if fetch is None else fetch
        idxs = [fetch(b["_idx"]) for b in banked]
        s0 = sum(i.shape[0] for i in idxs)
        trim = {}
        if screens is not None:
            kept = max(
                (int(fetch(b["hist_count"]).max(initial=0)) for b in banked),
                default=0,
            )
            trim = {"hist_word": kept, "hist_t": kept}
        out = {}
        for f in out_fields:
            proto = banked[0][f]
            buf = np.zeros((s0,) + tuple(proto.shape[1:]), proto.dtype)
            k = trim.get(f)
            for b, idx in zip(banked, idxs):
                if k is None:
                    buf[idx] = fetch(b[f])
                else:
                    # device-side slice: only the surviving prefix
                    # crosses the boundary (rows past hist_count are
                    # zero by the fold, so the untransferred tail of
                    # the host buffer is value-identical)
                    buf[idx, :k] = fetch(b[f][:, :k])
            out[f] = buf
        return SimpleNamespace(**out)

    def run(state: SimState) -> SimpleNamespace:
        return assemble(jax.block_until_ready(jitted(state)))

    # benchmark seam: time `compute` (device work only, block on device
    # arrays) and call `assemble` outside the window — keeps the metric
    # methodologically identical to timing the lockstep loop, where the
    # host read also happened after the timed region. The banks' last
    # entry also carries the loop's `_row_steps` (module row_steps)
    run.compute = jitted
    run.assemble = assemble
    # sharding seam: the raw phase program, for parallel.shard_run_compacted
    run.phases = compiled
    return run
