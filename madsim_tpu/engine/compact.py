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

Results: after the last phase the program concatenates the banks,
gathers them back into seed order by the inverse of their row indices,
and packs every result field into one flat uint32 buffer
(:class:`Packed`), so the host makes one copy — not one per field per
bank followed by a scatter — and gets each field as a view.

The total-step cap is shared across phases (one counter threaded through
all while_loops), so ``max_steps`` means the same thing as in
``make_run_while``: rows still live when the cap hits are frozen
mid-flight exactly like the lockstep loop would leave them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from types import SimpleNamespace

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .core import Build, EngineConfig, SimState, Workload, make_step

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
    """``np.asarray`` that counts what crosses device->host: each device
    array once (a fetched array keeps its host copy), host arrays free.
    ``nbytes`` is the running total of bytes, ``transfers`` of arrays."""

    def __init__(self):
        self.nbytes = 0
        self.transfers = 0
        self._fetched: list = []

    def __call__(self, x) -> np.ndarray:
        if isinstance(x, jax.Array) and not any(
            x is y for y in self._fetched
        ):
            self._fetched.append(x)
            self.nbytes += int(x.nbytes)
            self.transfers += 1
        return np.asarray(x)


@jax.tree_util.register_pytree_node_class
class Packed:
    """A compacted run's results in original seed order, packed on the
    device into one flat uint32 buffer ``buf``; ``loose`` holds the
    columns that cross on their own (a screened run's
    ``hist_word``/``hist_t``, trimmed first), and the static ``layout``
    says where each packed field lies: one ``(name, word offset,
    shape, dtype)`` entry per field."""

    def __init__(self, buf, loose: dict, layout: tuple):
        self.buf, self.loose, self.layout = buf, loose, layout

    def tree_flatten(self):
        return (self.buf, self.loose), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(*children, layout)

    def fields(self, host: np.ndarray) -> dict:
        """Every packed field as a view of ``host``, the host copy of
        ``buf``."""
        return {name: _view(host[off:], shape, dt)
                for name, off, shape, dt in self.layout}


def _view(words: np.ndarray, shape, dtype) -> np.ndarray:
    """The array of ``shape`` and ``dtype`` whose bytes lead ``words``
    (C-contiguous uint32)."""
    dt = np.dtype(dtype)
    n = math.prod(shape) * dt.itemsize
    return words.reshape(-1).view(np.uint8)[:n].view(dt).reshape(shape)


def _flat_words(x) -> jnp.ndarray:
    """The bytes of ``x`` as uint32 words (a value of under four bytes
    shares its word, the last word zero-padded)."""
    if x.dtype.itemsize >= 4:
        return lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    b = (x.astype(jnp.uint8) if x.dtype == jnp.bool_
         else lax.bitcast_convert_type(x, jnp.uint8)).reshape(-1)
    b = jnp.pad(b, (0, -b.size % 4)).reshape(-1, 4)
    return lax.bitcast_convert_type(b, jnp.uint32)


def _row_words(x) -> jnp.ndarray:
    """(rows, ...) -> (rows, t) uint32 words of each row: an 8-byte
    value as two words, a narrower one widened to one."""
    if x.dtype.itemsize < 4:
        x = x.astype(jnp.int32 if jnp.issubdtype(x.dtype, jnp.signedinteger)
                     else jnp.uint32)
    return lax.bitcast_convert_type(x, jnp.uint32).reshape(x.shape[0], -1)


def _from_row_words(w, like) -> jnp.ndarray:
    """Inverse of :func:`_row_words` for an array shaped like ``like``."""
    if like.dtype.itemsize < 4:
        wide = (jnp.int32 if jnp.issubdtype(like.dtype, jnp.signedinteger)
                else jnp.uint32)
        return lax.bitcast_convert_type(w, wide).reshape(like.shape).astype(
            like.dtype)
    pair = (2,) if like.dtype.itemsize == 8 else ()
    return lax.bitcast_convert_type(w.reshape(like.shape + pair), like.dtype)


def _unpermute(cols: dict, idx) -> dict:
    """Every (rows, ...) column gathered into the order ``argsort(idx)``
    by one row gather of a single uint32 word matrix: on the chip a
    gather per column costs several times more (64-bit columns most),
    and a gather, unlike a scatter, is safe for bools there."""
    live = {f: x for f, x in cols.items() if x.size}
    words = {f: _row_words(x) for f, x in live.items()}
    mat = jnp.concatenate(list(words.values()), axis=1)[jnp.argsort(idx)]
    out, o = dict(cols), 0
    for f, x in live.items():
        t = words[f].shape[1]
        out[f] = _from_row_words(mat[:, o:o + t], x)
        o += t
    return out


def _pack(cols: dict, loose: tuple) -> Packed:
    """Flatten every column but the ``loose`` ones into one uint32
    buffer, field after field, the widest dtypes first so that each
    field lies aligned to its item size on the host."""
    names = sorted((f for f in cols if f not in loose),
                   key=lambda f: -cols[f].dtype.itemsize)
    parts, layout, off = [], [], 0
    for f in names:
        x = cols[f]
        parts.append(_flat_words(x))
        layout.append((f, off, x.shape, np.dtype(x.dtype).str))
        off += parts[-1].size
    return Packed(jnp.concatenate(parts), {f: cols[f] for f in loose},
                  tuple(layout))


@functools.partial(jax.jit, static_argnums=1)
def _prefix_words(x, k: int):
    # compiled once per distinct surviving record count; kept 2-D, as
    # the chip's compiler takes minutes to flatten such an input
    return _row_words(x[:, :k])


def row_steps(banked, fetch=np.asarray) -> tuple[int, int]:
    """(paid, live) row-steps of one phase-program result: rows x loop
    iterations, each phase at its own size, and those in which the row
    had not yet halted — summed over the shards of a mesh run."""
    if isinstance(banked, Packed):
        counts = banked.fields(fetch(banked.buf))["_row_steps"]
    else:
        counts = fetch(banked[-1]["_row_steps"])
    paid, live = counts.reshape(-1, 2).sum(0)
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
    build: Build = Build(),
    *,
    fields: tuple = RESULT_FIELDS,
    shrink: int = 4,
    min_size: int = 2048,
    hist_screen=None,
):
    """Build ``run(state) -> SimpleNamespace`` of per-original-seed results.

    The returned callable takes the batched :class:`SimState` from
    ``make_init`` and returns numpy arrays (one per name in ``fields``,
    leading axis = the original seed order) — the same fields bench.py
    and the verify tools read off a ``SimState``, minus the live event
    pool (which only straggler rows still meaningfully own). Its
    ``compute`` is the jitted program, returning a :class:`Packed`
    result that ``assemble`` copies to the host; ``phases`` is the
    untransformed phase program (a list of banks, for a mesh run's
    ``shard_map``) and ``assemble_banks`` their host assembly. The
    state must come from an init built with the same ``build``.

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
    step = jax.vmap(make_step(wl, cfg, build))
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

    def phases(state: SimState, idx_offset=0):
        """The phase program: a list of banks, each holding its rows'
        original positions as ``_idx``. Shapes are static per input
        size, so the same traced function serves the full batch
        (``compiled`` below) or one device's shard
        (parallel.shard_run_compacted, which passes the shard's global
        row offset as ``idx_offset``)."""
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

    out_fields = fields if screens is None else fields + SCREEN_FIELDS
    # a screened run's history columns cross trimmed to the longest
    # surviving record count, known only once the counts are on the host
    loose = ("hist_word", "hist_t") if screens is not None else ()

    def compiled(state: SimState) -> Packed:
        """The phase program with its banks put back in seed order and
        packed into one buffer on the device, so the host makes one
        copy instead of one per field per bank and a scatter."""
        banked = phases(state)
        cols = {
            f: jnp.concatenate([b[f] for b in banked]) for f in out_fields
        }
        if len(banked) > 1:
            cols = _unpermute(
                cols, jnp.concatenate([b["_idx"] for b in banked])
            )
        cols["_row_steps"] = banked[-1]["_row_steps"]
        if screens is not None:
            cols["_kept"] = jnp.max(cols["hist_count"], initial=0)
        return _pack(cols, loose)

    # no donate_argnums: banked phase-0 rows alias the input buffers, so
    # XLA can't actually reuse them (it would only warn); the one extra
    # input-sized allocation is cheap next to the loop carries
    jitted = jax.jit(compiled)

    def assemble(packed: Packed, fetch=None) -> SimpleNamespace:
        """Device->host transfer of a :class:`Packed` result: one copy,
        each field a view of it (the copy duplicated once on the host
        where the transfer is read-only, so every field is writable).

        Under a ``hist_screen``, the folded history columns transfer
        only up to the longest surviving record count (read from the
        packed buffer): the fold's whole point is that the big (rows,
        H, ...) column transfer shrinks to the pending-invoke prefix
        plus the flagged seeds' full histories.

        ``fetch`` (a :class:`HostFetch`) counts what crossed.
        """
        fetch = HostFetch() if fetch is None else fetch
        host = fetch(packed.buf)
        out = packed.fields(host if host.flags.writeable else host.copy())
        for f, x in packed.loose.items():
            k = int(out["_kept"])
            got = _view(fetch(_prefix_words(x, k)),
                        (x.shape[0], k) + x.shape[2:], x.dtype)
            if k == x.shape[1]:
                out[f] = got.copy()
            else:
                # rows past hist_count are zero by the fold, so the
                # untransferred tail is value-identical
                out[f] = np.zeros(x.shape, x.dtype)
                out[f][:, :k] = got
        return SimpleNamespace(**{f: out[f] for f in out_fields})

    def assemble_banks(banked, fetch=None) -> SimpleNamespace:
        """:func:`assemble` for the banks of :func:`phases` (a mesh run's,
        per shard): one copy per field per bank, scattered into seed
        order on the host."""
        fetch = HostFetch() if fetch is None else fetch
        idxs = [fetch(b["_idx"]) for b in banked]
        s0 = sum(i.shape[0] for i in idxs)
        trim = {}
        if screens is not None:
            kept = max(
                (int(fetch(b["hist_count"]).max(initial=0)) for b in banked),
                default=0,
            )
            trim = dict.fromkeys(loose, kept)
        out = {}
        for f in out_fields:
            proto = banked[0][f]
            buf = np.zeros((s0,) + tuple(proto.shape[1:]), proto.dtype)
            k = trim.get(f)
            for b, idx in zip(banked, idxs):
                if k is None:
                    buf[idx] = fetch(b[f])
                else:
                    buf[idx, :k] = fetch(b[f][:, :k])
            out[f] = buf
        return SimpleNamespace(**out)

    def run(state: SimState) -> SimpleNamespace:
        return assemble(jax.block_until_ready(jitted(state)))

    # benchmark seam: time `compute` (device work only, block on device
    # arrays) and call `assemble` outside the window — keeps the metric
    # methodologically identical to timing the lockstep loop, where the
    # host read also happened after the timed region. The packed result
    # also carries the loop's `_row_steps` (module row_steps)
    run.compute = jitted
    run.assemble = assemble
    # sharding seam: the raw phase program and its banks' assembly, for
    # parallel.shard_run_compacted
    run.phases = phases
    run.assemble_banks = assemble_banks
    return run
