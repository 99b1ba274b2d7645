"""Batched discrete-event simulation core — the TPU path.

The reference advances one seeded simulation per OS thread: a
single-threaded executor pops ready tasks in random order, polls
arbitrary futures, and jumps a virtual clock between timer events
(reference madsim/src/sim/task.rs:142-216, time/mod.rs:45-60). This
module inverts that architecture for TPUs: **simulation state is a pytree
of dense arrays with a leading seed axis**, and one XLA-compiled step
function advances *every* seed by one event in lockstep —
``vmap`` over seeds, ``lax.scan`` over steps, ``shard_map``/``jit`` with
``NamedSharding`` over device meshes (see madsim_tpu.parallel).

Mapping from the reference's moving parts to array form:

  reference (per run)                      engine (per seed row)
  ---------------------------------------  --------------------------------
  ready queue + timer wheel                one event pool (E slots): time,
    (task.rs:176-216, time/mod.rs:45-60)   kind, dst, src, epoch, args
  random ready-task pick (mpsc.rs:73-83)   per-event latency/cost draws
                                           randomize order; argmin pops the
                                           earliest event deterministically
  50-100 ns poll cost (task.rs:213)        poll-cost draw added to the
                                           clock after each dispatch
  serial SmallRng (rand.rs:30-61)          counter-based threefry draws
                                           keyed (seed, step, purpose)
  NodeInfo epoch swap on kill              alive/epoch arrays; events carry
    (task.rs:255-276)                      their target's epoch and are
                                           dropped on mismatch
  NetSim clog/loss/latency                 clog matrix (N,N); per-send loss
    (network.rs:75-95, 268-276)            and latency draws; clogged
                                           deliveries self-reschedule with
                                           exponential backoff
                                           (net/mod.rs:341-355 semantics)
  user futures polled by the executor      user code is a **state
                                           machine**: per-node int32 state
                                           rows + pure handler functions
                                           dispatched by ``lax.switch``

The last row is the central design decision (SURVEY.md §7 hard part 1):
XLA cannot trace arbitrary coroutines, so batched workloads are written
as event handlers over integer node state. The asyncio-style frontend in
madsim_tpu.runtime remains the ergonomic single-seed API; this engine is
the scaling path, and workloads written for it get 10^4-10^5 seeds per
chip.

Everything in the hot path is integer arithmetic (int32/int64/uint32) —
bit-identical across CPU and TPU backends, which makes the trace hash an
exact cross-backend determinism check (the analog of the reference's
replay checker, runtime/mod.rs:165-190).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .rng import (
    DRAW_SPAN_MAX,
    PURPOSE_DUP,
    PURPOSE_LATENCY,
    PURPOSE_LOSS,
    PURPOSE_POLL_COST,
    PURPOSE_RETRY,
    PURPOSE_TORN,
    PURPOSE_USER,
    Draw,
    chance_threshold,
    validate_user_purposes,
)

__all__ = [
    "Build",
    "EngineConfig",
    "HistorySpec",
    "LatencySpec",
    "N_LAT_BUCKETS",
    "LAT_EDGES_NS",
    "lat_bucket",
    "lat_bucket_lo",
    "lat_bucket_hi",
    "Workload",
    "SimState",
    "RetrySpec",
    "RETRY_ATTEMPT_SHIFT",
    "RETRY_ATTEMPT_MAX",
    "RETRY_OP_MASK",
    "RETRY_STATE_FIELDS",
    "retry_token",
    "retry_token_op",
    "retry_token_attempt",
    "MET_RETRY",
    "MET_RETRY_GIVEUP",
    "Emits",
    "EmitBuilder",
    "HandlerCtx",
    "PlanRows",
    "METRIC_NAMES",
    "N_METRICS",
    "MET_HALT_CODE",
    "HALT_RUNNING",
    "HALT_DONE",
    "HALT_TIME_LIMIT",
    "HALT_IDLE",
    "KIND_KILL",
    "KIND_RESTART",
    "KIND_CLOG",
    "KIND_UNCLOG",
    "KIND_CLOG_NODE",
    "KIND_UNCLOG_NODE",
    "KIND_HALT",
    "KIND_NOP",
    "KIND_PAUSE",
    "KIND_RESUME",
    "FIRST_USER_KIND",
    "FIRST_EXT_KIND",
    "KIND_SLOW_LINK",
    "KIND_UNSLOW",
    "KIND_DUP_ON",
    "KIND_DUP_OFF",
    "KIND_SKEW",
    "KIND_CLOG_1W",
    "KIND_UNCLOG_1W",
    "KIND_SYNC_LOSS",
    "KIND_SYNC_OK",
    "KIND_TORN_ON",
    "KIND_TORN_OFF",
    "pack_slow_arg",
    "unpack_slow_arg",
    "user_kind",
    "make_init",
    "make_step",
    "make_run",
    "make_run_while",
    "check_plan_horizon",
    "time32_eligible",
    "DERIVED_STATE_FIELDS",
    "STORAGE_STATE_FIELDS",
    "POOL_INDEX_STATE_FIELDS",
    "CAUSAL_STATE_FIELDS",
    "derived_fields",
    "core_fields",
    "ColumnContract",
    "column_contracts",
    "ABSINT_HORIZON_NS",
    "ABSINT_COUNTER_MAX",
    "ABSINT_STEP_MAX",
    "SLOW_MULT_MAX",
    "pool_tile",
    "pool_index_eligible",
    "resolve_layout",
    "build_pool_index",
]

_INF_NS = np.int64(2**62)
_INF_32 = np.int32(2**31 - 1)
_T32_LIMIT = 2**31 - 1  # max future-event offset representable in int32

# Pool-size crossover for the scatter layout's two placement lowerings
# (see Build's ``placement``). Rank-matched placement costs fused
# vector passes over the whole pool (O(E) elements, ~60 ns per pass per
# seed per 64 slots on CPU); scatter-store placement costs one serial
# row-update per emit slot (~110 ns per row on XLA CPU, independent of
# E). Measured crossover sits near E ≈ 1k; 512 keeps headroom for wider
# emit rows (tools/profile_step.py re-measures it per config).
_RANK_PLACE_MAX_POOL = 512


# ---------------------------------------------------------------------------
# Readiness-partitioned pool index (Build's ``pool_index``). The E
# pool slots split into fixed tiles of T rows; SimState carries per-tile
# summary columns (tile_min = earliest VALID time in the tile, tile_cnt
# = number of valid slots), maintained incrementally by the step. The
# pop becomes argmin over E/T tile minima + argmin inside the ONE
# winning tile, and free-slot search for placement becomes a cumsum
# over per-tile free counts + a rank match inside the target tiles —
# O(E/T + T + emits) per event instead of the flat layout's O(E)
# masked-min + flatnonzero passes, which dominate the step at
# client-army pool sizes (thousands of slots, see ISSUE 13 /
# PROFILE_CPU_r07). Values are identical by construction: argmin over
# tile minima followed by argmin inside the winning tile picks exactly
# the global first-minimum slot, and the rank-matched free search
# reproduces flatnonzero's slot order bit-for-bit.
#
# The summaries are DERIVED BY CONSTRUCTION — a pure function of
# (ev_time, ev_valid), rebuilt on checkpoint restore (engine/checkpoint
# excludes them from the format; _FORMAT is unchanged) — but they are
# trajectory-COUPLED: the pop reads them. So they live in core_fields
# for the static taint proof (derived obs columns must stay out of
# them, which lint.check_matrix proves over the indexed program), and
# their own correctness certificate is the index on/off bit-identity
# pin (tests/test_pool_index.py, tests/test_stepident.py goldens).
# ---------------------------------------------------------------------------
# candidate tile widths, preferred first. T ~ sqrt(E) is the pop
# optimum; 64 serves every army-scale pool (2048 -> 32 tiles, 8192 ->
# 128), the smaller widths let small test pools (40/48/64/72/96) run
# the indexed program for identity pins.
POOL_TILE_CANDIDATES = (64, 32, 16, 8)
# auto-resolution threshold: pool_index=None turns the index on (CPU
# backend, scatter layout) for pools STRICTLY larger than this. 1024
# keeps every measured small-pool config (<= 512, BENCH_SPECS) on
# today's lowering — the interleaved A/B (BENCH_AB_r07.txt) measured
# the crossover between 1024 and 2048 on CPU.
_POOL_INDEX_MIN_POOL = 1024


def pool_tile(pool_size: int) -> int:
    """Tile width the readiness index would use for this pool size
    (the largest :data:`POOL_TILE_CANDIDATES` divisor leaving >= 2
    tiles), or 0 when no candidate divides it — the pool is then not
    index-eligible and ``pool_index=True`` is rejected."""
    for t in POOL_TILE_CANDIDATES:
        if pool_size % t == 0 and pool_size // t >= 2:
            return t
    return 0


def pool_index_eligible(cfg: "EngineConfig") -> bool:
    """Whether this config's pool can carry the readiness index."""
    return pool_tile(cfg.pool_size) > 0


def resolve_layout(layout: str | None) -> str:
    """THE layout default (Build's ``layout=None`` rule): scatter on
    the CPU backend, dense elsewhere. Called only by
    :meth:`Build.resolve`."""
    if layout is None:
        return "scatter" if jax.default_backend() == "cpu" else "dense"
    if layout not in ("dense", "scatter"):
        raise ValueError(f"unknown layout {layout!r}")
    return layout


def _resolve_pool_index(
    cfg: "EngineConfig", pool_index: bool | None, dense: bool
) -> bool:
    """THE pool-index rule, for the resolved layout (``dense``). Called
    only by :meth:`Build.resolve`. Auto (None) turns the index on only
    where it wins: the scatter layout's large pools on the CPU backend.
    Explicit True on an ineligible pool or under the dense layout is an
    error, never a silent fallback."""
    tile = pool_tile(cfg.pool_size)
    if pool_index is None:
        return (
            bool(tile)
            and not dense
            and jax.default_backend() == "cpu"
            and cfg.pool_size > _POOL_INDEX_MIN_POOL
        )
    if pool_index:
        if not tile:
            raise ValueError(
                f"pool_index requested but pool_size={cfg.pool_size} has "
                f"no tile divisor in {POOL_TILE_CANDIDATES} with >= 2 "
                f"tiles; round the pool up (chaos.FaultPlan.min_pool_size "
                f"sizes army pools tile-aligned)"
            )
        if dense:
            raise ValueError(
                "pool_index is a scatter-layout lowering; the dense "
                "layout's one-hot pop has no tile index — pass "
                "pool_index=False (or leave it None) with layout='dense'"
            )
    return bool(pool_index)


def build_pool_index(ev_time, ev_valid, tile: int):
    """Compute ``(tile_min, tile_cnt)`` summaries from pool columns.

    Pure function of the pool — THE definition the step maintains
    incrementally and checkpoint restore / tests rebuild from scratch.
    Works on one seed's ``(E,)`` columns or a batch's ``(S, E)``
    (any leading axes; the pool axis is last). ``tile_min`` of an empty
    tile is the +inf sentinel of the time dtype; every consumer masks
    by ``tile_cnt > 0`` (stale minima of empty tiles are meaningless,
    exactly like stale times of invalid pool slots)."""
    v = jnp.asarray(ev_valid)
    t = jnp.asarray(ev_time)
    e = v.shape[-1]
    if tile <= 0 or e % tile:
        raise ValueError(f"tile={tile} does not partition pool_size={e}")
    shape = v.shape[:-1] + (e // tile, tile)
    inf = jnp.asarray(_INF_32 if t.dtype == jnp.int32 else _INF_NS, t.dtype)
    tile_min = jnp.min(jnp.where(v.reshape(shape), t.reshape(shape), inf), axis=-1)
    tile_cnt = jnp.sum(v.reshape(shape).astype(jnp.int32), axis=-1)
    return tile_min, tile_cnt

# ---------------------------------------------------------------------------
# ev_meta byte layout. The four small per-event fields travel as one
# uint32 word — every per-slot pick and placement touches one array
# instead of four, the dominant per-step cost on TPU (the placement
# selects scale with the number of placed words).
#   byte 0: kind                  (engine kinds + user handlers <= 255)
#   byte 1: target node + 1       (-1..n clipped; 0 = no node, n+1 = OOB)
#   byte 2: source node + 1       (0 = timer/engine event)
#   byte 3: clog-retry count, saturating at 255 (the backoff shift caps
#           at 34, so saturation is behaviorally invisible)
# Out-of-range kinds/nodes are clipped at pack time; every consumer
# treats a clipped value exactly like the out-of-range original (a
# no-match in the one-hots / in_range masks), so observable semantics
# are unchanged.
# ---------------------------------------------------------------------------


def _meta_pack(kind, node1, src1, retry):
    return (
        kind.astype(jnp.uint32)
        | (node1.astype(jnp.uint32) << jnp.uint32(8))
        | (src1.astype(jnp.uint32) << jnp.uint32(16))
        | (retry.astype(jnp.uint32) << jnp.uint32(24))
    )


def _meta_kind(meta):
    return (meta & jnp.uint32(0xFF)).astype(jnp.int32)


def _meta_node(meta):
    return ((meta >> jnp.uint32(8)) & jnp.uint32(0xFF)).astype(jnp.int32) - 1


def _meta_src(meta):
    return ((meta >> jnp.uint32(16)) & jnp.uint32(0xFF)).astype(jnp.int32) - 1


def _meta_retry(meta):
    return ((meta >> jnp.uint32(24)) & jnp.uint32(0xFF)).astype(jnp.int32)


def _check_meta_ranges(wl: "Workload") -> None:
    """ev_meta byte-range requirements — enforced wherever packing
    happens (make_init and make_step), so no corrupt state can be built."""
    if wl.n_nodes > 254:
        raise ValueError(
            f"n_nodes={wl.n_nodes} exceeds the meta byte range (254)"
        )
    if FIRST_USER_KIND + len(wl.handlers) > FIRST_EXT_KIND:
        raise ValueError(
            f"{len(wl.handlers)} handlers exceed the user kind range "
            f"[{FIRST_USER_KIND}, {FIRST_EXT_KIND}) (extended chaos "
            f"kinds occupy {FIRST_EXT_KIND}..255)"
        )
_TRACE_PRIME = np.uint64(0x100000001B3)
_TRACE_MIX = np.uint64(0x9E3779B97F4A7C15)

# ---------------------------------------------------------------------------
# Event kinds. Engine kinds come first so user handler k has kind
# FIRST_USER_KIND + k regardless of workload; handler 0 is by convention
# on_init (run for every node at t=0 and again after RESTART).
# ---------------------------------------------------------------------------
KIND_KILL = 0  # args[0]=node          Handle::kill        (runtime/mod.rs:246)
KIND_RESTART = 1  # args[0]=node       Handle::restart     (runtime/mod.rs:251)
KIND_CLOG = 2  # args[0]=a args[1]=b   NetSim::clog_link   (net/mod.rs:157-216)
KIND_UNCLOG = 3  # args[0]=a args[1]=b
KIND_CLOG_NODE = 4  # args[0]=node     NetSim::clog_node
KIND_UNCLOG_NODE = 5  # args[0]=node
KIND_HALT = 6  # scenario complete: freeze this seed's instance
KIND_NOP = 7
KIND_PAUSE = 8  # args[0]=node      Handle::pause       (runtime/mod.rs:256)
KIND_RESUME = 9  # args[0]=node     Handle::resume
FIRST_USER_KIND = 10

# Extended chaos kinds (madsim_tpu.chaos): allocated at the TOP of the
# kind byte so every existing kind id — and therefore every existing
# trace hash and the C++ oracle — is untouched. User handler kinds live
# in [FIRST_USER_KIND, FIRST_EXT_KIND); anything >= FIRST_EXT_KIND is an
# engine kind again (dispatched inline, exempt from the epoch/pause
# gates exactly like kinds < FIRST_USER_KIND). The oracle does not
# implement these kinds, so plan-driven runs are verified by the
# two-run/two-layout checks, not the oracle compare.
FIRST_EXT_KIND = 244
KIND_SLOW_LINK = 244  # args[0]=a args[1]=pack_slow_arg(b, mult): gray
#                       failure — multiply a<->b latency (b=-1: node a)
KIND_UNSLOW = 245  # args[0]=a args[1]=pack_slow_arg(b, 1): restore x1
KIND_DUP_ON = 246  # message duplication: every send also delivers a copy
KIND_DUP_OFF = 247
KIND_SKEW = 248  # args[0]=node args[1]=skew_ns: the node's clock reads
#                  now+skew (what its handlers observe as ctx.now)
KIND_CLOG_1W = 249  # args[0]=src args[1]=dst — asymmetric partition edge
KIND_UNCLOG_1W = 250
# disk-fault kinds (madsim_tpu.chaos DiskFault; only meaningful for
# Workload.durable_sync workloads — a no-op otherwise, like DUP_ON
# without dup_rows). args[0] = target node, -1 = every node.
KIND_SYNC_LOSS = 251  # args[1]=0 (default): the node's disk starts
#                       LYING — sync commits are silently dropped (the
#                       committed bit never sets). args[1]=1: the disk
#                       starts FAILING — syncs still don't commit, but
#                       the fault is OBSERVABLE: handlers see
#                       ctx.sync_err while the window is open, the
#                       batched analog of FsSim.set_fail_writes raising
#                       OSError(EIO)
KIND_SYNC_OK = 252  # end of the sync-lie/EIO window: syncs commit again
KIND_TORN_ON = 253  # arm torn-write mode: the next KILL persists only a
#                     threefry-drawn PREFIX of the last uncommitted
#                     durable write (PURPOSE_TORN) on top of the synced
#                     image — the FDB/sled power-failure fault
KIND_TORN_OFF = 254


# ---------------------------------------------------------------------------
# Fleet-metric slot layout (madsim_tpu.obs). SimState.met is an
# (N_METRICS,) int32 vector per seed when the step is built with
# ``metrics=True`` (else zero-size). Every slot except MET_HALT_CODE is
# a monotone counter folded at dispatch time; all of them are derived
# from values the step already computes — no RNG draws, no feedback into
# the trajectory, so metrics-off runs are bit-identical (the cov_words
# discipline applied again). The obs package reduces these columns on
# device (obs.fleet_reduce) so a 65k-seed sweep reports fleet histograms
# without moving per-seed state to the host.
# ---------------------------------------------------------------------------
MET_SENT = 0  # messages sent (valid send emits at dispatch, lost or not)
MET_DELIVERED = 1  # message deliveries dispatched (src >= 0)
MET_LOST = 2  # sends dropped by the loss draw
MET_DEAD_DROP = 3  # sends dropped because the dst was dead at send time
MET_DUP = 4  # duplicated deliveries inserted (chaos KIND_DUP_ON)
MET_CRASH = 5  # KIND_KILL dispatches
MET_RESTART = 6  # KIND_RESTART dispatches
MET_PAUSE = 7  # KIND_PAUSE dispatches
MET_CLOG_BLOCK = 8  # delivery attempts held by a clogged link (each
#                     backoff retry counts again — it is an attempt)
MET_TIMER = 9  # user timer fires (non-message user dispatches)
MET_RECORD = 10  # history records appended
MET_RNG = 11  # threefry blocks drawn while the seed was active
MET_HALT_CODE = 12  # not a counter: HALT_* code of how the seed stopped
# storage-fault counters (Workload.durable_sync; always 0 otherwise).
# Appended after MET_HALT_CODE so every pre-existing slot id is stable.
MET_SYNC = 13  # sync commits honored (EmitBuilder.sync, disk committed)
MET_SYNC_LOST = 14  # syncs that failed to commit inside a
#                     KIND_SYNC_LOSS window — silently (lie mode) or
#                     observably (EIO mode, ctx.sync_err)
MET_TORN = 15  # kills that landed inside an armed torn-write window
#                (whether bytes actually tore depends on an uncommitted
#                write being outstanding — on a correct fsync-everywhere
#                model nothing ever is, which is the theorem, so this
#                counts the exercised windows, not the data damage)
# client-retry counters (RetrySpec; always 0 without a policy). Appended
# after MET_TORN so every pre-existing slot id is stable.
MET_RETRY = 16  # army re-deliveries dispatched (attempt > 0 that ran)
MET_RETRY_GIVEUP = 17  # ops abandoned: the max_attempts-th timer fired
#                        with no response recorded — at-least-once gave up
N_METRICS = 18

METRIC_NAMES = (
    "sent", "delivered", "lost", "dead_drop", "dup", "crash", "restart",
    "pause", "clog_block", "timer", "record", "rng_blocks", "halt_code",
    "sync", "sync_lost", "torn", "retry", "retry_giveup",
)

# ---------------------------------------------------------------------------
# Tail-latency sketch ladder (madsim_tpu.obs latency). Per-op latencies
# fold ON DEVICE into a per-seed log-linear histogram — the property
# that matters from t-digest is *exact mergeability* (sketch of a union
# = sum of sketches), which a FIXED bucket ladder gives for free while
# staying pure integer arithmetic (bit-identical across backends, like
# every other column). Ladder: bucket 0 holds [0, 64 µs); buckets 1..62
# are quarter-octaves (edge ratio 2^(1/4) ≈ 1.19x) from 64 µs up to
# ~3.0 s; bucket 63 saturates above that. Quantiles read off the ladder
# are exact to one bucket of rank error — ~19% relative, far inside
# what any p99 SLO statement needs — and the ladder is a static module
# constant, so merged sketches from any run ever taken remain
# comparable.
# ---------------------------------------------------------------------------
N_LAT_BUCKETS = 64
_LAT_EDGE0_NS = 1 << 16  # 65.536 µs, the bottom of the interesting range
# 63 edges; bucket(v) = #edges <= v, in 0..63. Rounded to exact int64
# once, host-side: the table itself is the spec.
LAT_EDGES_NS = np.asarray(
    [int(round(_LAT_EDGE0_NS * 2.0 ** (b / 4.0))) for b in range(N_LAT_BUCKETS - 1)],
    np.int64,
)


def lat_bucket(v_ns) -> np.ndarray:
    """Host-side ladder lookup: bucket index of a latency (vectorized)."""
    return np.searchsorted(LAT_EDGES_NS, np.asarray(v_ns, np.int64), side="right")


def lat_bucket_lo(b) -> np.ndarray:
    """Inclusive lower edge of bucket ``b`` (0 for bucket 0)."""
    b = np.asarray(b, np.int64)
    return np.where(b <= 0, 0, LAT_EDGES_NS[np.clip(b - 1, 0, N_LAT_BUCKETS - 2)])


def lat_bucket_hi(b) -> np.ndarray:
    """Exclusive upper edge of bucket ``b`` (the top bucket saturates at
    the last edge — values above it are reported AS that edge, loudly
    documented rather than silently exact)."""
    b = np.asarray(b, np.int64)
    return LAT_EDGES_NS[np.clip(b, 0, N_LAT_BUCKETS - 2)]


@dataclasses.dataclass(frozen=True)
class LatencySpec:
    """Build parameters of the engine's latency observability tap.

    ``ops`` sizes the per-seed op-slot columns: every client-army op id
    must lie in [0, ops). ``phases``/``phase_ns`` cut the run into
    fixed measurement windows (an op belongs to the window its INVOKE
    fell in; the last window is open-ended): per-window sketches are
    what makes an SLO check gray-failure-aware — a p99 blowup during a
    120 ms fault window is invisible in a whole-run percentile but is
    exactly window k's histogram. Hashable (frozen), so it keys the
    compiled-run caches like every other build flag.
    """

    ops: int
    phases: int = 1
    phase_ns: int = 1 << 27  # ~134 ms, the coverage time-phase width

    def __post_init__(self):
        if self.ops < 1:
            raise ValueError(f"LatencySpec.ops must be >= 1, got {self.ops}")
        if self.phases < 1:
            raise ValueError(
                f"LatencySpec.phases must be >= 1, got {self.phases}"
            )
        if self.phase_ns < 1:
            raise ValueError(
                f"LatencySpec.phase_ns must be >= 1, got {self.phase_ns}"
            )


# ---------------------------------------------------------------------------
# Client-retry token packing (madsim_tpu.chaos RetryPolicy). A retried
# op rides the SAME user kind as the original offer; the attempt id is
# packed into the high bits of the op token (args[0]) so handlers,
# history records and the Perfetto sidecar can tell re-sends apart while
# attempt-0 tokens stay PLAIN op ids — the bit-identity-off-policy
# invariant costs nothing to state: with no policy, no attempt is ever
# nonzero, so every token is the pre-retry value.
# ---------------------------------------------------------------------------
RETRY_ATTEMPT_SHIFT = 26
RETRY_ATTEMPT_MAX = 15  # attempt ids 0..15 fit bits 26..29 (sign bit free)
RETRY_OP_MASK = (1 << RETRY_ATTEMPT_SHIFT) - 1


def retry_token(op, attempt):
    """Pack (op id, attempt id) into an op token. Host or traced."""
    return op | (attempt << RETRY_ATTEMPT_SHIFT)


def retry_token_op(token):
    """The plain op id of a token (identity for attempt-0 tokens)."""
    return token & RETRY_OP_MASK


def retry_token_attempt(token):
    """The attempt id of a token (0 for plain pre-retry tokens)."""
    return (token >> RETRY_ATTEMPT_SHIFT) & RETRY_ATTEMPT_MAX


# backoff entries are clipped host-side so the traced jitter product
# (entry * uint32 draw) stays inside int64: cap * 2^32 < 2^63
_RETRY_BACKOFF_CAP = 2**31 - 1


@dataclasses.dataclass(frozen=True)
class RetrySpec:
    """Build parameters of the engine's client-retry timer mechanism.

    The compiled form of ``chaos.RetryPolicy`` attached to a
    ``ClientArmy``: ``kind``/``node``/``op_base``/``n_ops`` identify
    the army's offered ops (one retry-state slot per op), the policy
    fields drive the timers. Each delivered army attempt arms ONE
    follow-up pool row at ``now + timeout_ns + backoff + jitter`` with
    the attempt id incremented; when it pops, the op is re-delivered
    unless a response was recorded meanwhile (the op's ``lat_end``
    marker — the same first-response-wins discipline the latency tap
    uses, which is why a retry build requires ``Workload.lat_markers``).
    ``max_attempts`` counts total deliveries: the row carrying attempt
    id ``max_attempts`` is the give-up sentinel — it never delivers,
    only closes the books (MET_RETRY_GIVEUP). Backoff before attempt
    ``a >= 1`` is ``backoff_base_ns * backoff_mult**(a-1)``, jittered
    by a fresh PURPOSE_RETRY threefry draw scaled to ``[0, jitter]`` of
    the backoff — per (seed, step), so the schedule is seed-pure.
    Hashable (frozen), so it keys the compiled-run caches like every
    other build flag.
    """

    kind: int
    node: int
    op_base: int
    n_ops: int
    timeout_ns: int
    max_attempts: int = 3
    backoff_base_ns: int = 0
    backoff_mult: float = 2.0
    jitter: float = 0.0

    def __post_init__(self):
        if self.n_ops < 1:
            raise ValueError(f"RetrySpec.n_ops must be >= 1, got {self.n_ops}")
        if self.timeout_ns < 1:
            raise ValueError(
                f"RetrySpec.timeout_ns must be >= 1, got {self.timeout_ns}"
            )
        if not (1 <= self.max_attempts <= RETRY_ATTEMPT_MAX):
            raise ValueError(
                f"RetrySpec.max_attempts must be in 1..{RETRY_ATTEMPT_MAX} "
                f"(the token packs attempts into 4 bits), got "
                f"{self.max_attempts}"
            )
        if self.op_base < 0:
            raise ValueError(
                f"RetrySpec.op_base must be >= 0, got {self.op_base}"
            )
        if self.op_base + self.n_ops - 1 > RETRY_OP_MASK:
            raise ValueError(
                f"RetrySpec op ids reach {self.op_base + self.n_ops - 1}, "
                f"past the {RETRY_ATTEMPT_SHIFT}-bit token op field "
                f"(max {RETRY_OP_MASK})"
            )
        if not (FIRST_USER_KIND <= self.kind < FIRST_EXT_KIND):
            raise ValueError(
                f"RetrySpec.kind={self.kind} must be a user kind "
                f"(in [{FIRST_USER_KIND}, {FIRST_EXT_KIND}))"
            )
        if self.backoff_base_ns < 0:
            raise ValueError(
                f"RetrySpec.backoff_base_ns must be >= 0, got "
                f"{self.backoff_base_ns}"
            )
        if self.backoff_mult < 1.0:
            raise ValueError(
                f"RetrySpec.backoff_mult must be >= 1, got "
                f"{self.backoff_mult}"
            )
        if not (0.0 <= self.jitter <= 1.0):
            raise ValueError(
                f"RetrySpec.jitter must be in [0, 1], got {self.jitter}"
            )


def _retry_backoff_tables(rt: RetrySpec):
    """Host-side backoff tables, indexed by the NEXT attempt id.

    Entry ``a`` is the deterministic backoff before delivering attempt
    ``a`` (0 for a=0/1 when base is 0), clipped to the int64-safe cap;
    the jit table is the maximum jitter addend (``backoff * jitter``,
    same cap) that the uint32 draw scales down. Both are plain Python
    int tuples — compiled into the step as constants.
    """
    boff = [0]
    for a in range(1, rt.max_attempts + 1):
        b = rt.backoff_base_ns * rt.backoff_mult ** (a - 1)
        boff.append(min(int(b), _RETRY_BACKOFF_CAP))
    bjit = [min(int(b * rt.jitter), _RETRY_BACKOFF_CAP) for b in boff]
    return tuple(boff), tuple(bjit)


def _check_retry(wl: "Workload", retry: "RetrySpec | None") -> None:
    """Validate a retry build parameter (run by :meth:`Build.resolve`)."""
    if retry is None:
        return
    if not isinstance(retry, RetrySpec):
        raise TypeError(
            f"retry must be a RetrySpec or None, got {type(retry).__name__}"
        )
    if wl.lat_markers == 0:
        raise ValueError(
            "retry needs a workload with latency markers "
            "(Workload.lat_markers > 0): the response-deadline timer is "
            "disarmed by the op's lat_end marker, so a model that never "
            "marks responses would retry forever"
        )


# MET_HALT_CODE values
HALT_RUNNING = 0  # still live (or stopped only by the step cap)
HALT_DONE = 1  # workload emitted KIND_HALT: scenario complete
HALT_TIME_LIMIT = 2  # cfg.time_limit_ns tripped
HALT_IDLE = 3  # event pool ran empty while unhalted (a deadlocked seed:
#                nothing pending, nothing will ever be)


# ---------------------------------------------------------------------------
# Derived-state manifest (madsim_tpu.lint). The engine's observability
# discipline — "off = zero-size arrays + bit-identical traces" — rests
# on these SimState fields being WRITE-ONLY with respect to the
# trajectory: the step may read them to append to them, but no value
# derived from them may ever reach a core column, an RNG draw, or the
# trace fold. The names below are the stable taint-source vocabulary
# the static non-interference proof (lint.check_noninterference) tags
# and the isolation-frontier report cites; obs.explain's views use the
# same field names, so a reported leak names exactly the columns a
# forensics reader already knows.
# ---------------------------------------------------------------------------

# always derived, whatever the build flags: history columns
# (madsim_tpu.check), the coverage fingerprint (explore), fleet metrics
# and the timeline ring (obs). With the matching tap off they are
# zero-size arrays — trivially non-interfering — and with it on the
# proof obligation is exactly the bit-identity the runtime tests sample.
DERIVED_STATE_FIELDS = (
    "hist_count", "hist_drop", "hist_word", "hist_t",
    "cov", "cov_last", "cov_hits",
    "met",
    "tl_count", "tl_drop", "tl_t", "tl_meta", "tl_args", "tl_pay",
    # emit-time sidecar (timeline_cap > 0): the pool row's insertion
    # clock, read only into tl_emit — flow-arrow anchoring, never the
    # trajectory
    "ev_emit", "tl_emit",
    # causal provenance (causal=True): per-node Lamport clocks, the
    # pool rows' emitting-dispatch seq + emit-time clock, and the ring
    # columns they bank into — read exclusively to fold more causal
    # state / the ring, never the trajectory
    "lam", "ev_parent", "ev_lam", "tl_seq", "tl_parent", "tl_lam",
    # tail-latency columns (LatencySpec): per-op invoke/response clocks
    # and the per-seed log-linear sketch
    "lat_inv", "lat_resp", "lat_hist", "lat_count", "lat_drop",
)

# ev_parent sentinel classes (causal=True): a pool row whose value is
# >= 0 was emitted by the dispatch with that event-sequence number
# (SimState.step at emit time); negative values classify rows with no
# emitting dispatch. obs.causal treats sentinel-parented events as DAG
# roots and labels them by class.
PARENT_NONE = -1  # on_init rows and never-written slots
PARENT_PLAN = -2  # compiled fault-plan rows (engine/extended-chaos kinds)
PARENT_ARMY = -3  # client-army plan rows (open-loop USER-kind arrivals)

# the two-phase sync-discipline columns: derived (zero-size) when
# Workload.durable_sync is off, CORE when it is on — a crash then reads
# the disk image back into node_state, a legitimate feedback path.
STORAGE_STATE_FIELDS = ("disk", "wmask", "sync_loss", "sync_eio", "torn")

# the readiness-index tile summaries (see the pool-index note above):
# derived BY CONSTRUCTION — a pure function of (ev_time, ev_valid),
# rebuilt on checkpoint restore, excluded from the checkpoint format —
# but trajectory-coupled (the pop reads them), so they are NOT in the
# taint-source set: the static proof treats them as core columns (obs
# state must never reach them) and their value-correctness certificate
# is the index on/off bit-identity pin. Zero-size when the index is
# off, the usual discipline.
POOL_INDEX_STATE_FIELDS = ("tile_min", "tile_cnt")

# the causal-provenance columns (causal=True, ISSUE 19): inside the
# derived set above, zero-size when the axis is off. Named separately
# so schema-sensitive consumers (tools/step_goldens.py digests every
# SimState field name+shape) can keep pre-causal golden digests valid
# for causal=False builds — the off-state value identity is pinned by
# tests/test_causal.py, the on-state fold by its rederive pins.
CAUSAL_STATE_FIELDS = (
    "lam", "ev_parent", "ev_lam", "tl_seq", "tl_parent", "tl_lam",
)

# the client-retry columns (RetrySpec, ISSUE 20): CORE state, not
# derived — rt_done feeds the deliver/suppress gate, so retried
# trajectories legitimately depend on it — but zero-size when no policy
# is attached (the usual off-axis discipline: retry-off runs are
# bit-identical to pre-retry builds). Named separately for the same
# schema-sensitive consumers CAUSAL_STATE_FIELDS serves: excluding the
# field NAMES keeps pre-retry golden digests valid for retry-off
# builds; the off-state value identity is pinned by tests/test_retry.py.
RETRY_STATE_FIELDS = ("rt_done", "rt_attempt", "rt_deadline")


def derived_fields(wl: "Workload") -> tuple:
    """SimState field names that are derived-only for this workload.

    The manifest the static non-interference proof (madsim_tpu.lint)
    taints: no data path from any of these fields may reach a field
    outside the set (nor the trace fold, which lives in the core
    ``trace`` field). Build flags (metrics/cov_words/timeline_cap)
    don't change membership — an off tap is a zero-size array whose
    non-interference is vacuous — but the sync discipline does: its
    columns feed ``node_state`` on a crash when ``durable_sync`` is on.
    """
    out = DERIVED_STATE_FIELDS
    if not wl.durable_sync:
        out = out + STORAGE_STATE_FIELDS
    return out


def core_fields(wl: "Workload") -> tuple:
    """Complement of :func:`derived_fields` over the SimState fields."""
    derived = set(derived_fields(wl))
    return tuple(
        f.name for f in dataclasses.fields(SimState)
        if f.name not in derived
    )


# ---------------------------------------------------------------------------
# Column range contracts (lint.absint). Each SimState column declares
# the integer range its values occupy at step boundaries, under the
# certification horizon — the assumptions the interval abstract
# interpreter seeds its walk with, and the vocabulary its findings
# cite. Two tracked families:
#   "time"    — virtual-clock values (absolute int64 ns, or int32
#               offsets under time32). Bounded by the horizon plus the
#               largest insertion offset; under time32, pool columns
#               span the full int32 range because STALE slot offsets
#               keep rebasing after their slot is consumed and may wrap
#               (masked at every use — the per-site pragma'd
#               subtractions in make_step are exactly these).
#   "counter" — monotone/capacity-bounded counts (event sequence
#               number, overflow/drop tallies, history/timeline fills,
#               metrics). Bounded by capacity where one exists, else by
#               ABSINT_COUNTER_MAX (the certified run-length budget).
# Untracked columns (hashes, RNG seeds, workload state words, packed
# meta) get their full dtype range and no family: arithmetic on them is
# either intentionally modular (unsigned hashes/ciphers) or
# workload-defined (node_state), neither a time32/counter wraparound
# surface.
# ---------------------------------------------------------------------------

# Default certification horizon: the largest virtual clock the prover
# certifies arithmetic under when the config declares no time_limit_ns.
# 2^42 ns ~ 73 sim-minutes — an order of magnitude past every recorded
# run shape (bench runs sim seconds to minutes); models declare their
# own (smaller) horizons via absint_entries().
ABSINT_HORIZON_NS = 1 << 42
# Certified bound on unbounded counters (cumulative drops, msg counts,
# metrics): a run is certified for at most this many counted events.
ABSINT_COUNTER_MAX = 1 << 30
# Certified bound on the event sequence number (the RNG step
# coordinate, uint32): one instance is certified for this many steps.
ABSINT_STEP_MAX = 1 << 31


@dataclasses.dataclass(frozen=True)
class ColumnContract:
    """Declared value range of one SimState column at step boundaries."""

    field: str
    lo: int
    hi: int
    family: str | None = None  # "time" | "counter" | None (untracked)
    note: str = ""


@dataclasses.dataclass(frozen=True)
class StateContract:
    """Declared range of ONE workload state column at step boundaries.

    A workload that declares ``Workload.state_contracts`` (one entry
    per state column, total over ``state_width``) narrows the
    ``node_state`` ColumnContract from the full-int32 default to the
    hull of its declared columns, and TAGS it — so the interval prover
    (lint.absint) tracks overflow through the workload's own deadline
    and epoch arithmetic instead of waving it through as
    "workload-defined words". The contract is assume-guarantee like
    every loop-carried contract: the prover ASSUMES it at each step
    entry and the model author owes its truth (clamp what you store).
    """

    col: int
    lo: int
    hi: int
    family: str | None = None  # "time" | "counter" | None (untracked)
    note: str = ""


def _dtype_full(dt) -> tuple:
    info = np.iinfo(dt)
    return int(info.min), int(info.max)


def _node_state_contract(wl: "Workload", i32: tuple) -> "ColumnContract":
    """The node_state ColumnContract for one workload: full int32 and
    untracked by default; the hull of the declared per-column ranges
    (tagged "time" if any declared column is) when the workload ships
    ``state_contracts``. The hull is the honest join — node_state is
    one (N, U) array to the prover, so the contract of the array is
    the union of the contracts of its columns."""
    if not wl.state_contracts:
        return ColumnContract(
            "node_state", *i32, None, "workload-defined words"
        )
    lo = min(sc.lo for sc in wl.state_contracts)
    hi = max(sc.hi for sc in wl.state_contracts)
    families = {sc.family for sc in wl.state_contracts if sc.family}
    family = "time" if "time" in families else (
        "counter" if families else None
    )
    return ColumnContract(
        "node_state", lo, hi, family,
        f"hull of {len(wl.state_contracts)} declared state columns",
    )


def column_contracts(
    wl: "Workload",
    cfg: "EngineConfig",
    *,
    time32: bool = False,
    horizon_ns: int | None = None,
) -> dict:
    """The per-column range contracts for one (workload, config) build.

    ``horizon_ns`` is the certification horizon (default: the config's
    ``time_limit_ns`` when set, else :data:`ABSINT_HORIZON_NS`). The
    returned dict maps field name -> :class:`ColumnContract` and is
    TOTAL over SimState: every field must be declared here (an
    untracked column declares its full dtype range with no family) —
    a new column missing from the list raises, because silently
    defaulting would weaken the proof without anyone deciding so.
    """
    if horizon_ns is None:
        horizon_ns = cfg.time_limit_ns or ABSINT_HORIZON_NS
    h = int(horizon_ns)
    cnt = ABSINT_COUNTER_MAX
    i32 = _dtype_full(np.int32)
    i64 = _dtype_full(np.int64)
    u32 = _dtype_full(np.uint32)
    u64 = _dtype_full(np.uint64)
    # the largest offset one insertion can put on the pool clock: a
    # handler timer (declared bound, else the horizon itself), a
    # slow-scaled latency draw, or a clog-backoff reschedule (+ <1 us
    # jitter) — the same terms time32_eligible bounds
    delay_hi = wl.delay_bound_ns if wl.delay_bound_ns is not None else h
    offset_hi = max(
        int(delay_hi),
        int(cfg.lat_max_ns) * SLOW_MULT_MAX,
        int(cfg.clog_backoff_max_ns) + 1_000,
    )
    hcap = wl.history.capacity if wl.history is not None else 0

    def c(field, lo, hi, family=None, note=""):
        return ColumnContract(field, int(lo), int(hi), family, note)

    if time32:
        # offsets from `now`; valid slots are bounded by the insertion
        # clamp (lim32), but stale slots rebase forever and may wrap —
        # the honest contract is the full dtype range (family still
        # "time": any NEW arithmetic on these columns is a wrap surface
        # unless its site is individually annotated)
        ev_time = c("ev_time", *i32, "time", "int32 offsets; stale may wrap")
        tile_min = c("tile_min", *i32, "time", "empty tiles = +inf sentinel")
    else:
        ev_time = c("ev_time", 0, h + offset_hi, "time", "absolute ns")
        tile_min = c(
            "tile_min", 0, int(_INF_NS), "time", "empty tiles = +inf sentinel"
        )
    out = [
        c("seed", *u64),
        c("now", 0, h, "time"),
        c("step", 0, ABSINT_STEP_MAX, "counter", "RNG step coordinate"),
        c("halted", 0, 1),
        c("halt_time", 0, h, "time"),
        c("trace", *u64, None, "rolling hash, modular by design"),
        c("overflow", 0, cnt, "counter"),
        c("msg_count", 0, cnt, "counter"),
        ev_time,
        c("ev_valid", 0, 1),
        c("ev_meta", *u32, None, "packed kind/node/src/retry bytes"),
        c("ev_epoch", -1, cnt, "counter", "-1 = ANY-epoch sentinel"),
        c("ev_args", *i32),
        c("ev_pay", *i32),
        c("alive", 0, 1),
        c("paused", 0, 1),
        c("epoch", 0, cnt, "counter"),
        _node_state_contract(wl, i32),
        c("clog", 0, 1),
        c("slow", 0, SLOW_MULT_MAX, None, "link latency multiplier"),
        c("dup", 0, 1),
        c("skew", *i32, None, "per-node clock skew ns"),
        c("disk", *i32),
        c("wmask", 0, 1),
        c("sync_loss", 0, 1),
        c("sync_eio", 0, 1),
        c("torn", 0, 1),
        c("hist_count", 0, max(hcap, 0), "counter"),
        c("hist_drop", 0, cnt, "counter"),
        c("hist_word", *i32),
        c("hist_t", 0, h, "time"),
        c("cov", *u32, None, "bitmap words, modular folds"),
        c("cov_last", -1, 255),
        c("cov_hits", 0, 255),
        c("met", 0, cnt, "counter"),
        c("tl_count", 0, cnt, "counter"),
        c("tl_drop", 0, cnt, "counter"),
        c("tl_t", 0, h, "time"),
        c("tl_meta", *u32),
        c("tl_args", *i32),
        c("tl_pay", *i32),
        c("ev_emit", 0, h, "time"),
        c("tl_emit", 0, h, "time"),
        # causal columns (causal=True): the Lamport clocks grow by at
        # most one per dispatch, so the step-count budget bounds them;
        # parent seqs are clamped copies of `step` with the sentinel
        # classes below zero (PARENT_ARMY = -3 is the floor)
        c("lam", 0, ABSINT_STEP_MAX, "counter", "per-node Lamport clock"),
        c("ev_parent", PARENT_ARMY, ABSINT_STEP_MAX, "counter",
          "emitting dispatch seq; -1/-2/-3 sentinel classes"),
        c("ev_lam", 0, ABSINT_STEP_MAX, "counter",
          "emitting dispatch's Lamport clock"),
        c("tl_seq", 0, ABSINT_STEP_MAX, "counter", "dispatch seq per row"),
        c("tl_parent", PARENT_ARMY, ABSINT_STEP_MAX, "counter",
          "parent seq per row; sentinel classes below zero"),
        c("tl_lam", 0, ABSINT_STEP_MAX, "counter"),
        c("lat_inv", -1, h, "time", "-1 = never invoked"),
        c("lat_resp", -1, h, "time", "-1 = incomplete"),
        c("lat_hist", 0, cnt, "counter"),
        c("lat_count", 0, cnt, "counter"),
        c("lat_drop", 0, cnt, "counter"),
        # client-retry columns (RetrySpec): attempt ids are token-packed
        # 4-bit values; the deadline clock is ALWAYS absolute int64
        # (observability-friendly even under time32 — it never feeds the
        # pool), bounded by the horizon plus one timer arm's offset and
        # the int64-capped backoff+jitter
        c("rt_done", 0, 1),
        c("rt_attempt", 0, RETRY_ATTEMPT_MAX, "counter",
          "delivered attempt id per op"),
        c("rt_deadline", 0,
          h + offset_hi + 2 * _RETRY_BACKOFF_CAP, "time",
          "absolute ns; armed response deadline per op"),
        tile_min,
        c("tile_cnt", 0, max(pool_tile(cfg.pool_size), 64), "counter"),
    ]
    contracts = {cc.field: cc for cc in out}
    missing = [
        f.name for f in dataclasses.fields(SimState)
        if f.name not in contracts
    ]
    if missing:
        # a new SimState column without a declared contract would
        # silently weaken the proof (full-range, untracked)
        raise AssertionError(
            f"column_contracts is missing SimState fields: {missing}"
        )
    return contracts


# Largest slow-link latency multiplier the packed args word can carry:
# pack_slow_arg stores the multiplier in bits 8..30 of an int32. The
# chaos plan validator (chaos/plan.py GrayFailure) enforces it at spec
# build time and the absint range contracts (column_contracts) assume
# it — one declaration, so the validator and the prover cannot drift.
SLOW_MULT_MAX = (1 << 23) - 1


def pack_slow_arg(b, mult):
    """Pack a slow-link peer + multiplier into one int32 args word:
    low byte = peer node + 1 (0 = node-wide), bits 8.. = multiplier.
    This function OWNS the layout (the chaos plan compiler and the
    in-step decode both route through it / unpack_slow_arg). Works on
    Python ints, numpy arrays (plan compilation) and traced values
    (EmitBuilder helpers)."""
    if isinstance(b, (int, np.integer)) and isinstance(mult, (int, np.integer)):
        return ((int(b) + 1) & 0xFF) | (int(mult) << 8)
    if isinstance(b, np.ndarray) or isinstance(mult, np.ndarray):
        return ((np.asarray(b, np.int64) + 1) & 0xFF) | (
            np.asarray(mult, np.int64) << 8
        )
    return (
        (jnp.asarray(b, jnp.int32) + 1) & jnp.int32(0xFF)
    ) | (jnp.asarray(mult, jnp.int32) << jnp.int32(8))


def unpack_slow_arg(word: int) -> tuple:
    """Inverse of :func:`pack_slow_arg` for host ints: (peer, mult) —
    peer -1 means node-wide."""
    return (int(word) & 0xFF) - 1, int(word) >> 8


def user_kind(i: int) -> int:
    """Kind id of user handler ``i`` (handler 0 = on_init)."""
    return FIRST_USER_KIND + i


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static simulation parameters (the analog of sim Config, config.rs:15).

    All values participate in the config hash printed on failure so a
    repro needs (seed, config) exactly like the reference
    (runtime/mod.rs:193-200).
    """

    pool_size: int = 256  # E: max in-flight events per seed
    lat_min_ns: int = 1_000_000  # network latency range, default 1-10 ms
    lat_max_ns: int = 10_000_000  # (reference network.rs:84-90)
    loss_p: float = 0.0  # packet loss rate (network.rs:75-95)
    proc_min_ns: int = 50  # per-event processing cost
    proc_max_ns: int = 100  # (task.rs:213)
    clog_backoff_min_ns: int = 1_000_000  # clogged-delivery recheck backoff
    clog_backoff_max_ns: int = 10_000_000_000  # 1 ms -> 10 s (net/mod.rs:341-355)
    time_limit_ns: int = 0  # 0 = unlimited (set_time_limit, runtime/mod.rs:143)

    def __post_init__(self):
        # draws are 32-bit; a span that doesn't fit uint32 would silently
        # wrap in the modulo reduction and skew the distribution
        for lo, hi, what in (
            (self.lat_min_ns, self.lat_max_ns, "latency"),
            (self.proc_min_ns, self.proc_max_ns, "processing-cost"),
        ):
            if hi < lo:
                raise ValueError(f"{what} range [{lo}, {hi}) is empty")
            if hi - lo > DRAW_SPAN_MAX:
                raise ValueError(
                    f"{what} span {hi - lo} ns does not fit uint32 "
                    f"(max {DRAW_SPAN_MAX} ns, ~4.29 s)"
                )

    @property
    def loss_u32(self) -> int:
        return chance_threshold(self.loss_p)

    def hash(self) -> str:
        """Stable hex hash of the config (config.rs:27-31 analog)."""
        import hashlib

        s = repr(dataclasses.astuple(self)).encode()
        return hashlib.sha256(s).hexdigest()[:16]


@dataclasses.dataclass(frozen=True)
class Build:
    """Everything a program is built from besides the workload and the
    config: the lowering and the program-shaping axes.

    Each of :func:`make_init`, :func:`make_step`, :func:`make_run`,
    :func:`make_run_while`, ``make_run_compacted`` and ``make_sweep``
    takes one and resolves it first, so an init and a run built from
    the same Build cannot disagree. Frozen and hashable: a resolved
    Build keys the compiled-program caches whole.

    The lowering — four value-identical choices of how the same program
    is written (the oracle suite and the goldens pin the identity);
    ``None`` means automatic:

    * ``layout`` picks the lowering of the per-event reads/writes.
      ``"dense"``: one-hot masked reductions and rank-match placement,
      no gather/scatter ops — TPU lowers batched scatter/gather to
      serial loops (measured 96% of step wall time,
      examples/profile_step.py), so dense is ~70x faster there.
      ``"scatter"``: row-indexed gathers plus ``placement``-selected
      pool writes, the fast CPU lowering. Auto: scatter on the CPU
      backend, dense elsewhere.
    * ``placement`` picks the scatter layout's pool-WRITE lowering
      (dense ignores it — its one-hot placement is already
      rank-matched). ``"rank"``: the j-th ready emit pairs with the
      j-th free slot by cumsum rank, pool columns update through fused
      gather+select passes — no scatter ops in the hot path (XLA CPU
      lowers a batched scatter to a serial per-row loop, ~110 ns/row).
      ``"scatter"``: ``.at[].set`` stores, O(emit rows) each whatever
      the pool size, so they win once the pool is large. Auto:
      ``"rank"`` at or below 512 slots when the pool index is off,
      else ``"scatter"``.
    * ``pool_index`` adds the two-level readiness index to the pool:
      per-tile ``tile_min``/``tile_cnt`` summary columns ride SimState
      (derived by construction, rebuilt on checkpoint restore), the pop
      runs argmin over E/T tile minima then inside the one winning
      tile, and placement's free-slot search runs over per-tile free
      counts — O(E/T + T + emits) per event instead of O(E). Requires
      a tile-divisible pool (:func:`pool_tile`;
      ``chaos.FaultPlan.min_pool_size`` sizes army pools aligned) and
      the scatter layout. Auto: on for CPU scatter pools larger than
      1024 slots.
    * ``time32`` picks the representation of pool event times: int32
      offsets from ``now`` rebased each step (native-width on TPU; v5e
      emulates 64-bit lanes at ~2x cost; needs
      :func:`time32_eligible` bounds) or absolute int64 nanoseconds.
      Auto: :func:`resolve_time32`.

    The axes — each off by default, where it compiles away and values
    are bit-identical to the plain program:

    * ``plan_slots=P`` reserves P pool rows per seed for a compiled
      fault plan (madsim_tpu.chaos): ``init(seeds, plan)`` then takes a
      :class:`PlanRows`. Only init reads it.
    * ``dup_rows=True`` compiles the message-duplication path
      (KIND_DUP_ON chaos): each user emit row gets a shadow row that,
      while the seed's ``dup`` flag is set, inserts a second delivery
      with an independent latency/loss draw (purpose PURPOSE_DUP+slot).
    * ``cov_words=CW`` compiles the coverage taps (madsim_tpu.explore):
      each dispatched event folds behavior features — user (node,
      previous kind, kind) transitions, (kind, coarse time phase)
      markers for engine/chaos events, appended history records — into
      a CW*32-bit per-seed bitmap. ``cov_hitcount=True`` upgrades it to
      AFL-style hit-count bucketing (a saturating per-feature counter;
      the bitmap bit is keyed by (feature, bucket class)).
    * ``metrics=True`` folds the MET_* fleet counters into
      ``SimState.met``; ``timeline_cap=T`` records the dispatched-event
      stream into a T-slot ring per seed (overflow counted in
      ``tl_drop``, decoded by ``obs.decode_timeline``).
    * ``latency=LatencySpec(...)`` compiles the tail-latency tap:
      handlers mark client-army op invokes/responses, the engine stamps
      per-op clocks and folds each completed op into the per-seed
      log-linear sketch of its invoke-time window; with coverage on,
      each completion also folds a (window, latency-bucket) feature.
    * ``causal=True`` folds exact causal provenance: each dispatch's
      sequence number becomes the ``ev_parent`` of every event it
      emits, the destination's Lamport clock folds, and — with the
      ring on — each captured row banks ``tl_seq``/``tl_parent``/
      ``tl_lam`` (decoded by ``obs.causal``).
    * ``retry=RetrySpec(...)`` arms the client-retry timers (core
      state: armed deadlines are history, unlike the taps above).
    """

    layout: str | None = None
    placement: str | None = None
    pool_index: bool | None = None
    time32: bool | None = None
    plan_slots: int = 0
    dup_rows: bool = False
    cov_words: int = 0
    cov_hitcount: bool = False
    metrics: bool = False
    timeline_cap: int = 0
    latency: LatencySpec | None = None
    causal: bool = False
    retry: RetrySpec | None = None

    def resolve(self, wl: "Workload", cfg: EngineConfig) -> "Build":
        """This spec with its lowering made concrete for ``(wl, cfg)``
        on the current backend, after the build checks. Idempotent:
        resolving a resolved spec returns an equal one."""
        _check_cov_words(self.cov_words)
        _check_obs(
            self.cov_words, self.cov_hitcount, self.timeline_cap,
            self.latency,
        )
        _check_retry(wl, self.retry)
        layout = resolve_layout(self.layout)
        pool_index = _resolve_pool_index(
            cfg, self.pool_index, dense=layout == "dense"
        )
        placement = self.placement
        if placement is None:
            placement = (
                "rank"
                if not pool_index and cfg.pool_size <= _RANK_PLACE_MAX_POOL
                else "scatter"
            )
        if placement not in ("rank", "scatter"):
            raise ValueError(f"unknown placement {placement!r}")
        time32 = resolve_time32(wl, cfg, self.time32)
        if self.retry is not None and time32:
            # a retry timer's delay must fit the int32 offset form like
            # any declared timer bound (the delay_bound_ns eligibility
            # rule)
            boff, bjit = _retry_backoff_tables(self.retry)
            worst = int(self.retry.timeout_ns) + boff[-1] + bjit[-1]
            lim32 = _T32_LIMIT - cfg.proc_max_ns - 1
            if worst > lim32:
                raise ValueError(
                    f"retry timeout + max backoff + max jitter = {worst} ns "
                    f"exceeds the int32 offset horizon ({lim32} ns); shrink "
                    f"the policy or build with time32=False"
                )
        return dataclasses.replace(
            self, layout=layout, placement=placement,
            pool_index=pool_index, time32=time32,
        )


def check_plan_horizon(
    build: Build, cfg: EngineConfig, worst_ns: int, *, space: bool = False
) -> None:
    """Refuse plan event times past the int32 offset horizon of a
    resolved ``time32`` build, where they would silently wrap.
    ``space=True`` words the error for a plan space's windows."""
    if not build.time32:
        return
    lim = _T32_LIMIT - cfg.proc_max_ns - 1
    if worst_ns <= lim:
        return
    if space:
        raise ValueError(
            f"plan-space window reaches t={worst_ns} ns, past the int32 "
            f"time horizon ({lim} ns) active for this (workload, "
            f"config); shrink the windows or disable time32"
        )
    raise ValueError(
        f"fault-plan event at t={worst_ns} ns exceeds the int32 time "
        f"horizon ({lim} ns) active for this (workload, config); shrink "
        f"the plan windows or disable time32"
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Emits:
    """Fixed-capacity batch of events a handler emits (K slots).

    ``send`` slots are translated by the engine into future deliveries
    (latency + loss + clog, the NetSim path in SURVEY §3.3); timer slots
    become plain future events (add_timer, time/mod.rs:138-149).
    """

    valid: jnp.ndarray  # (K,)  bool
    send: jnp.ndarray  # (K,)  bool: network message vs local timer
    kind: jnp.ndarray  # (K,)  int32
    dst: jnp.ndarray  # (K,)  int32
    delay: jnp.ndarray  # (K,)  int64 ns (timer) / ignored for sends
    args: jnp.ndarray  # (K,4) int32
    pay: jnp.ndarray  # (K,W) int32 payload words (W = Workload.payload_words)
    # operation-history records (R = HistorySpec.max_records, 0 = off):
    # each row is (op, key, arg, ok); the engine stamps the client node
    # and the dispatch time when appending to the history columns
    rec_valid: jnp.ndarray = None  # (R,) bool
    rec: jnp.ndarray = None  # (R,4) int32
    # sync flag (Workload.durable_sync): True = the handler called
    # fsync before returning — the engine commits the node's durable
    # columns to its disk image at THIS dispatch (unless a SYNC_LOSS
    # window makes the disk lie). A scalar, not per-slot: one dispatch
    # is one fsync decision. Ignored when the discipline is off.
    sync: jnp.ndarray = None  # () bool
    # latency markers (L = Workload.lat_markers, 0 = off): each row
    # marks one client-army op — lat[j] = (op_id, phase) with phase 0 =
    # invoke (EmitBuilder.lat_start) and 1 = response (lat_end). The
    # engine stamps the dispatch clock into the latency columns; with
    # the latency tap off the markers are dead values XLA removes.
    lat_valid: jnp.ndarray = None  # (L,) bool
    lat: jnp.ndarray = None  # (L, 2) int32

    @staticmethod
    def none(k: int, w: int = 0, a: int = 4, r: int = 0, l: int = 0) -> "Emits":
        return Emits(
            valid=jnp.zeros((k,), jnp.bool_),
            send=jnp.zeros((k,), jnp.bool_),
            kind=jnp.zeros((k,), jnp.int32),
            dst=jnp.zeros((k,), jnp.int32),
            delay=jnp.zeros((k,), jnp.int64),
            args=jnp.zeros((k, a), jnp.int32),
            pay=jnp.zeros((k, w), jnp.int32),
            rec_valid=jnp.zeros((r,), jnp.bool_),
            rec=jnp.zeros((r, 4), jnp.int32),
            sync=jnp.asarray(False),
            lat_valid=jnp.zeros((l,), jnp.bool_),
            lat=jnp.zeros((l, 2), jnp.int32),
        )


class EmitBuilder:
    """Trace-time helper for constructing :class:`Emits` inside handlers.

    Slot assignment happens at Python trace time (static); the ``when``
    flag is the traced per-seed condition making an emit conditional.
    """

    def __init__(self, k: int, w: int = 0, a: int = 4, r: int = 0, l: int = 0):
        self._k = k
        self._w = w
        self._a = a
        self._r = r
        self._l = l
        self._recs: list[tuple] = []
        self._rows: list[tuple] = []
        self._syncs: list = []
        self._lats: list[tuple] = []

    def _push(self, send, kind, dst, delay, args, when, pay=()):
        if len(self._rows) >= self._k:
            raise ValueError(
                f"handler emits more than max_emits={self._k} events; "
                f"raise Workload.max_emits"
            )
        if len(args) > self._a:
            raise ValueError(
                f"{len(args)} event args exceed Workload.args_words={self._a}"
            )
        a = list(args) + [0] * (self._a - len(args))
        p = list(pay)
        if len(p) > self._w:
            raise ValueError(
                f"payload of {len(p)} words exceeds "
                f"Workload.payload_words={self._w}"
            )
        self._rows.append((when, send, kind, dst, delay, a, p))

    def send(self, dst, kind, args=(), when=True, pay=()):
        """Send a network message: delivery after latency unless lost/clogged.
        ``pay`` is an optional payload of up to ``Workload.payload_words``
        int32 words, carried with the event (the batched analog of the
        reference's ``Payload = Box<dyn Any>``, sim/net/endpoint.rs:13-23)."""
        self._push(True, kind, dst, 0, args, when, pay)

    def after(self, delay_ns, kind, dst, args=(), when=True, pay=()):
        """Schedule a local event ``delay_ns`` in the future (a timer)."""
        self._push(False, kind, dst, delay_ns, args, when, pay)

    def kill(self, node, when=True):
        self.after(0, KIND_KILL, 0, (node,), when)

    def restart(self, node, when=True):
        self.after(0, KIND_RESTART, 0, (node,), when)

    def restart_after(self, delay_ns, node, when=True):
        self.after(delay_ns, KIND_RESTART, 0, (node,), when)

    def pause(self, node, when=True):
        self.after(0, KIND_PAUSE, 0, (node,), when)

    def resume(self, node, when=True):
        self.after(0, KIND_RESUME, 0, (node,), when)

    def clog_link(self, a, b, when=True):
        self.after(0, KIND_CLOG, 0, (a, b), when)

    def unclog_link(self, a, b, when=True):
        self.after(0, KIND_UNCLOG, 0, (a, b), when)

    def clog_link_one_way(self, src, dst, when=True):
        """Asymmetric partition edge: block src -> dst only."""
        self.after(0, KIND_CLOG_1W, 0, (src, dst), when)

    def unclog_link_one_way(self, src, dst, when=True):
        self.after(0, KIND_UNCLOG_1W, 0, (src, dst), when)

    def slow_link(self, a, b, mult, when=True):
        """Gray failure: multiply a<->b latency by ``mult`` (b=-1 slows
        every link in or out of a)."""
        self.after(0, KIND_SLOW_LINK, 0, (a, pack_slow_arg(b, mult)), when)

    def unslow_link(self, a, b, when=True):
        self.after(0, KIND_UNSLOW, 0, (a, pack_slow_arg(b, 1)), when)

    def dup_on(self, when=True):
        """Start duplicating messages (needs a Build with dup_rows=True)."""
        self.after(0, KIND_DUP_ON, 0, (), when)

    def dup_off(self, when=True):
        self.after(0, KIND_DUP_OFF, 0, (), when)

    def set_skew(self, node, skew_ns, when=True):
        """Set the node's clock skew: its handlers observe now+skew_ns."""
        self.after(0, KIND_SKEW, 0, (node, skew_ns), when)

    def sync(self, when=True):
        """fsync the handling node's durable columns (Workload.durable_sync).

        Under the two-phase sync discipline a durable write lands in a
        volatile buffer and survives KIND_KILL only once a sync has
        committed it; calling this inside the dispatch that wrote models
        a blocking fsync before the handler's messages go out. A no-op
        when the workload does not opt into the discipline, and a LIE
        inside a chaos ``KIND_SYNC_LOSS`` window (the commit silently
        does not happen — what the hunt for missing-sync bugs injects).
        """
        self._syncs.append(when)

    def sync_loss(self, node, when=True):
        """Chaos: the node's disk starts lying — syncs stop committing
        (node=-1: every node). See ``chaos.DiskFault`` for the plan form."""
        self.after(0, KIND_SYNC_LOSS, 0, (node,), when)

    def sync_eio(self, node, when=True):
        """Chaos: the node's disk starts FAILING observably — syncs stop
        committing and the node's handlers see ``ctx.sync_err`` until a
        ``sync_ok`` (the batched ``FsSim.set_fail_writes``)."""
        self.after(0, KIND_SYNC_LOSS, 0, (node, 1), when)

    def sync_ok(self, node, when=True):
        """Chaos: end the node's sync-lie AND fsync-EIO windows."""
        self.after(0, KIND_SYNC_OK, 0, (node,), when)

    def torn_on(self, node, when=True):
        """Chaos: arm torn-write mode — the node's next KILL persists
        only a drawn prefix of its last uncommitted durable write."""
        self.after(0, KIND_TORN_ON, 0, (node,), when)

    def torn_off(self, node, when=True):
        self.after(0, KIND_TORN_OFF, 0, (node,), when)

    def halt(self, when=True):
        self.after(0, KIND_HALT, 0, (), when)

    def record(self, op, key=0, arg=0, ok=1, when=True):
        """Append one operation-history record (madsim_tpu.check).

        ``op``/``key``/``arg`` are workload-defined int32 words; ``ok``
        follows the check.history convention (-1 = invoke of a pending
        operation, 1 = successful response, 0 = failed response). The
        engine stamps the record with the handling node (the client
        column) and the dispatch sim-time. Requires ``Workload.history``.
        """
        if self._r == 0:
            raise ValueError(
                "record() needs history slots; set Workload.history to a "
                "HistorySpec (and size its max_records)"
            )
        if len(self._recs) >= self._r:
            raise ValueError(
                f"handler records more than max_records={self._r} history "
                f"entries; raise HistorySpec.max_records"
            )
        self._recs.append((when, op, key, arg, ok))

    def _lat_mark(self, op_id, phase: int, when) -> None:
        if self._l == 0:
            raise ValueError(
                "lat_start/lat_end need latency marker slots; set "
                "Workload.lat_markers (the per-invocation marker count)"
            )
        if len(self._lats) >= self._l:
            raise ValueError(
                f"handler marks more than lat_markers={self._l} latency "
                f"ops; raise Workload.lat_markers"
            )
        self._lats.append((when, op_id, phase))

    def lat_start(self, op_id, when=True):
        """Mark the INVOKE of client-army op ``op_id`` (madsim_tpu.obs
        latency): the engine stamps this dispatch's clock into
        ``lat_inv[op_id]``. The first start wins; repeats are ignored —
        an open-loop army invokes each op id exactly once, so repeats
        only arise from hand-built workloads. Derived state only: with
        the latency tap off (``latency=None``) the marker costs nothing
        and traces are bit-identical."""
        self._lat_mark(op_id, 0, when)

    def lat_end(self, op_id, when=True):
        """Mark the RESPONSE of client-army op ``op_id``: the engine
        stamps ``lat_resp[op_id]`` and folds the op's latency into the
        per-seed log-linear sketch (``lat_hist``). First response wins
        (a duplicated delivery does not count twice); an end without a
        prior start is ignored (the invoke never happened)."""
        self._lat_mark(op_id, 1, when)

    def _build_recs(self):
        r = self._r
        if not self._recs:
            return (
                jnp.zeros((r,), jnp.bool_),
                jnp.zeros((r, 4), jnp.int32),
            )
        pad = r - len(self._recs)
        valid = [jnp.asarray(wh, jnp.bool_) for (wh, *_x) in self._recs]
        words: list = []
        for (_wh, *rest) in self._recs:
            words.extend(rest)
        words += [0] * (pad * 4)
        return (
            jnp.stack(valid + [False] * pad),
            jnp.stack([jnp.asarray(x, jnp.int32) for x in words]).reshape(r, 4),
        )

    def _build_sync(self):
        sync = jnp.asarray(False)
        for wh in self._syncs:
            sync = sync | jnp.asarray(wh, jnp.bool_)
        return sync

    def _build_lats(self):
        l = self._l
        if not self._lats:
            return (
                jnp.zeros((l,), jnp.bool_),
                jnp.zeros((l, 2), jnp.int32),
            )
        pad = l - len(self._lats)
        valid = [jnp.asarray(wh, jnp.bool_) for (wh, *_x) in self._lats]
        words: list = []
        for (_wh, oid, ph) in self._lats:
            words.extend((oid, ph))
        words += [0] * (pad * 2)
        return (
            jnp.stack(valid + [False] * pad),
            jnp.stack([jnp.asarray(x, jnp.int32) for x in words]).reshape(l, 2),
        )

    def build(self) -> Emits:
        k, w = self._k, self._w
        rec_valid, rec = self._build_recs()
        sync = self._build_sync()
        lat_valid, lat = self._build_lats()
        if not self._rows:
            em = Emits.none(k, w, self._a)
            return dataclasses.replace(
                em, rec_valid=rec_valid, rec=rec, sync=sync,
                lat_valid=lat_valid, lat=lat,
            )
        pad = k - len(self._rows)
        valid = [jnp.asarray(wh, jnp.bool_) for (wh, *_r) in self._rows]
        send = [jnp.asarray(s, jnp.bool_) for (_w, s, *_r) in self._rows]
        kind = [jnp.asarray(kd, jnp.int32) for (_w, _s, kd, *_r) in self._rows]
        dst = [jnp.asarray(d, jnp.int32) for (*_h, d, _dl, _a, _p) in self._rows]
        delay = [jnp.asarray(dl, jnp.int64) for (*_h, dl, _a, _p) in self._rows]
        args = [
            jnp.stack([jnp.asarray(x, jnp.int32) for x in a])
            for (*_h, a, _p) in self._rows
        ]

        def pay_row(p: list) -> jnp.ndarray:
            if not p:
                return jnp.zeros((w,), jnp.int32)
            row = jnp.stack([jnp.asarray(x, jnp.int32) for x in p])
            return jnp.concatenate([row, jnp.zeros((w - len(p),), jnp.int32)])

        pay = [pay_row(p) for (*_h, p) in self._rows]
        z32 = jnp.int32(0)
        return Emits(
            valid=jnp.stack(valid + [jnp.asarray(False)] * pad),
            send=jnp.stack(send + [jnp.asarray(False)] * pad),
            kind=jnp.stack(kind + [z32] * pad),
            dst=jnp.stack(dst + [z32] * pad),
            delay=jnp.stack(delay + [jnp.int64(0)] * pad),
            args=jnp.stack(args + [jnp.zeros((self._a,), jnp.int32)] * pad),
            pay=jnp.stack(pay + [jnp.zeros((w,), jnp.int32)] * pad),
            rec_valid=rec_valid,
            rec=rec,
            sync=sync,
            lat_valid=lat_valid,
            lat=lat,
        )


@dataclasses.dataclass(frozen=True)
class HistorySpec:
    """Per-seed operation-history recording (madsim_tpu.check).

    Histories are fixed-size on-device columns, the same discipline as
    the trace hash: ``capacity`` slots per seed, each slot one record of
    (op, key, arg, client, ok) int32 words plus an int64 sim-time.
    Handlers append records through :meth:`EmitBuilder.record`; a full
    buffer never drops silently — overflow is counted in
    ``SimState.hist_drop`` and the checkers refuse such seeds.

    Sizing: one *operation* costs two records (an invoke and a
    response); instantaneous events (e.g. an election win) cost one.
    ``max_records`` is the per-handler-invocation slot count (the
    history analog of ``max_emits``).
    """

    capacity: int
    max_records: int = 2

    def __post_init__(self):
        if self.capacity < 1:
            raise ValueError(f"history capacity must be >= 1, got {self.capacity}")
        if self.max_records < 1:
            raise ValueError(
                f"max_records must be >= 1, got {self.max_records}"
            )


@dataclasses.dataclass
class HandlerCtx:
    """Everything a handler sees about the event it is processing."""

    now: jnp.ndarray  # int64 ns — virtual clock
    node: jnp.ndarray  # int32 — the node this event targets
    state: jnp.ndarray  # (U,) int32 — the node's state row
    args: jnp.ndarray  # (4,) int32 — event arguments
    src: jnp.ndarray  # int32 — sender node for messages, -1 for timers
    draw: Draw  # counter-based RNG for this event
    max_emits: int
    payload: jnp.ndarray = None  # (W,) int32 — the event's payload words
    payload_words: int = 0
    args_words: int = 4
    max_records: int = 0  # history record slots (Workload.history)
    # () bool — the handling node is inside an injected fsync-EIO
    # window (KIND_SYNC_LOSS mode 1, chaos.DiskFault n_eio): its syncs
    # are failing OBSERVABLY, the batched analog of FsSim's
    # set_fail_writes OSError(EIO). Always False when the workload has
    # no sync discipline or no EIO window is open, so handlers that
    # gate on it (e.g. withhold an ack they cannot persist) are
    # value-identical to ungated ones on every fault-free trajectory.
    sync_err: jnp.ndarray = None
    max_lat: int = 0  # latency marker slots (Workload.lat_markers)

    def emits(self) -> EmitBuilder:
        return EmitBuilder(
            self.max_emits, self.payload_words, self.args_words,
            self.max_records, self.max_lat,
        )


Handler = Callable[[HandlerCtx], tuple]


@dataclasses.dataclass(frozen=True)
class Workload:
    """A batched simulation program: per-node int32 state + event handlers.

    This is how "user code" enters the traced step function. Handlers are
    pure: ``handler(ctx) -> (new_state_row, Emits)``. Handler 0 is
    ``on_init`` — invoked for every node at t=0 and again when a node is
    restarted (the stored-init-task semantics of task.rs:279-291).
    """

    name: str
    n_nodes: int
    state_width: int
    handlers: tuple  # tuple[Handler, ...]
    max_emits: int = 8
    init_state: np.ndarray | None = None  # (N,U) int32; zeros if None
    # payload arena width: int32 words carried by every event (0 = off).
    # The batched analog of Payload = Box<dyn Any> (endpoint.rs:13-23):
    # payload lifetime equals event lifetime, so the arena IS the event
    # pool — no separate allocator, no leaks
    payload_words: int = 0
    # width of the per-event args vector (int32 words). Engine kinds use
    # args[0:2] (kill/clog targets), so 2 is the floor; shrink from the
    # default 4 when no handler reads args[2:] — the args arena is a
    # per-step placement cost like every pool field
    args_words: int = 4
    # largest timer delay (ns) any handler can pass to EmitBuilder.after.
    # Declaring it (together with config bounds, see time32_eligible)
    # unlocks the int32 event-time representation on accelerators; None
    # = unknown, keep int64 times. The engine still guards the claim at
    # runtime: a timer emit beyond the int32 horizon is counted into
    # `overflow`, which the bench refuses (bench.py pool_overflow path)
    delay_bound_ns: int | None = None
    # optional human names for the user handlers (len == len(handlers)),
    # used only by engine.replay timelines — no effect on execution
    handler_names: tuple | None = None
    # state-row columns that survive kill/restart — the batched analog
    # of FsSim's power-fail semantics (fs.rs:51: disk contents survive
    # a crash, RAM doesn't). RESTART restores the workload's initial
    # rows for every column NOT listed here; listed columns keep their
    # pre-kill values. None = everything volatile (pure-RAM nodes, the
    # default and the previous behavior). Applies to every node — pick
    # column meanings so "disk" columns line up across roles.
    durable_cols: tuple | None = None
    # operation-history recording (madsim_tpu.check): None = off (no
    # history columns, zero step cost). With a HistorySpec, handlers may
    # call EmitBuilder.record and the engine appends fixed-size history
    # rows per seed, checked host-side by the check package.
    history: HistorySpec | None = None
    # two-phase sync discipline over durable_cols (the storage-chaos
    # analog of a real write buffer, fs.rs:51 taken seriously): a
    # durable write lands in the node's volatile buffer and survives
    # KIND_KILL only once an EmitBuilder.sync commits it to the node's
    # disk image; chaos SYNC_LOSS windows make syncs lie and TORN_ON
    # makes a kill persist a drawn prefix of the last uncommitted
    # write. False (default) keeps the historical all-or-nothing
    # semantics: durable columns survive kill verbatim. NOTE: a
    # workload that syncs every durable write in the same dispatch is
    # trajectory-identical either way when no disk faults are injected
    # (the revert is a no-op), which keeps oracle compares exact.
    durable_sync: bool = False
    # latency marker slots per handler invocation (madsim_tpu.obs
    # latency): how many EmitBuilder.lat_start/lat_end calls one
    # dispatch may make. 0 (default) keeps the Emits pytree free of the
    # marker rows — every pre-latency workload is byte-identical.
    # Marker semantics are derived-state-only: the markers do nothing
    # at all unless the step is built with a LatencySpec.
    lat_markers: int = 0
    # protocol-specific coverage features (madsim_tpu.explore): a
    # traceable hook ``cov_features(node_state, now) -> iterable of
    # (feature, on)`` pairs — feature a uint32 word (the engine
    # namespaces it under its own tag before hashing into the bitmap),
    # ``on`` a () bool gate (ANDed with the user-dispatch gate). Runs
    # once per dispatched event over the POST-dispatch fleet state, so
    # a workload can contribute guidance signals the generic taps
    # cannot see — e.g. the fleet's commit-index spread (raftlog's
    # ``cov_spread=True``): a schedule that drags replicas apart is
    # new behavior even when no individual event is. Coverage is
    # derived state, so the hook CHANGES BITMAPS ONLY: traces,
    # trajectories and verdicts are bit-identical with it on or off
    # (and campaigns must not mix hooks, the cov_hitcount rule). None
    # (default) = no extra features, bitmaps unchanged.
    cov_features: Callable | None = None
    # user purposes to PREFETCH into the per-dispatch batched RNG block
    # (the BatchRNG shape, PAPERS.md): handler draws at these purposes
    # (the ints passed to ctx.draw.user/user_int) are served from lanes
    # of the ONE cipher pass the step already runs, instead of each
    # branch issuing its own scalar threefry — under vmap the
    # lax.switch evaluates EVERY branch per dispatch, so each distinct
    # in-branch cipher is a per-step cost whether or not its branch is
    # selected. Draw VALUES are bit-identical either way (same
    # (seed, step, purpose) counter per lane), so this is a pure
    # declaration of which lanes to batch; None/() changes nothing.
    draw_purposes: tuple | None = None
    # per-column range declarations (lint.absint): a tuple of
    # StateContract, TOTAL over state_width when present. Narrows the
    # node_state contract in column_contracts() from full int32 to the
    # hull of the declared columns and tags it, which makes the
    # interval prover check the workload's own deadline/epoch
    # arithmetic for overflow. Assume-guarantee: the model owes the
    # declared bounds (clamp before storing). None (default) keeps the
    # untracked full-range contract — existing proofs are unchanged.
    state_contracts: tuple | None = None

    def __post_init__(self):
        # emit slot s draws both its latency and loss words from the
        # PURPOSE_LATENCY(8)+s block (Draw.bits2); the slot range must
        # stay below the reserved PURPOSE_LOSS(64) space so it can never
        # bleed toward PURPOSE_USER and correlate "independent" draws.
        # -1: the engine appends one internal row (the restart re-init
        # event) after the user slots
        if not (2 <= self.args_words <= 4):
            raise ValueError(
                f"args_words={self.args_words} must be in [2, 4] "
                f"(engine kinds read args[0:2])"
            )
        limit = PURPOSE_LOSS - PURPOSE_LATENCY - 1
        if self.max_emits > limit:
            raise ValueError(
                f"max_emits={self.max_emits} exceeds the purpose-namespace "
                f"limit of {limit} (engine/rng.py purpose layout)"
            )
        if self.durable_cols is not None:
            bad = [c for c in self.durable_cols if not 0 <= c < self.state_width]
            if bad:
                raise ValueError(
                    f"durable_cols {bad} out of range for "
                    f"state_width={self.state_width}"
                )
        if self.durable_sync and not self.durable_cols:
            raise ValueError(
                "durable_sync needs durable_cols: the sync discipline "
                "governs exactly the columns that survive a kill"
            )
        if self.lat_markers < 0:
            raise ValueError(
                f"lat_markers must be >= 0, got {self.lat_markers}"
            )
        if self.draw_purposes is not None:
            # validated against the structured lane registry: before
            # PURPOSE_LANES, any purpose below 2^32 - PURPOSE_USER was
            # accepted and an out-of-range user lane silently aliased
            # the plan/explore/client high blocks. The error now names
            # the lane the purpose would collide with.
            validate_user_purposes(
                self.draw_purposes, what="Workload.draw_purposes"
            )
        if self.state_contracts is not None:
            cols = sorted(sc.col for sc in self.state_contracts)
            if cols != list(range(self.state_width)):
                raise ValueError(
                    f"state_contracts must declare every state column "
                    f"exactly once (expected cols 0..{self.state_width - 1}, "
                    f"got {cols}) — a partial declaration would silently "
                    f"weaken the node_state hull"
                )
            bad = [
                sc.col for sc in self.state_contracts
                if not (-(2 ** 31) <= sc.lo <= sc.hi <= 2 ** 31 - 1)
            ]
            if bad:
                raise ValueError(
                    f"state_contracts columns {bad} declare ranges that "
                    f"are empty or exceed int32"
                )
        if self.handler_names is not None and len(self.handler_names) != len(
            self.handlers
        ):
            raise ValueError(
                f"handler_names has {len(self.handler_names)} entries for "
                f"{len(self.handlers)} handlers — replay timelines would "
                f"label the wrong handlers"
            )

    def initial_state(self) -> np.ndarray:
        if self.init_state is not None:
            return np.asarray(self.init_state, np.int32)
        return np.zeros((self.n_nodes, self.state_width), np.int32)

    def volatile_mask(self) -> np.ndarray:
        """(U,) bool — True where RESTART resets to the initial row."""
        mask = np.ones((self.state_width,), bool)
        if self.durable_cols:
            mask[list(self.durable_cols)] = False
        return mask


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SimState:
    """One seed's full simulation state. ``vmap`` adds the (S,) axis."""

    seed: jnp.ndarray  # ()  uint64 instance seed
    now: jnp.ndarray  # ()  int64 virtual clock, ns
    step: jnp.ndarray  # ()  uint32 event sequence number (RNG coordinate)
    halted: jnp.ndarray  # () bool
    halt_time: jnp.ndarray  # () int64: clock when halted (else 0)
    trace: jnp.ndarray  # () uint64 rolling hash of dispatched events
    overflow: jnp.ndarray  # () int32 events dropped to pool overflow
    msg_count: jnp.ndarray  # () int64 — Stat{msg_count} (network.rs:106-111)
    # event pool, E slots
    ev_time: jnp.ndarray  # (E,) int64 absolute ns — or, under time32
    #                          (make_step), int32 offset from `now`
    ev_valid: jnp.ndarray  # (E,) bool
    ev_meta: jnp.ndarray  # (E,) uint32 packed kind/node/src/retry (see
    #                          the ev_meta byte-layout note above)
    ev_epoch: jnp.ndarray  # (E,) int32 target-node epoch at emit time
    ev_args: jnp.ndarray  # (E,4) int32
    ev_pay: jnp.ndarray  # (E,W) int32 payload words (W=0 when disabled)
    # nodes
    alive: jnp.ndarray  # (N,) bool
    paused: jnp.ndarray  # (N,) bool — events held while paused (pause/resume)
    epoch: jnp.ndarray  # (N,) int32
    node_state: jnp.ndarray  # (N,U) int32
    # network
    clog: jnp.ndarray  # (N,N) bool — link-clog matrix (net/mod.rs:157-216)
    # extended chaos state (madsim_tpu.chaos; defaults are the identity,
    # so workloads that never emit the extended kinds are bit-identical
    # to the pre-chaos engine)
    slow: jnp.ndarray  # (N,N) int32 — per-link latency multiplier (1 = normal)
    dup: jnp.ndarray  # () bool — message duplication on
    skew: jnp.ndarray  # (N,) int32 — per-node clock skew, ns (ctx.now offset)
    # two-phase sync discipline (Workload.durable_sync; D = n_nodes when
    # on, else 0 — zero-size arrays, zero step cost, bit-identical
    # values, the cov_words discipline). ``disk`` is the last-SYNCED
    # image of each node's durable columns (volatile columns unused);
    # a KILL reverts the node's durable state to it. ``wmask`` marks
    # the columns of the node's most recent uncommitted durable write —
    # the write a TORN_ON kill tears (a drawn prefix persists).
    disk: jnp.ndarray  # (D,U) int32 synced durable image
    wmask: jnp.ndarray  # (D,U) bool last uncommitted durable write's columns
    sync_loss: jnp.ndarray  # (D,) bool — sync-lie window active (chaos)
    sync_eio: jnp.ndarray  # (D,) bool — observable fsync-EIO window
    #   active (chaos): syncs fail AND handlers see ctx.sync_err, the
    #   batched FsSim.set_fail_writes. A lie window hides the failure;
    #   an EIO window reports it — the two bug surfaces differ exactly
    #   in whether correct code can react.
    torn: jnp.ndarray  # (D,) bool — torn-write mode armed (chaos)
    # operation history (madsim_tpu.check), H = HistorySpec.capacity
    # (0 when Workload.history is None). Rows are append-ordered by
    # dispatch time; hist_drop counts records lost to a full buffer —
    # a nonzero value means the seed's history verdict is unreliable
    # (the checkers refuse it, the pool-overflow rule applied again).
    hist_count: jnp.ndarray  # () int32 records stored
    hist_drop: jnp.ndarray  # () int32 records dropped at capacity
    hist_word: jnp.ndarray  # (H,5) int32 [op, key, arg, client, ok]
    hist_t: jnp.ndarray  # (H,) int64 record sim-time ns (absolute)
    # coverage fingerprint (madsim_tpu.explore), CW = the cov_words
    # build parameter (0 = off, zero-size arrays, zero step cost). Each
    # dispatched event folds features — per-node event-kind transition
    # pairs, engine/chaos kind x time-phase markers, history-record
    # words — into a CW*32-bit AFL-style bitmap; a set bit is a
    # behavior the seed exhibited. Purely derived from dispatched
    # values, so it never feeds back into the trajectory or the trace.
    cov: jnp.ndarray  # (CW,) uint32 coverage bitmap words
    cov_last: jnp.ndarray  # (N,) int32 last user kind per node (CW>0), else (0,)
    # AFL-style hit-count counters (cov_hitcount=True, madsim_tpu.obs):
    # one saturating uint8 per bitmap bit position; a feature's bit is
    # then keyed by (feature, hit-count bucket class) so a behavior
    # happening an order of magnitude more often is NEW coverage. Empty
    # (0,) when the bucketing flag is off — set-only bitmaps unchanged.
    cov_hits: jnp.ndarray  # (CW*32,) uint8 when hit-counting, else (0,)
    # fleet metrics (madsim_tpu.obs, metrics=True): see the MET_* slot
    # layout above. (0,) when off — derived state only, like cov.
    met: jnp.ndarray  # (N_METRICS,) int32 counters + the halt code
    # per-seed timeline ring (madsim_tpu.obs, timeline_cap=T): the
    # dispatched-event stream, exactly the tuples the trace hash folds
    # (time, packed meta, args, payload). A full
    # ring drops LOUDLY via tl_drop, the hist_drop rule again: the
    # timeline is forensics, not evidence, so a drop never quarantines
    # the seed's verdict — but the search banner surfaces it.
    tl_count: jnp.ndarray  # () int32 events recorded
    tl_drop: jnp.ndarray  # () int32 events dropped at capacity
    tl_t: jnp.ndarray  # (T,) int64 dispatch clock ns (unskewed)
    tl_meta: jnp.ndarray  # (T,) uint32 packed kind/node/src (ev_meta layout)
    tl_args: jnp.ndarray  # (T, A) int32 event args
    tl_pay: jnp.ndarray  # (T, W) int32 payload words — so the decoded
    # stream refolds to the certified trace for payload workloads too
    # emit-time sidecar (timeline_cap > 0, else both zero-size):
    # ev_emit[e] is the clock at which pool row e was INSERTED (the
    # emitting dispatch's time; 0 for init/plan rows), carried into
    # tl_emit so Perfetto flow arrows anchor at the true send time.
    # Derived state only — read exclusively into the ring.
    ev_emit: jnp.ndarray  # (E,) int64 when the ring is on, else (0,)
    tl_emit: jnp.ndarray  # (T,) int64 emit clock per captured dispatch
    # causal provenance (causal=True, else all zero-size — the same
    # derived-state-only discipline). ``lam`` is the node's Lamport
    # clock, folded at dispatch: lam[dst] = max(lam[dst], lam-at-emit)
    # + 1. ``ev_parent`` carries each pool row's emitting dispatch's
    # event-sequence number (SimState.step at emit; sentinel classes
    # PARENT_NONE/PLAN/ARMY for rows with no emitting dispatch) and
    # ``ev_lam`` that dispatch's folded clock — both read at pop
    # exclusively into the ring / the next fold, exactly the ev_emit
    # pattern. The ring banks the dispatch's own seq (``tl_seq``), its
    # parent's seq (``tl_parent``) and the folded clock (``tl_lam``),
    # which is the exact event-derivation DAG obs.causal reconstructs.
    lam: jnp.ndarray  # (N,) uint32 per-node Lamport clock, else (0,)
    ev_parent: jnp.ndarray  # (E,) int32 emitting dispatch seq, else (0,)
    ev_lam: jnp.ndarray  # (E,) uint32 clock at emit, else (0,)
    tl_seq: jnp.ndarray  # (T,) int32 dispatch seq per captured row
    tl_parent: jnp.ndarray  # (T,) int32 parent seq per captured row
    tl_lam: jnp.ndarray  # (T,) uint32 folded clock per captured row
    # tail-latency columns (madsim_tpu.obs latency; C = LatencySpec.ops,
    # 0 when the tap is off — zero-size, zero cost, bit-identical, the
    # cov_words discipline once more). lat_inv/lat_resp are the per-op
    # invoke/response clocks (-1 = not yet); lat_hist is the per-seed
    # log-linear sketch, (P, B) over (LatencySpec.phases, N_LAT_BUCKETS)
    # — exactly mergeable across seeds/shards by summation.
    lat_inv: jnp.ndarray  # (C,) int64 invoke clock per op id, -1 = never
    lat_resp: jnp.ndarray  # (C,) int64 response clock, -1 = incomplete
    lat_hist: jnp.ndarray  # (P, B) int32 latency sketch
    lat_count: jnp.ndarray  # () int32 completed ops folded into the sketch
    lat_drop: jnp.ndarray  # () int32 markers with out-of-range op ids (loud)
    # client-retry columns (Build's ``retry``; CR = RetrySpec.n_ops,
    # 0 with no policy — zero-size, zero cost, bit-identical). CORE
    # state, not derived: rt_done gates re-delivery, so retried
    # trajectories depend on it (see RETRY_STATE_FIELDS). rt_deadline is
    # ALWAYS absolute int64 — it never feeds the pool clock, so the
    # time32 representation does not apply (forensics read it directly).
    rt_done: jnp.ndarray  # (CR,) bool: response recorded for op
    rt_attempt: jnp.ndarray  # (CR,) int32: last DELIVERED attempt id
    rt_deadline: jnp.ndarray  # (CR,) int64: armed deadline, absolute ns
    # readiness-partitioned pool index (Build's ``pool_index``; NT =
    # pool_size/tile when on, else 0 — zero-size, zero cost, the usual
    # off discipline). Derived by construction from (ev_time, ev_valid)
    # — build_pool_index is the definition, checkpoint restore rebuilds
    # them, the format is unchanged — but trajectory-coupled: the pop
    # reads them, so they sit in core_fields for the taint proof (see
    # POOL_INDEX_STATE_FIELDS). tile_min of an empty tile is stale
    # (masked by tile_cnt > 0 at every use, the invalid-slot rule).
    tile_min: jnp.ndarray  # (NT,) pool-time dtype: earliest valid time/tile
    tile_cnt: jnp.ndarray  # (NT,) int32: valid slots per tile

    @property
    def sim_seconds(self):
        """Virtual seconds this instance has advanced (bench metric)."""
        return self.now.astype(jnp.float64) / 1e9


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def time32_eligible(wl: Workload, cfg: EngineConfig) -> bool:
    """Whether this (workload, config) pair can use int32 event times.

    Pool times under ``time32`` are offsets from the current clock; they
    only shrink as the clock advances, so the static bound is just the
    largest offset any insertion can create: a handler timer
    (``delay_bound_ns``), a network latency draw, or a clog-backoff
    reschedule (cap + the <1 µs jitter draw). The headroom subtracts
    ``proc_max_ns + 1`` so (a) a maximal valid offset stays strictly
    below the ``_INF_32`` invalid-slot sentinel in the pop, and (b) the
    per-step clock advance (offset + poll cost) can never overflow the
    int32 rebase.
    """
    lim = _T32_LIMIT - cfg.proc_max_ns - 1
    return (
        wl.delay_bound_ns is not None
        and wl.delay_bound_ns <= lim
        and cfg.lat_max_ns <= lim
        and cfg.clog_backoff_max_ns + 1_000 <= lim
    )


def resolve_time32(wl: Workload, cfg: EngineConfig, time32: bool | None) -> bool:
    """THE time32 default (Build's ``time32=None`` rule), the twin of
    :func:`resolve_layout`: int32 event times on accelerators
    whenever the bounds allow, int64 on the CPU backend."""
    if time32 is None:
        # int64 is native on CPU; accelerators (v5e has no 64-bit lanes)
        # get the narrow representation whenever the bounds allow it
        return time32_eligible(wl, cfg) and jax.default_backend() != "cpu"
    if time32 and not time32_eligible(wl, cfg):
        raise ValueError(
            f"time32 requested but {wl.name} / config are not eligible: "
            f"need delay_bound_ns ({wl.delay_bound_ns}), lat_max_ns "
            f"({cfg.lat_max_ns}) and clog_backoff_max_ns+1000 "
            f"({cfg.clog_backoff_max_ns + 1000}) all <= "
            f"{_T32_LIMIT - cfg.proc_max_ns - 1}"
        )
    return bool(time32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PlanRows:
    """Per-seed fault-plan events, compiled to pre-seeded event-pool rows.

    Produced host-side by ``madsim_tpu.chaos`` (FaultPlan.compile_batch)
    and consumed by the ``init`` built with ``Build(plan_slots=P)``:
    slot ``j`` of seed ``s`` becomes pool row ``n_nodes + j`` — an engine
    (or extended-chaos) event at the given absolute time. Invalid rows
    are skipped, so the per-seed event *count* may vary under one static
    ``P``. Times must respect the time32 horizon when that representation
    is active (the chaos compiler validates this).
    """

    time: jnp.ndarray  # (S, P) int64 absolute ns
    kind: jnp.ndarray  # (S, P) int32 engine/extended kind ids
    args: jnp.ndarray  # (S, P, 2) int32 — engine kinds read args[0:2]
    valid: jnp.ndarray  # (S, P) bool
    # target node per row (chaos ClientArmy: USER-kind rows address a
    # client node). None (the pre-army form) = every row targets node 0,
    # which engine kinds ignore — old plans are bit-identical.
    node: jnp.ndarray = None  # (S, P) int32, or None


def _check_cov_words(cov_words: int) -> None:
    if cov_words and (cov_words < 1 or cov_words & (cov_words - 1)):
        raise ValueError(
            f"cov_words={cov_words} must be 0 (off) or a power of two "
            f"(the feature hash reduces by bitmask)"
        )


def _check_obs(
    cov_words: int,
    cov_hitcount: bool,
    timeline_cap: int,
    latency: "LatencySpec | None" = None,
) -> None:
    """Observability build-parameter validation (run by
    :meth:`Build.resolve`)."""
    if cov_hitcount and not cov_words:
        raise ValueError(
            "cov_hitcount=True needs coverage enabled (cov_words > 0): "
            "hit-count buckets refine the coverage bitmap"
        )
    if timeline_cap < 0:
        raise ValueError(f"timeline_cap={timeline_cap} must be >= 0")
    if latency is not None and not isinstance(latency, LatencySpec):
        raise TypeError(
            f"latency must be a LatencySpec or None, got "
            f"{type(latency).__name__}"
        )


def make_init(wl: Workload, cfg: EngineConfig, build: Build = Build()):
    """Build ``init(seeds) -> SimState`` (batched over the seeds array).

    Seeds every node with an on_init event at t=0, mirroring the builder
    running each node's init task at simulation start. The columns are
    sized by ``build`` (:class:`Build`): build the step from the same
    spec. With ``plan_slots=P`` the returned ``init(seeds, plan)``
    requires a :class:`PlanRows` whose arrays carry the (S, P) events.
    Under ``causal`` plan rows are classed by sentinel:
    engine/chaos rows get :data:`PARENT_PLAN`, client-army USER rows
    :data:`PARENT_ARMY`, on_init rows :data:`PARENT_NONE`.
    """
    b = build.resolve(wl, cfg)
    n, u, e = wl.n_nodes, wl.state_width, cfg.pool_size
    p = b.plan_slots
    if e < n + p:
        raise ValueError(
            f"pool_size={e} must hold one on_init event per node ({n}) "
            f"plus the {p} fault-plan rows"
        )
    _check_meta_ranges(wl)
    cov_words, metrics, timeline_cap = b.cov_words, b.metrics, b.timeline_cap
    cov_hitcount, latency, causal, retry = (
        b.cov_hitcount, b.latency, b.causal, b.retry
    )
    rt_c = retry.n_ops if retry is not None else 0
    w = wl.payload_words
    h = wl.history.capacity if wl.history is not None else 0
    lat_c = latency.ops if latency is not None else 0
    lat_p = latency.phases if latency is not None else 0
    tdtype = jnp.int32 if b.time32 else jnp.int64
    base_state = jnp.asarray(wl.initial_state())
    # sync discipline: a fresh node's disk holds the initial image (the
    # durable columns of init_state are what a cold start reads back)
    d = n if wl.durable_sync else 0
    # readiness index: tile width + count (0 tiles = index off)
    p_tile = pool_tile(e) if b.pool_index else 0
    n_tiles = e // p_tile if p_tile else 0

    def init_one(seed, pt=None, pk=None, pa=None, pv=None, pn=None) -> SimState:
        seed = jnp.asarray(seed, jnp.uint64)
        ev_valid = jnp.zeros((e,), jnp.bool_).at[:n].set(True)
        ev_kind = jnp.full((e,), KIND_NOP, jnp.int32)
        ev_kind = ev_kind.at[:n].set(FIRST_USER_KIND)
        ev_node = jnp.zeros((e,), jnp.int32).at[:n].set(jnp.arange(n, dtype=jnp.int32))
        ev_time = jnp.zeros((e,), tdtype)
        ev_args = jnp.zeros((e, wl.args_words), jnp.int32)
        ev_epoch = jnp.zeros((e,), jnp.int32)
        if p:
            # plan rows ride slots [n, n+p): engine kinds target node 0
            # from a timer source, epoch 0 (engine kinds bypass the
            # epoch gate); client-army rows (USER kinds, chaos
            # ClientArmy) carry their target in the plan's node column
            # and ride the ANY-epoch sentinel (-1): open-loop load is
            # addressed to whatever incarnation of the client is up at
            # arrival time, so a kill+restart of the client drops only
            # the ops that arrive while it is DOWN — not every op for
            # the rest of the run (arrivals are wall-scheduled, not
            # incarnation-scoped). The liveness gate still applies.
            # At t=0 the time32 offset form equals the absolute form,
            # so the cast below is exact for validated plans (times
            # within the int32 horizon).
            ev_valid = ev_valid.at[n : n + p].set(pv)
            ev_kind = ev_kind.at[n : n + p].set(pk)
            ev_time = ev_time.at[n : n + p].set(pt.astype(tdtype))
            ev_args = ev_args.at[n : n + p, 0:2].set(pa)
            is_user_row = (pk >= FIRST_USER_KIND) & (pk < FIRST_EXT_KIND)
            ev_epoch = ev_epoch.at[n : n + p].set(
                jnp.where(is_user_row, jnp.int32(-1), jnp.int32(0))
            )
            if pn is not None:
                # clip to the meta byte range like every emit pack: an
                # out-of-range target matches nothing downstream
                ev_node = ev_node.at[n : n + p].set(
                    jnp.clip(pn.astype(jnp.int32), -1, n)
                )
        # src = -1 (timer), retry = 0 for every initial on_init event
        ev_meta = _meta_pack(
            ev_kind,
            ev_node + 1,
            jnp.zeros((e,), jnp.int32),
            jnp.zeros((e,), jnp.int32),
        )
        if causal:
            # no pre-seeded row has an emitting dispatch: on_init rows
            # (and never-written slots) are PARENT_NONE roots; plan rows
            # are classed engine/chaos vs client-army by the same USER-
            # kind predicate the epoch sentinel uses above
            ev_parent = jnp.full((e,), PARENT_NONE, jnp.int32)
            if p:
                ev_parent = ev_parent.at[n : n + p].set(
                    jnp.where(
                        is_user_row,
                        jnp.int32(PARENT_ARMY),
                        jnp.int32(PARENT_PLAN),
                    )
                )
        else:
            ev_parent = jnp.zeros((0,), jnp.int32)
        tc_c = timeline_cap if causal else 0
        if n_tiles:
            tile_min, tile_cnt = build_pool_index(ev_time, ev_valid, p_tile)
        else:
            tile_min = jnp.zeros((0,), tdtype)
            tile_cnt = jnp.zeros((0,), jnp.int32)
        return SimState(
            seed=seed,
            now=jnp.int64(0),
            step=jnp.uint32(0),
            halted=jnp.asarray(False),
            halt_time=jnp.int64(0),
            trace=jnp.uint64(0),
            overflow=jnp.int32(0),
            msg_count=jnp.int64(0),
            ev_time=ev_time,
            ev_valid=ev_valid,
            ev_meta=ev_meta,
            ev_epoch=ev_epoch,
            ev_args=ev_args,
            ev_pay=jnp.zeros((e, w), jnp.int32),
            alive=jnp.ones((n,), jnp.bool_),
            paused=jnp.zeros((n,), jnp.bool_),
            epoch=jnp.zeros((n,), jnp.int32),
            node_state=base_state,
            clog=jnp.zeros((n, n), jnp.bool_),
            slow=jnp.ones((n, n), jnp.int32),
            dup=jnp.asarray(False),
            skew=jnp.zeros((n,), jnp.int32),
            disk=(base_state if d else jnp.zeros((0, u), jnp.int32)),
            wmask=jnp.zeros((d, u), jnp.bool_),
            sync_loss=jnp.zeros((d,), jnp.bool_),
            sync_eio=jnp.zeros((d,), jnp.bool_),
            torn=jnp.zeros((d,), jnp.bool_),
            hist_count=jnp.int32(0),
            hist_drop=jnp.int32(0),
            hist_word=jnp.zeros((h, 5), jnp.int32),
            hist_t=jnp.zeros((h,), jnp.int64),
            cov=jnp.zeros((cov_words,), jnp.uint32),
            cov_last=jnp.zeros((n if cov_words else 0,), jnp.int32),
            cov_hits=jnp.zeros(
                (cov_words * 32 if cov_hitcount else 0,), jnp.uint8
            ),
            met=jnp.zeros((N_METRICS if metrics else 0,), jnp.int32),
            tl_count=jnp.int32(0),
            tl_drop=jnp.int32(0),
            tl_t=jnp.zeros((timeline_cap,), jnp.int64),
            tl_meta=jnp.zeros((timeline_cap,), jnp.uint32),
            tl_args=jnp.zeros((timeline_cap, wl.args_words), jnp.int32),
            tl_pay=jnp.zeros((timeline_cap, w), jnp.int32),
            ev_emit=jnp.zeros((e if timeline_cap else 0,), jnp.int64),
            tl_emit=jnp.zeros((timeline_cap,), jnp.int64),
            lam=jnp.zeros((n if causal else 0,), jnp.uint32),
            ev_parent=ev_parent,
            ev_lam=jnp.zeros((e if causal else 0,), jnp.uint32),
            tl_seq=jnp.zeros((tc_c,), jnp.int32),
            tl_parent=jnp.zeros((tc_c,), jnp.int32),
            tl_lam=jnp.zeros((tc_c,), jnp.uint32),
            lat_inv=jnp.full((lat_c,), -1, jnp.int64),
            lat_resp=jnp.full((lat_c,), -1, jnp.int64),
            lat_hist=jnp.zeros((lat_p, N_LAT_BUCKETS if lat_c else 0), jnp.int32),
            lat_count=jnp.int32(0),
            lat_drop=jnp.int32(0),
            rt_done=jnp.zeros((rt_c,), jnp.bool_),
            rt_attempt=jnp.zeros((rt_c,), jnp.int32),
            rt_deadline=jnp.zeros((rt_c,), jnp.int64),
            tile_min=tile_min,
            tile_cnt=tile_cnt,
        )

    def init(seeds, plan: PlanRows | None = None) -> SimState:
        seeds = jnp.asarray(seeds, jnp.uint64)
        if p:
            if plan is None:
                raise ValueError(
                    f"init was built with plan_slots={p}; pass the "
                    f"compiled PlanRows"
                )
            pn = getattr(plan, "node", None)
            if pn is None:
                # pre-army PlanRows: every row targets node 0 (engine
                # kinds ignore the target, the historical layout)
                pn = jnp.zeros_like(jnp.asarray(plan.kind, jnp.int32))
            return jax.vmap(init_one)(
                seeds,
                jnp.asarray(plan.time, jnp.int64),
                jnp.asarray(plan.kind, jnp.int32),
                jnp.asarray(plan.args, jnp.int32),
                jnp.asarray(plan.valid, jnp.bool_),
                jnp.asarray(pn, jnp.int32),
            )
        return jax.vmap(init_one)(seeds)

    return init


# ---------------------------------------------------------------------------
# step
# ---------------------------------------------------------------------------


@jax.custom_batching.custom_vmap
def _materialize(xs):
    """Identity barrier: force XLA to materialize ``xs`` here.

    Blocks producer fusion across the boundary —
    ``lax.optimization_barrier`` with the vmap rule the primitive
    itself lacks (the engine is always used under one ``jax.vmap``
    over seeds). The rank-placement path uses it to materialize the
    branch-selected emit rows ONCE: without it XLA fuses the
    lax.switch select chain into every per-slot placement pass and
    recomputes it per pool slot (measured 3.2 µs/seed-step in one
    fusion — a third of the raftlog step, PROFILE_CPU_r06)."""
    return lax.optimization_barrier(xs)


@_materialize.def_vmap
def _materialize_vmap(axis_size, in_batched, xs):
    del axis_size
    return lax.optimization_barrier(xs), in_batched[0]


def _trace_fold(trace, now, kind, node, args, pay=None):
    """Fold one dispatched event into the rolling trace hash (uint64)."""
    h = now.astype(jnp.uint64) * _TRACE_MIX
    h = h ^ (kind.astype(jnp.uint64) << jnp.uint64(32))
    h = h ^ (node.astype(jnp.uint64) << jnp.uint64(40))
    a = args.astype(jnp.uint32).astype(jnp.uint64)
    # missing high words were always emitted as zeros, so folding only
    # the declared args_words is value-identical to the 4-wide fold
    for j in range(args.shape[0]):
        h = h ^ (a[j] << jnp.uint64(8 * j))
    if pay is not None and pay.shape[0] > 0:
        # payload words participate in the trace so a payload divergence
        # between backends is caught; W=0 keeps pre-payload traces intact
        p = pay.astype(jnp.uint32).astype(jnp.uint64)
        idx = jnp.arange(p.shape[0], dtype=jnp.uint64)
        h = h ^ jnp.sum(p * (_TRACE_MIX ^ idx))
    return trace * _TRACE_PRIME + h


def make_step(wl: Workload, cfg: EngineConfig, build: Build = Build()):
    """Build the single-seed ``step(SimState) -> SimState`` function.

    Pops the earliest pending event, dispatches it through
    ``lax.switch`` (engine kinds + user handlers), applies chaos effects,
    and inserts emitted events. ``jax.vmap`` over the seed axis and
    ``lax.scan`` over steps give the batched run loop. ``build``
    (:class:`Build`) picks the lowering and the compiled axes; the
    state must come from an init built with the same spec.
    """
    b = build.resolve(wl, cfg)
    layout, placement, pool_index, time32 = (
        b.layout, b.placement, b.pool_index, b.time32
    )
    dup_rows, cov_words, cov_hitcount = b.dup_rows, b.cov_words, b.cov_hitcount
    metrics, timeline_cap, latency = b.metrics, b.timeline_cap, b.latency
    causal, retry = b.causal, b.retry
    n = wl.n_nodes
    k = wl.max_emits
    w = wl.payload_words
    aw = wl.args_words
    # history columns: capacity H and per-invocation record slots R
    # (both 0 when recording is off — the history block compiles away)
    hcap = wl.history.capacity if wl.history is not None else 0
    rr = wl.history.max_records if wl.history is not None else 0
    # latency tap: C op slots / P windows (both 0 when off) and the
    # per-invocation marker slots L (an Emits-shape constant like rr)
    ll = wl.lat_markers
    lat_c = latency.ops if latency is not None else 0
    lat_p = latency.phases if latency is not None else 0
    lat_phase_ns = latency.phase_ns if latency is not None else 1
    # numpy (not jnp) so they embed as literals
    init_rows = wl.initial_state()
    # durable columns survive kill/restart (FsSim power-fail analog);
    # static per workload, so the select compiles to a constant mask
    volatile = wl.volatile_mask()
    # two-phase sync discipline (durable_sync): durable columns survive
    # a KILL only up to the node's last committed sync — static per
    # workload, so the whole block compiles away when off
    sync_on = wl.durable_sync
    n_user = len(wl.handlers)
    # user purposes prefetched into the per-dispatch RNG block (static)
    user_purposes = tuple(int(p) for p in (wl.draw_purposes or ()))
    _check_meta_ranges(wl)
    rt_c = retry.n_ops if retry is not None else 0
    if rt_c:
        rt_boff, rt_bjit = _retry_backoff_tables(retry)
        rt_kind = np.int32(retry.kind)
        rt_node = np.int32(retry.node)
        rt_base = np.int32(retry.op_base)
        rt_max = np.int32(retry.max_attempts)
        rt_timeout = np.int64(retry.timeout_ns)
    dense = layout == "dense"
    p_tile = pool_tile(cfg.pool_size) if pool_index else 0
    n_tiles = cfg.pool_size // p_tile if p_tile else 0
    # rank-matched pool writes (scatter layout only; dense has its own
    # one-hot placement). Single-row appends (timeline ring, latency
    # clocks) stay .at[] stores either way — one serial row per step is
    # exactly the O(1) write a cold-bank append wants. Under the
    # readiness index, "rank" means the within-tile select-chain
    # variant: the full-pool passes never run.
    rank_place = (not dense) and placement == "rank"
    t_inf = _INF_32 if time32 else _INF_NS

    # -- user branch table -------------------------------------------------
    # Only USER handlers go through lax.switch; engine kinds (kill, clog,
    # halt, ...) are trivial functions of (kind, args) and are computed
    # inline below as masked selects — under vmap a switch evaluates
    # every branch and selects each output leaf, so ten extra engine
    # branches cost real per-step op count for no information.
    # lax.switch operands must be pytrees, so the context travels as a
    # tuple of arrays and each branch rebuilds the HandlerCtx view.
    def _unpack(op) -> HandlerCtx:
        now, node, state, args, src, k0, k1, stp, pay, eio, ul0, ul1 = op
        # prefetched user lanes -> the Draw cache (trace-time dict,
        # static purposes): a declared purpose's draw reads its lane of
        # the per-dispatch block instead of running a scalar cipher in
        # this branch — identical (seed, step, purpose) values
        cache = {
            PURPOSE_USER + p: (ul0[j], ul1[j])
            for j, p in enumerate(user_purposes)
        } or None
        return HandlerCtx(
            now=now,
            node=node,
            state=state,
            args=args,
            src=src,
            draw=Draw.from_parts(k0, k1, stp, cache),
            max_emits=k,
            payload=pay,
            payload_words=w,
            args_words=aw,
            max_records=rr,
            sync_err=eio,
            max_lat=ll,
        )

    def _user_branch(handler):
        def branch(op):
            ctx = _unpack(op)
            new_state, emits = handler(ctx)
            rv = emits.rec_valid
            if rv is None or (rr > 0 and rv.shape[0] == 0):
                # hand-built Emits (not via ctx.emits()): no history
                # records — normalize to the branch pytree shape so the
                # switch doesn't fail on a None/empty leaf
                emits = dataclasses.replace(
                    emits,
                    rec_valid=jnp.zeros((rr,), jnp.bool_),
                    rec=jnp.zeros((rr, 4), jnp.int32),
                )
            elif rv.shape[0] != rr:
                raise ValueError(
                    f"handler returned Emits with {rv.shape[0]} history-"
                    f"record rows but the workload's HistorySpec allows "
                    f"{rr}; build emits via ctx.emits() (EmitBuilder) to "
                    f"get the right row count"
                )
            if emits.sync is None:
                # hand-built Emits: no sync flag — normalize so the
                # switch branches share one pytree shape
                emits = dataclasses.replace(emits, sync=jnp.asarray(False))
            lv = emits.lat_valid
            if lv is None or (ll > 0 and lv.shape[0] == 0):
                # hand-built Emits: no latency markers — normalize to
                # the branch pytree shape (the rec rule again)
                emits = dataclasses.replace(
                    emits,
                    lat_valid=jnp.zeros((ll,), jnp.bool_),
                    lat=jnp.zeros((ll, 2), jnp.int32),
                )
            elif lv.shape[0] != ll:
                raise ValueError(
                    f"handler returned Emits with {lv.shape[0]} latency-"
                    f"marker rows but Workload.lat_markers={ll}; build "
                    f"emits via ctx.emits() (EmitBuilder) to get the "
                    f"right row count"
                )
            return jnp.asarray(new_state, jnp.int32), emits

        return branch

    user_branches = [_user_branch(h) for h in wl.handlers]

    loss_u32 = cfg.loss_u32
    time_limit = np.int64(cfg.time_limit_ns) if cfg.time_limit_ns else _INF_NS

    def step(st: SimState) -> SimState:
        # representation guard (trace-time): a state built or restored
        # under the other time representation would be silently
        # misread — e.g. a checkpoint saved where time32 auto-resolved
        # differently (engine/checkpoint.py). Dtypes are static, so
        # this costs nothing in the compiled program.
        expected_t = jnp.int32 if time32 else jnp.int64
        if st.ev_time.dtype != expected_t:
            raise TypeError(
                f"SimState.ev_time has dtype {st.ev_time.dtype} but this "
                f"step was built with time32={time32} (expects "
                f"{jnp.dtype(expected_t).name}); build init and step "
                f"from the same Build"
            )
        # pool-index shape guard (the same trace-time rule): an INDEXED
        # step popping a state without matching summaries would be
        # silently wrong, so it demands the exact tile count. The
        # off-step accepts anything — it rebuilds whatever summaries
        # the state carries (index-preserving, below), so a flat build
        # can always consume an indexed state.
        if pool_index and st.tile_cnt.shape[0] != n_tiles:
            raise TypeError(
                f"SimState carries {st.tile_cnt.shape[0]} pool-index "
                f"tiles but this step was built with {n_tiles}; build "
                f"init and step from the same Build"
            )
        # causal shape guard (the same trace-time rule): a causal step
        # folding zero-size clock columns would be silently wrong
        if causal and st.lam.shape[0] != n:
            raise TypeError(
                f"SimState.lam has shape {st.lam.shape} but this step "
                f"was built with causal=True (expects ({n},)); build "
                f"init and step from the same Build"
            )
        # ---- pop the earliest pending event (the timer-jump of
        # time/mod.rs:45-60 merged with the ready-queue drain) ----
        # Two value-identical lowerings of every per-event read/write
        # (see Build's ``layout``): dense = one-hot masked
        # reductions over the small E or N axis (per-seed dynamic
        # indexing lowers to batched gathers under vmap, ~1 ms/step on
        # TPU, examples/profile_step.py); scatter = plain indexing with
        # in_range masks so OOB handling matches dense and the oracle.
        e_slots = st.ev_valid.shape[0]
        if pool_index:
            # two-level pop: argmin over the E/T carried tile minima
            # (empty tiles masked to +inf — their stale minima never
            # compete), then argmin inside the ONE winning tile (a
            # single T-wide row gather from the reshaped pool). The
            # first tile achieving the global minimum contains the
            # globally first minimal slot, and argmin's first-match
            # tie-break inside it picks exactly that slot — identical
            # to the flat argmin over all E, at O(E/T + T).
            tmin = jnp.where(st.tile_cnt > 0, st.tile_min, t_inf)
            wtile = jnp.argmin(tmin).astype(jnp.int32)
            tv_row = st.ev_valid.reshape(n_tiles, p_tile)[wtile]
            tt_row = st.ev_time.reshape(n_tiles, p_tile)[wtile]
            li = jnp.argmin(
                jnp.where(tv_row, tt_row, t_inf)
            ).astype(jnp.int32)
            i = wtile * p_tile + li
        else:
            tmask = jnp.where(st.ev_valid, st.ev_time, t_inf)
            i = jnp.argmin(tmask)
        slot_ids = jnp.arange(e_slots, dtype=jnp.int32)
        is_popped = slot_ids == i.astype(jnp.int32)

        if dense:

            def pick_slot(arr):
                """arr (E, ...) -> arr[i] via the one-hot mask (exact)."""
                extra = arr.ndim - 1
                m = is_popped.reshape((-1,) + (1,) * extra)
                return jnp.sum(jnp.where(m, arr, 0), axis=0).astype(arr.dtype)

        else:

            def pick_slot(arr):
                return arr[i]

        if pool_index:
            # == ev_valid[i], read from the already-gathered tile row
            # instead of an O(E) masked any
            has_event = tv_row[li]
        else:
            has_event = jnp.any(st.ev_valid & is_popped)
        ev_time_i = pick_slot(st.ev_time)
        if time32:
            # offsets are relative to st.now; a (slightly) negative
            # offset is an event whose time the clock already passed by
            # a poll cost — identical to the absolute-form maximum
            ev_t = st.now + jnp.maximum(ev_time_i, 0).astype(jnp.int64)
        else:
            ev_t = jnp.maximum(st.now, ev_time_i)
        over_limit = ev_t > time_limit
        active = has_event & ~st.halted & ~over_limit

        meta_i = pick_slot(st.ev_meta)
        kind = _meta_kind(meta_i)
        dst = _meta_node(meta_i)
        src = _meta_src(meta_i)
        args = pick_slot(st.ev_args)
        ev_epoch_i = pick_slot(st.ev_epoch)
        pay_i = pick_slot(st.ev_pay)
        # emit-time sidecar (ring on): when THIS event entered the pool
        # — read before placement can reuse the freed slot
        emit_i = pick_slot(st.ev_emit) if timeline_cap else jnp.int64(0)
        if causal:
            # causal sidecar: the popped row's emitting-dispatch seq and
            # the clock that dispatch folded — the same read-before-
            # placement rule as emit_i
            parent_i = pick_slot(st.ev_parent)
            evlam_i = pick_slot(st.ev_lam)
        else:
            parent_i = jnp.int32(PARENT_NONE)
            evlam_i = jnp.uint32(0)
        # extended chaos kinds (>= FIRST_EXT_KIND) are engine kinds too:
        # dispatched inline, exempt from the epoch/pause gates
        is_engine = (kind < FIRST_USER_KIND) | (kind >= FIRST_EXT_KIND)
        is_msg = src >= 0

        node_ids = jnp.arange(n, dtype=jnp.int32)
        dst_oh = node_ids == dst  # (N,) one-hot; all-False for OOB dst
        if dense:
            state_row = jnp.sum(
                jnp.where(dst_oh[:, None], st.node_state, 0), axis=0
            ).astype(jnp.int32)
            alive_dst = jnp.any(st.alive & dst_oh)
            paused_dst = jnp.any(st.paused & dst_oh)
            epoch_dst = jnp.sum(jnp.where(dst_oh, st.epoch, 0)).astype(jnp.int32)
            skew_dst = jnp.sum(jnp.where(dst_oh, st.skew, 0)).astype(jnp.int32)
        else:
            # gather lowering. Gathers clamp out-of-range indices, which
            # would silently diverge from the dense form's no-match (and
            # the oracle); mask with in_range so an OOB dst reads as a
            # dead node with zero state in BOTH layouts
            in_range = (dst >= 0) & (dst < n)
            dst_c = jnp.clip(dst, 0, n - 1)
            state_row = jnp.where(in_range, st.node_state[dst_c], 0)
            alive_dst = st.alive[dst_c] & in_range
            paused_dst = st.paused[dst_c] & in_range
            epoch_dst = jnp.where(in_range, st.epoch[dst_c], 0)
            skew_dst = jnp.where(in_range, st.skew[dst_c], 0)
        # the handling node's observable fsync-EIO bit (ctx.sync_err):
        # pre-dispatch state, like every other ctx view. Constant False
        # without the sync discipline — the gate compiles away.
        if sync_on:
            if dense:
                eio_dst = jnp.any(st.sync_eio & dst_oh)
            else:
                eio_dst = st.sync_eio[dst_c] & in_range
        else:
            eio_dst = jnp.asarray(False)

        # liveness/epoch gate: user events to a dead or reincarnated node
        # are dropped — the kill-drops-futures semantics of task.rs:255-276.
        # Epoch -1 is the ANY-epoch sentinel client-army plan rows carry
        # (make_init): open-loop arrivals address whatever incarnation is
        # up, so only the liveness half gates them. No emitted event ever
        # carries -1 (emit epochs copy node epochs, which only grow), so
        # sentinel-free runs take the exact historical gate.
        live = alive_dst & (
            (epoch_dst == ev_epoch_i) | (ev_epoch_i == jnp.int32(-1))
        )
        # clogged links hold messages; re-check with exponential backoff
        # like the connection pump (net/mod.rs:341-355)
        if dense:
            src_oh = node_ids == jnp.maximum(src, 0)
            clogged = is_msg & jnp.any(
                st.clog & src_oh[:, None] & dst_oh[None, :]
            )
        else:
            clogged = is_msg & st.clog[jnp.maximum(src, 0), dst_c] & in_range
        # paused node: user events are stashed and retried, like the
        # executor stashing a paused node's ready tasks (task.rs:294-314)
        held = (~is_engine) & paused_dst
        blocked = clogged | held
        dispatch = active & ~blocked & (is_engine | live)

        # ---- client-retry decode (RetrySpec; rt_c=0 compiles the whole
        # mechanism away). An army row — the original offer or an armed
        # re-send timer — is a USER-kind dispatch of the policy's kind
        # at the army node whose token op id falls in the policy's op
        # range; the attempt id rides the token high bits (attempt-0
        # tokens are plain op ids, the off-policy bit-identity). A row
        # whose op already saw a response (rt_done) or that carries the
        # give-up sentinel attempt (== max_attempts) is SUPPRESSED: it
        # dispatches as a no-op — handler effects, emits and records
        # dropped; only the trace fold and the retry books see it.
        if rt_c:
            rt_tok = args[0]
            rt_idx = (rt_tok & jnp.int32(RETRY_OP_MASK)) - rt_base
            rt_att = (
                rt_tok >> jnp.int32(RETRY_ATTEMPT_SHIFT)
            ) & jnp.int32(RETRY_ATTEMPT_MAX)
            rt_in_r = (rt_idx >= 0) & (rt_idx < rt_c)
            is_army = (
                dispatch & ~is_engine & (kind == rt_kind)
                & (dst == rt_node) & rt_in_r
            )
            rt_ids = jnp.arange(rt_c, dtype=jnp.int32)
            rt_oh = rt_ids == rt_idx  # (CR,); all-False out of range
            rt_done_i = jnp.any(st.rt_done & rt_oh)
            rt_deliver = ~rt_done_i & (rt_att < rt_max)
            rt_suppress = is_army & ~rt_deliver
            rt_arm = is_army & rt_deliver
        else:
            rt_suppress = jnp.asarray(False)

        # ---- causal provenance fold (causal=True; derived state only,
        # the ev_emit discipline: everything below is read exclusively
        # into more causal columns / the ring, never the trajectory) ----
        if causal:
            # the dispatch's event-sequence number, int32 for the
            # sentinel classes: `step` is certified <= ABSINT_STEP_MAX
            # (= 2^31, one past int32), so the clamp makes the narrow
            # cast provably wrap-free — and a clamped seq can only occur
            # past the certified run length anyway
            seq_i32 = jnp.minimum(
                st.step, jnp.uint32(ABSINT_STEP_MAX - 1)
            ).astype(jnp.int32)
            if dense:
                lam_prev = jnp.sum(
                    jnp.where(dst_oh, st.lam, jnp.uint32(0))
                ).astype(jnp.uint32)
            else:
                lam_prev = jnp.where(
                    in_range, st.lam[dst_c], jnp.uint32(0)
                )
            # the Lamport fold: receive = max(own, sender's) + 1. An
            # undelivered step (no dispatch) folds nothing.
            lam_new = jnp.maximum(lam_prev, evlam_i) + jnp.uint32(1)
            if dense or rank_place:
                lam = jnp.where(
                    dst_oh & dispatch, lam_new, st.lam
                ).astype(jnp.uint32)
            else:
                # OOB dst writes nothing, the dropped-scatter rule
                lam = st.lam.at[
                    jnp.where(dispatch & in_range, dst_c, jnp.int32(n))
                ].set(lam_new, mode="drop")
        else:
            seq_i32 = jnp.int32(0)
            lam_prev = lam_new = jnp.uint32(0)
            lam = st.lam

        now = jnp.where(active, ev_t, st.now)
        draw = Draw(st.seed, st.step)
        # ---- per-dispatch batched RNG (the BatchRNG shape, PAPERS.md).
        # Every purpose one event-step can draw is enumerated as a
        # static lane vector and generated by ONE varying-counter
        # threefry pass (Draw.block2): lane 0 is the poll-cost/jitter
        # pair, lanes 1..k+1 the per-emit latency/loss pairs (+ the dup
        # shadow lanes, + the torn-prefix draw under the sync
        # discipline). Each lane keys the same (seed, step, purpose)
        # counter the retired per-use calls did, so every draw VALUE —
        # and therefore every trace and the C++ oracle compare — is
        # bit-identical; what changes is the cipher running as one
        # fused vector pass instead of per-use scalar invocations.
        n_em_lanes = (k + 1) + (k if dup_rows else 0)
        lane_p = [PURPOSE_POLL_COST]
        lane_p += [PURPOSE_LATENCY + s for s in range(k + 1)]
        if dup_rows:
            lane_p += [PURPOSE_DUP + s for s in range(k)]
        i_torn = len(lane_p)
        if sync_on:
            lane_p.append(PURPOSE_TORN)
        # retry backoff jitter: one fresh lane per dispatch (a re-send's
        # jitter is keyed by the ARMING step, seed-pure like everything)
        i_retry = len(lane_p)
        if rt_c:
            lane_p.append(PURPOSE_RETRY)
        # user lanes (Workload.draw_purposes): handler draws at these
        # purposes ride the same block; ctx.draw serves them from a
        # trace-time lane cache (rng.Draw.from_parts) so no branch
        # issues its own scalar cipher for a declared purpose
        i_user = len(lane_p)
        lane_p += [PURPOSE_USER + p for p in user_purposes]
        # stacked scalar literals (they inline into the jaxpr)
        lane0, lane1 = draw.block2(
            jnp.stack([jnp.uint32(p) for p in lane_p])
        )
        user_l0 = lane0[i_user:]
        user_l1 = lane1[i_user:]
        # per-event processing cost, 50-100 ns (task.rs:213), paired
        # with the clog-recheck jitter in ONE threefry block (lane 0 =
        # cost, lane 1 = jitter) — same bits2 pairing as latency/loss
        cost = draw._reduce(lane0[0], cfg.proc_min_ns, cfg.proc_max_ns)
        clog_jit = draw._reduce(lane1[0], 0, 1000)
        now_after = jnp.where(dispatch, now + cost, now)

        # ---- consume / reschedule the popped slot ----
        # dense: masked selects over the full pool (TPU lowers batched
        # scatter to a serial loop — it measured as 96% of step wall
        # time, examples/profile_step.py); scatter: .at[].set, the
        # faster CPU lowering. Same values either way.
        retries = _meta_retry(meta_i)
        shift = jnp.minimum(retries, jnp.int32(34)).astype(jnp.int64)
        backoff = jnp.minimum(
            jnp.int64(cfg.clog_backoff_min_ns) << shift,
            jnp.int64(cfg.clog_backoff_max_ns),
        )
        backoff = backoff + clog_jit
        resched = active & blocked & (is_engine | live)
        if time32:
            # rebase every offset by this step's clock advance so the
            # pool stays relative to the post-step clock. A reschedule
            # only happens when dispatch is false, so now_after == now
            # and the backoff offset needs no correction. Stale offsets
            # in invalid slots may wrap; they are masked at every use.
            adv32 = (now_after - st.now).astype(jnp.int32)
            # The two rebase subtractions below are the acknowledged
            # stale-slot wrap surface: a consumed slot's offset keeps
            # rebasing and may wrap int32 after ~2.1 sim-seconds —
            # masked at every use (ev_valid), and relationally bounded
            # for VALID slots (a valid offset is >= the popped minimum,
            # so it never drops below -proc_max), which a non-relational
            # interval domain cannot see. Certified instead by the
            # layout bit-identity pins (tests/test_engine.py).
            ev_time_reb = st.ev_time - adv32  # lint: allow(absint-overflow)
            back_t = backoff.astype(jnp.int32)
            old_t = ev_time_i - adv32  # lint: allow(absint-overflow)
        else:
            ev_time_reb = st.ev_time
            back_t = now + backoff
            old_t = ev_time_i
        # retry byte bump, saturating (shift caps at 34 so >=255 retries
        # behave identically); the other three meta bytes are unchanged
        meta_bumped = (meta_i & jnp.uint32(0x00FFFFFF)) | (
            jnp.minimum(retries + 1, 255).astype(jnp.uint32) << jnp.uint32(24)
        )
        if dense or (rank_place and not pool_index):
            # masked selects: the popped slot is consumed (or its
            # backoff rescheduled) by a fused vector pass — identical
            # values to the .at[i] store, no serial scatter
            ev_valid_mid = jnp.where(is_popped, resched, st.ev_valid)
            ev_time_mid = jnp.where(is_popped & resched, back_t, ev_time_reb)
            ev_meta_mid = jnp.where(is_popped & resched, meta_bumped, st.ev_meta)
        else:
            # O(1) element stores (under the readiness index too: one
            # serial row beats a full-pool select pass at army scale)
            ev_valid_mid = st.ev_valid.at[i].set(resched)
            ev_time_mid = ev_time_reb.at[i].set(
                jnp.where(resched, back_t, old_t)
            )
            ev_meta_mid = st.ev_meta.at[i].set(
                jnp.where(resched, meta_bumped, meta_i)
            )
        if pool_index:
            # index maintenance, part 1: rebase the carried minima with
            # the pool (time32 offsets shrink by the clock advance;
            # empty tiles' stale values may wrap — masked at every
            # use), and account the popped slot's consume/reschedule
            # into its tile's count. The popped tile's MIN is
            # recomputed exactly after placement (a consume can RAISE
            # it, which no incremental min update can express).
            # (same stale-wrap surface as the pool rebase above: empty
            # tiles' carried sentinels decay here and are re-masked to
            # +inf before any min-fold reads them — the PR-13 rule)
            tile_min_mid = (st.tile_min - adv32) if time32 else st.tile_min  # lint: allow(absint-overflow)
            tile_cnt_mid = st.tile_cnt.at[wtile].add(
                resched.astype(jnp.int32) - has_event.astype(jnp.int32)
            )

        # ---- dispatch: user handlers via lax.switch; engine kinds are
        # computed inline as masked selects (see the branch-table note) ----
        if n_user:
            user_idx = jnp.clip(kind - FIRST_USER_KIND, 0, n_user - 1)
            # the handler observes its NODE's clock: the true dispatch
            # time plus the node's chaos skew (KIND_SKEW). Zero skew adds
            # nothing, so non-chaos runs see the exact historical ctx.now.
            # The trace fold and history timestamps keep the unskewed
            # time (cross-node orderings stay exact).
            user_now = now + skew_dst.astype(jnp.int64)
            operand = (
                user_now, dst, state_row, args, src,
                draw.k0, draw.k1, draw.step, pay_i, eio_dst,
                user_l0, user_l1,
            )
            user_state, uem = lax.switch(user_idx, user_branches, operand)
        else:
            # chaos-only workload: no user branches to run
            user_state, uem = state_row, Emits.none(k, w, aw, rr, ll)
        user_dispatch = dispatch & ~is_engine
        if rt_c:
            # suppressed army rows are no-ops: the branch ran (a switch
            # always does) but none of its effects apply
            user_dispatch = user_dispatch & ~rt_suppress

        # ---- apply node-state update (an OOB dst matches no row in the
        # dense form, exactly the dropped-scatter semantics) ----
        row = jnp.where(user_dispatch, user_state, state_row)
        if dense or rank_place:
            # (an OOB dst has an all-False one-hot — the dropped-scatter
            # semantics as a select; N*U is small for every model, so
            # the fused pass beats a serial row store)
            node_state = jnp.where(dst_oh[:, None], row[None, :], st.node_state)
        else:
            # negative indices would wrap (numpy semantics); redirect OOB
            # to index n so mode="drop" discards it like dense's no-match
            node_state = st.node_state.at[
                jnp.where(in_range, dst_c, jnp.int32(n))
            ].set(row, mode="drop")

        # ---- engine effects: kill / restart / pause / clog / halt ----
        a0, a1 = args[0], args[1]
        kill_id = jnp.where(dispatch & (kind == KIND_KILL), a0, jnp.int32(-1))
        restart_id = jnp.where(dispatch & (kind == KIND_RESTART), a0, jnp.int32(-1))
        is_killed = node_ids == kill_id
        is_restarted = node_ids == restart_id
        alive = jnp.where(is_killed, False, st.alive)
        alive = jnp.where(is_restarted, True, alive)
        is_pause_kind = (kind == KIND_PAUSE) | (kind == KIND_RESUME)
        pause_id = jnp.where(dispatch & is_pause_kind, a0, jnp.int32(-1))
        paused = jnp.where(node_ids == pause_id, kind == KIND_PAUSE, st.paused)
        # kill/restart clears paused (fresh incarnation runs)
        paused = jnp.where(is_killed | is_restarted, False, paused)
        # epoch bumps invalidate every in-flight event targeting the node
        epoch = st.epoch + is_killed + is_restarted
        node_state = jnp.where(
            is_restarted[:, None] & volatile[None, :], init_rows, node_state
        )

        is_clog_kind = (kind >= KIND_CLOG) & (kind <= KIND_UNCLOG_NODE)
        clog_on = (kind == KIND_CLOG) | (kind == KIND_CLOG_NODE)
        clog_set = jnp.where(
            dispatch & is_clog_kind, clog_on.astype(jnp.int32), jnp.int32(-1)
        )
        is_node_clog = (kind == KIND_CLOG_NODE) | (kind == KIND_UNCLOG_NODE)
        clog_a = a0
        clog_b = jnp.where(is_node_clog, jnp.int32(-1), a1)
        src_ax = node_ids[:, None]
        dst_ax = node_ids[None, :]
        # clog_link(a, b) blocks both directions; clog_b < 0 means
        # clog_node(a): everything in or out of a (net/mod.rs:157-216)
        pair_sel = ((src_ax == clog_a) & (dst_ax == clog_b)) | (
            (src_ax == clog_b) & (dst_ax == clog_a)
        )
        node_sel = (clog_b < 0) & ((src_ax == clog_a) | (dst_ax == clog_a))
        sel = pair_sel | node_sel
        clog = jnp.where(
            sel & (clog_set == 1), True, jnp.where(sel & (clog_set == 0), False, st.clog)
        )
        # asymmetric partition edge (extended kinds): one direction only
        is_c1w = (kind == KIND_CLOG_1W) | (kind == KIND_UNCLOG_1W)
        c1w_set = jnp.where(
            dispatch & is_c1w,
            (kind == KIND_CLOG_1W).astype(jnp.int32),
            jnp.int32(-1),
        )
        sel_1w = (src_ax == a0) & (dst_ax == a1)
        clog = jnp.where(
            sel_1w & (c1w_set == 1),
            True,
            jnp.where(sel_1w & (c1w_set == 0), False, clog),
        )

        # ---- extended chaos effects: gray failure / duplication / skew.
        # Defaults (slow=1, dup off, skew=0) are identities, so these
        # selects change no value for workloads that never emit them.
        is_slow_kind = (kind == KIND_SLOW_LINK) | (kind == KIND_UNSLOW)
        slow_b = (a1 & jnp.int32(0xFF)) - 1  # packed peer; -1 = node-wide
        slow_mult = jnp.maximum(a1 >> jnp.int32(8), 1)
        slow_mult = jnp.where(kind == KIND_UNSLOW, jnp.int32(1), slow_mult)
        slow_set = jnp.where(
            dispatch & is_slow_kind, slow_mult, jnp.int32(-1)
        )
        pair_sl = ((src_ax == a0) & (dst_ax == slow_b)) | (
            (src_ax == slow_b) & (dst_ax == a0)
        )
        node_sl = (slow_b < 0) & ((src_ax == a0) | (dst_ax == a0))
        slow = jnp.where(
            (pair_sl | node_sl) & (slow_set > 0), slow_set, st.slow
        )
        is_dup_kind = (kind == KIND_DUP_ON) | (kind == KIND_DUP_OFF)
        dup = jnp.where(dispatch & is_dup_kind, kind == KIND_DUP_ON, st.dup)
        skew_id = jnp.where(dispatch & (kind == KIND_SKEW), a0, jnp.int32(-1))
        skew = jnp.where(node_ids == skew_id, a1, st.skew)

        # ---- two-phase sync discipline (Workload.durable_sync) ----
        # Durable writes buffer until an explicit sync commits them to
        # the node's disk image; a KILL reverts durable columns to that
        # image (plus, under an armed TORN mode, a threefry-drawn PREFIX
        # of the last uncommitted write — the FDB-style torn write).
        # Everything here is masked selects over (N,)/(N,U) arrays, the
        # same arithmetic in both layouts; with the discipline off the
        # arrays are zero-size and the block compiles away entirely.
        if sync_on:
            dur_m = jnp.asarray(~volatile)  # (U,) the durable-column mask
            # chaos windows (engine kinds 251-254): per-node flags,
            # args[0] = node, -1 = every node
            sel_n = (node_ids == a0) | (a0 < jnp.int32(0))
            # args[1] picks the window mode: 0 = silent lie (the
            # historical default, so pre-EIO plans are bit-identical),
            # 1 = observable EIO (ctx.sync_err). SYNC_OK ends both.
            eio_mode = a1 == jnp.int32(1)
            sl_on = dispatch & (kind == KIND_SYNC_LOSS) & ~eio_mode
            ei_on = dispatch & (kind == KIND_SYNC_LOSS) & eio_mode
            sl_off = dispatch & (kind == KIND_SYNC_OK)
            sync_loss = jnp.where(
                sl_on & sel_n, True,
                jnp.where(sl_off & sel_n, False, st.sync_loss),
            )
            sync_eio = jnp.where(
                ei_on & sel_n, True,
                jnp.where(sl_off & sel_n, False, st.sync_eio),
            )
            tn_on = dispatch & (kind == KIND_TORN_ON)
            tn_off = dispatch & (kind == KIND_TORN_OFF)
            torn = jnp.where(
                tn_on & sel_n, True,
                jnp.where(tn_off & sel_n, False, st.torn),
            )
            # the LAST durable write: this dispatch's changed durable
            # columns REPLACE the node's mask (earlier unsynced writes
            # are wholly lost on a crash; only the newest one tears)
            changed = (row != state_row) & dur_m  # (U,)
            wrote = user_dispatch & jnp.any(changed)
            wmask = jnp.where(
                (dst_oh & wrote)[:, None], changed[None, :], st.wmask
            )
            # sync commit: honored unless the node's disk is lying or
            # failing (EIO). Either failure is total — no commit, no
            # wmask clear: the write stays uncommitted and the next
            # kill still loses/tears it. The difference is upstream:
            # an EIO window also showed the handler ctx.sync_err.
            failing = sync_loss | sync_eio
            if dense:
                lying = jnp.any(failing & dst_oh)
            else:
                lying = failing[dst_c] & in_range
            do_sync = user_dispatch & uem.sync & ~lying
            sync_lied = user_dispatch & uem.sync & lying
            commit_sel = (dst_oh & do_sync)[:, None] & dur_m[None, :]
            disk = jnp.where(commit_sel, node_state, st.disk)
            wmask = jnp.where((dst_oh & do_sync)[:, None], False, wmask)
            # crash: durable columns revert to the synced image; an
            # armed torn mode persists rank < keep_cnt columns (column
            # order) of the last uncommitted write on top of it
            torn_bits = lane0[i_torn]  # the PURPOSE_TORN lane of the block
            n_dirty = jnp.sum(wmask.astype(jnp.int32), axis=1)  # (N,)
            rank = jnp.cumsum(wmask.astype(jnp.int32), axis=1) - 1
            keep_cnt = (
                torn_bits % (n_dirty + 1).astype(jnp.uint32)
            ).astype(jnp.int32)
            torn_keep = wmask & torn[:, None] & (rank < keep_cnt[:, None])
            crash_val = jnp.where(torn_keep, node_state, disk)
            crash_sel = is_killed[:, None] & dur_m[None, :]
            tore = jnp.any(is_killed & torn)
            node_state = jnp.where(crash_sel, crash_val, node_state)
            disk = jnp.where(crash_sel, crash_val, disk)
            wmask = jnp.where(is_killed[:, None], False, wmask)
        else:
            disk, wmask = st.disk, st.wmask
            sync_loss, sync_eio, torn = st.sync_loss, st.sync_eio, st.torn
            do_sync = sync_lied = tore = jnp.asarray(False)

        halted = st.halted | (dispatch & (kind == KIND_HALT)) | (has_event & over_limit)
        halt_time = jnp.where(
            (halted & ~st.halted), jnp.minimum(now, time_limit), st.halt_time
        )

        # ---- translate emits into pool insertions ----
        # user emits are suppressed for engine kinds (the clamped switch
        # ran *some* user branch); the reborn node's re-init event
        # (task.rs:279-291) rides an appended timer row — timers never
        # read their slot's latency/loss draws, so the extra slot is
        # trace-neutral
        restart_row = kind == KIND_RESTART
        # user emit rows are also dropped for suppressed army rows (the
        # retry no-op rule); without a policy this is exactly ~is_engine
        user_row_ok = (~is_engine & ~rt_suppress) if rt_c else ~is_engine
        em = Emits(
            valid=jnp.concatenate([uem.valid & user_row_ok, restart_row[None]]),
            send=jnp.concatenate([uem.send, jnp.zeros((1,), jnp.bool_)]),
            kind=jnp.concatenate(
                [uem.kind, jnp.full((1,), FIRST_USER_KIND, jnp.int32)]
            ),
            dst=jnp.concatenate([uem.dst, a0[None]]),
            delay=jnp.concatenate([uem.delay, jnp.zeros((1,), jnp.int64)]),
            args=jnp.concatenate([uem.args, jnp.zeros((1, aw), jnp.int32)]),
            pay=jnp.concatenate([uem.pay, jnp.zeros((1, w), jnp.int32)]),
            rec_valid=uem.rec_valid,  # records never ride the restart row
            rec=uem.rec,
        )
        # one threefry block per emit slot: lane 0 = latency, lane 1 =
        # loss — the emit slices of the per-dispatch batched block
        # (Draw.block2 above), bit-identical to the retired per-slot
        # vmapped cipher. Under dup_rows, K shadow rows follow the
        # restart row: copies of the user send slots, valid only while
        # the seed's dup flag is on, drawing an INDEPENDENT latency/loss
        # pair at the PURPOSE_DUP+slot lane — the duplicated delivery
        # arrives at its own time and is lost on its own coin, exactly
        # like a real duplicate in flight.
        if dup_rows:
            dvalid = uem.valid & user_row_ok & uem.send & st.dup
            em = Emits(
                valid=jnp.concatenate([em.valid, dvalid]),
                send=jnp.concatenate([em.send, uem.send]),
                kind=jnp.concatenate([em.kind, uem.kind]),
                dst=jnp.concatenate([em.dst, uem.dst]),
                delay=jnp.concatenate([em.delay, uem.delay]),
                args=jnp.concatenate([em.args, uem.args]),
                pay=jnp.concatenate([em.pay, uem.pay]),
                rec_valid=em.rec_valid,
                rec=em.rec,
            )
        lat_bits = lane0[1 : 1 + n_em_lanes]
        loss_bits = lane1[1 : 1 + n_em_lanes]
        if rt_c:
            # the armed re-send: ONE timer row per delivered army
            # attempt, appended last — the next attempt's token at
            # now + timeout + backoff + jitter, addressed to the army
            # node on the army kind. A timer (send=False) never reads
            # its latency/loss lane, draws no loss coin and rides the
            # standard epoch copy (a pending retry dies with its client
            # incarnation, exactly like any other timer). Backoff is an
            # unrolled table select — no gathers, layout-identical.
            rt_next = rt_att + jnp.int32(1)
            rt_boff_t = jnp.int64(0)
            rt_bjit_t = jnp.int64(0)
            for a in range(1, int(rt_max) + 1):
                rt_boff_t = jnp.where(
                    rt_next == a, jnp.int64(rt_boff[a]), rt_boff_t
                )
                rt_bjit_t = jnp.where(
                    rt_next == a, jnp.int64(rt_bjit[a]), rt_bjit_t
                )
            # jitter scales the capped max addend by a uint32 draw:
            # (cap * draw) >> 32 is exact integer arithmetic inside
            # int64 (the _RETRY_BACKOFF_CAP bound)
            rt_jit = (
                rt_bjit_t * lane0[i_retry].astype(jnp.int64)
            ) >> jnp.int64(32)
            rt_delay = jnp.int64(rt_timeout) + rt_boff_t + rt_jit
            rt_new_tok = (rt_tok & jnp.int32(RETRY_OP_MASK)) | (
                rt_next << jnp.int32(RETRY_ATTEMPT_SHIFT)
            )
            rt_args_row = jnp.where(
                jnp.arange(aw, dtype=jnp.int32) == 0, rt_new_tok, args
            )
            em = Emits(
                valid=jnp.concatenate([em.valid, rt_arm[None]]),
                send=jnp.concatenate([em.send, jnp.zeros((1,), jnp.bool_)]),
                kind=jnp.concatenate(
                    [em.kind, jnp.full((1,), int(rt_kind), jnp.int32)]
                ),
                dst=jnp.concatenate(
                    [em.dst, jnp.full((1,), int(rt_node), jnp.int32)]
                ),
                delay=jnp.concatenate([em.delay, rt_delay[None]]),
                args=jnp.concatenate([em.args, rt_args_row[None, :]]),
                pay=jnp.concatenate([em.pay, jnp.zeros((1, w), jnp.int32)]),
                rec_valid=em.rec_valid,
                rec=em.rec,
            )
            # keep the row/lane axes aligned: the timer row's lane is
            # never read (send=False), a zero entry suffices
            rt_zlane = jnp.zeros((1,), jnp.uint32)
            lat_bits = jnp.concatenate([lat_bits, rt_zlane])
            loss_bits = jnp.concatenate([loss_bits, rt_zlane])
        span = jnp.uint32(max(cfg.lat_max_ns - cfg.lat_min_ns, 1))
        if time32:  # same value, native width (lat_max fits by eligibility)
            latency = jnp.int32(cfg.lat_min_ns) + (lat_bits % span).astype(jnp.int32)
        else:
            latency = jnp.int64(cfg.lat_min_ns) + (lat_bits % span).astype(jnp.int64)
        # loss_u32 == 2^32 is the static always-drop path (loss_p=1.0);
        # a uint32 compare can't express it (chance_threshold contract)
        if loss_u32 >= (1 << 32):
            lost = em.send
        else:
            lost = em.send & (loss_bits < jnp.uint32(loss_u32))

        e_valid = dispatch & em.valid & ~lost
        if time32:
            # runtime backstop for the declared delay_bound_ns: a timer
            # past the int32 horizon would corrupt the offset form, so
            # it is clamped (to the max offset eligibility allows — the
            # sentinel/rebase headroom) and counted as an overflow
            # (loud — bench refuses any run with a nonzero overflow)
            lim32 = _T32_LIMIT - cfg.proc_max_ns - 1
            delay_over = e_valid & ~em.send & (em.delay > jnp.int64(lim32))
            n_delay_over = jnp.sum(delay_over).astype(jnp.int32)
            delay_t = jnp.minimum(em.delay, jnp.int64(lim32)).astype(
                jnp.int32
            )
        else:
            n_delay_over = jnp.int32(0)
            delay_t = em.delay
        # sends to dead nodes are dropped at send time (socket gone,
        # network.rs:311-313); timers to dead nodes die via the epoch gate
        if dense:
            emit_dst_oh = em.dst[:, None] == node_ids[None, :]  # (K+1, N)
            alive_at_dst = jnp.any(alive[None, :] & emit_dst_oh, axis=1)
            e_epoch = jnp.sum(
                jnp.where(emit_dst_oh, epoch[None, :], 0), axis=1
            ).astype(jnp.int32)
        else:
            em_in_range = (em.dst >= 0) & (em.dst < n)
            em_dst_c = jnp.clip(em.dst, 0, n - 1)
            alive_at_dst = alive[em_dst_c] & em_in_range
            e_epoch = jnp.where(em_in_range, epoch[em_dst_c], 0)
        e_valid = e_valid & jnp.where(em.send, alive_at_dst, True)
        # gray-failure latency multiplier: each send's latency scales by
        # slow[sender, dst] (post-effect, like the alive gate). mult==1
        # takes the untouched draw, so plan-free traces are unchanged.
        if dense:
            sender_slow = jnp.sum(
                jnp.where(dst_oh[:, None], slow, 0), axis=0
            ).astype(jnp.int32)  # (N,) the dispatching node's slow row
            emit_mult = jnp.sum(
                jnp.where(emit_dst_oh, sender_slow[None, :], 0), axis=1
            ).astype(jnp.int32)
        else:
            emit_mult = jnp.where(
                in_range & em_in_range, slow[dst_c, em_dst_c], 1
            )
        emit_mult = jnp.maximum(emit_mult, 1)
        lat_scaled = latency.astype(jnp.int64) * emit_mult.astype(jnp.int64)
        if time32:
            # clamp to the offset horizon (the delay-over rule applied to
            # latency): a pathological multiplier saturates loudly-visibly
            # late rather than corrupting the int32 offset form
            lat_scaled = jnp.minimum(
                lat_scaled, jnp.int64(_T32_LIMIT - cfg.proc_max_ns - 1)
            ).astype(jnp.int32)
        latency = jnp.where(emit_mult > 1, lat_scaled.astype(latency.dtype), latency)
        if time32:
            # offsets are relative to the post-step clock, which is
            # exactly now_after — no addition needed at all
            e_time = jnp.where(em.send, latency, delay_t)
        else:
            e_time = now_after + jnp.where(em.send, latency, delay_t)
        e_src = jnp.where(em.send, dst, jnp.int32(-1))
        # engine-kind events bypass the epoch gate; keep their slot epoch 0
        e_epoch = jnp.where(
            (em.kind < FIRST_USER_KIND) | (em.kind >= FIRST_EXT_KIND),
            0,
            e_epoch,
        )
        # pack the four small fields into the meta word (layout at top of
        # file); kind/dst clip to the byte ranges — out-of-range values
        # already matched nothing downstream, and clipping keeps them
        # matching nothing
        # negative kinds were engine kinds matching no KIND_* constant
        # (a no-op); map them to KIND_NOP, the in-byte value with that
        # exact behavior. Kinds > 255 already dispatched the clamped
        # last user handler and still do at 255 (the handler-count
        # guard keeps 255 above every valid kind). For these two
        # out-of-contract inputs only, the *trace* records the mapped
        # kind rather than the raw one
        e_meta = _meta_pack(
            jnp.where(em.kind < 0, KIND_NOP, jnp.minimum(em.kind, 255)),
            jnp.clip(em.dst, -1, n) + 1,
            jnp.clip(e_src, -1, n) + 1,
            jnp.zeros((em.kind.shape[0],), jnp.int32),
        )

        # compact placement: the j-th *valid* emit takes the j-th free
        # slot (pool order), so sparse emit patterns (gated `when` rows)
        # don't waste slots and only a genuinely full pool drops events.
        pos = jnp.cumsum(e_valid.astype(jnp.int32)) - 1
        msg_count = st.msg_count + jnp.sum(
            dispatch & em.valid & em.send
        ).astype(jnp.int64)
        # user slots + the restart row (+ the dup shadow rows when compiled)
        k1 = int(em.valid.shape[0])

        if dense:
            # slot j's rank among free slots must equal the emit's rank
            # among valid emits — an (E, K+1) match instead of a
            # flatnonzero + scatter (see the scatter note above)
            free_rank = jnp.cumsum(~ev_valid_mid) - 1
            n_free = jnp.sum((~ev_valid_mid).astype(jnp.int32))
            dropped = e_valid & (pos >= n_free)
            overflow = st.overflow + jnp.sum(dropped).astype(jnp.int32) + n_delay_over

            match = (
                (~ev_valid_mid)[:, None]
                & e_valid[None, :]
                & (free_rank[:, None] == pos[None, :])
            )  # (E, K+1); at most one emit matches any slot
            match_any = jnp.any(match, axis=1)

            def place(vals, mid):
                """Write each matched emit's value into its slot."""
                extra = vals.ndim - 1
                m = match.reshape(match.shape + (1,) * extra)
                picked = jnp.sum(
                    jnp.where(m, vals[None], 0), axis=1
                ).astype(vals.dtype)
                keep = match_any.reshape((-1,) + (1,) * extra)
                return jnp.where(keep, picked, mid)

            ev_valid = ev_valid_mid | match_any
            ev_time = place(e_time, ev_time_mid)
            ev_meta = place(e_meta, ev_meta_mid)
            ev_epoch = place(e_epoch, st.ev_epoch)
            ev_args = place(em.args, st.ev_args)
            ev_pay = place(em.pay, st.ev_pay)
            if timeline_cap:
                # every inserted event was emitted at this dispatch's
                # clock; rescheduled (clog-held) rows keep their
                # original emit time — a retry is not a new send
                ev_emit = place(
                    jnp.broadcast_to(now, (k1,)), st.ev_emit
                )
            else:
                ev_emit = st.ev_emit
            if causal:
                # every inserted event's parent is THIS dispatch; a
                # rescheduled row keeps its original parent (a retry is
                # not a new derivation — the emit-time rule again)
                ev_parent = place(
                    jnp.broadcast_to(seq_i32, (k1,)), st.ev_parent
                )
                ev_lam = place(
                    jnp.broadcast_to(lam_new, (k1,)), st.ev_lam
                )
            else:
                ev_parent, ev_lam = st.ev_parent, st.ev_lam
        elif rank_place and not pool_index:
            # rank-matched vector placement: the free slots are the
            # ready-to-receive partition of the pool, ranked in slot
            # order by one cumsum; the j-th valid emit pairs with the
            # j-th free slot exactly like the scatter store and the
            # dense match matrix. Each pool column then updates through
            # a statically-unrolled chain of masked selects — one
            # branchless compare+select per emit row, fused by XLA into
            # a single vector pass per column. No scatters (XLA CPU
            # lowers batched scatter to a serial per-row loop) and no
            # gathers (a batched gather is nearly as serial — the
            # gather-based first cut of this path measured 2.7 µs per
            # seed-step in ONE fusion, half the whole step wall,
            # PROFILE_CPU_r06): not-yet-due rows stream through the
            # selects untouched.
            free_rank = jnp.cumsum((~ev_valid_mid).astype(jnp.int32)) - 1
            n_free = free_rank[-1] + 1
            n_valid_em = jnp.sum(e_valid.astype(jnp.int32))
            dropped = e_valid & (pos >= n_free)
            overflow = st.overflow + jnp.sum(dropped).astype(jnp.int32) + n_delay_over
            place_free = ~ev_valid_mid
            take = place_free & (free_rank < n_valid_em)
            # Materialize the emit rows ONCE before the per-slot select
            # chains (see _materialize: XLA would otherwise recompute
            # the branch select per pool slot). Identity on values.
            e_time, e_meta, e_epoch, em_args_m, em_pay_m = _materialize(
                (e_time, e_meta, e_epoch, em.args, em.pay)
            )
            # slot e takes emit j iff j is valid and e is the free slot
            # whose rank equals j's emit rank — at most one j matches
            sel_rows = [
                place_free & e_valid[j] & (free_rank == pos[j])
                for j in range(k1)
            ]

            def rplace(vals, keep):
                """Each ready slot takes its rank-matched emit's value."""
                extra = vals.ndim - 1
                acc = keep
                for j in range(k1):
                    s = sel_rows[j].reshape((-1,) + (1,) * extra)
                    acc = jnp.where(s, vals[j], acc)
                return acc.astype(keep.dtype)

            ev_valid = ev_valid_mid | take
            ev_time = rplace(e_time, ev_time_mid)
            ev_meta = rplace(e_meta, ev_meta_mid)
            ev_epoch = rplace(e_epoch, st.ev_epoch)
            ev_args = rplace(em_args_m, st.ev_args)
            ev_pay = rplace(em_pay_m, st.ev_pay)
            if timeline_cap:
                # all emit rows share this dispatch's clock (the rule
                # in the dense branch above) — a plain masked select
                ev_emit = jnp.where(take, now, st.ev_emit)
            else:
                ev_emit = st.ev_emit
            if causal:
                # all emit rows share this dispatch's seq + clock (the
                # dense-branch rule) — plain masked selects
                ev_parent = jnp.where(take, seq_i32, st.ev_parent)
                ev_lam = jnp.where(take, lam_new, st.ev_lam)
            else:
                ev_parent, ev_lam = st.ev_parent, st.ev_lam
        else:
            if pool_index:
                # readiness-index free search, O(E/T + T + emits): the
                # j-th valid emit still takes the j-th free slot in
                # pool order (the flatnonzero contract, bit-for-bit) —
                # but the rank is resolved through the carried per-tile
                # counts: a cumsum over E/T free counts locates each
                # emit's target TILE (searchsorted over the exclusive
                # ranks), and one (k1, T) row gather + rank match finds
                # the slot inside it. No O(E) flatnonzero pass.
                free_tiles = jnp.int32(p_tile) - tile_cnt_mid
                cum_incl = jnp.cumsum(free_tiles)
                n_free = cum_incl[n_tiles - 1]
                cum_excl = cum_incl - free_tiles
                dropped = e_valid & (pos >= n_free)
                overflow = (
                    st.overflow + jnp.sum(dropped).astype(jnp.int32)
                    + n_delay_over
                )
                placed = e_valid & ~dropped
                # tile of the pos[j]-th free slot: the last tile whose
                # exclusive cumulative free count is <= pos[j]
                tj = jnp.clip(
                    jnp.searchsorted(cum_excl, pos, side="right").astype(
                        jnp.int32
                    )
                    - 1,
                    0,
                    n_tiles - 1,
                )
                loc_rank = pos - cum_excl[tj]
                fv_rows = (~ev_valid_mid).reshape(n_tiles, p_tile)[tj]
                frank = jnp.cumsum(fv_rows.astype(jnp.int32), axis=1) - 1
                # distinct emits have distinct global ranks, so their
                # (tile, local-rank) pairs are distinct — the match
                # one-hots are disjoint and need no sequential chain
                match = fv_rows & (frank == loc_rank[:, None]) & placed[:, None]
                lj = jnp.sum(
                    jnp.where(
                        match,
                        jnp.arange(p_tile, dtype=jnp.int32)[None, :],
                        0,
                    ),
                    axis=1,
                )
                slot = jnp.where(
                    placed, tj * p_tile + lj, jnp.int32(e_slots)
                )
            else:
                free = jnp.flatnonzero(
                    ~ev_valid_mid, size=k1, fill_value=e_slots
                )
                slot = jnp.where(
                    e_valid, free[jnp.clip(pos, 0, k1 - 1)], jnp.int32(e_slots)
                )
                dropped = e_valid & (slot >= e_slots)
                overflow = (
                    st.overflow + jnp.sum(dropped).astype(jnp.int32)
                    + n_delay_over
                )
            if pool_index and rank_place:
                # the within-tile select-chain write lowering (the
                # PR-8 rank placement confined to each emit's target
                # tile): per emit, gather the T-wide tile row, select
                # the matched slot branchlessly, store the row back.
                # Scatter-free in the ELEMENT sense but still one
                # dynamic row store per emit per column — an
                # interleaved CPU A/B measured it against the element
                # stores below; element stores won on CPU, so
                # "scatter" is the default under the index.
                emt, emm, eme, ema, emp = _materialize(
                    (e_time, e_meta, e_epoch, em.args, em.pay)
                )
                v2 = ev_valid_mid.reshape(n_tiles, p_tile)
                t2 = ev_time_mid.reshape(n_tiles, p_tile)
                m2 = ev_meta_mid.reshape(n_tiles, p_tile)
                ep2 = st.ev_epoch.reshape(n_tiles, p_tile)
                a2 = st.ev_args.reshape(n_tiles, p_tile, aw)
                p2 = st.ev_pay.reshape(n_tiles, p_tile, w)
                e2 = (
                    st.ev_emit.reshape(n_tiles, p_tile)
                    if timeline_cap else None
                )
                pa2 = (
                    st.ev_parent.reshape(n_tiles, p_tile)
                    if causal else None
                )
                pl2 = (
                    st.ev_lam.reshape(n_tiles, p_tile)
                    if causal else None
                )
                for j in range(k1):

                    def upd(arr2, val, _s=match[j], _t=tj[j]):
                        row = arr2[_t]
                        m = _s.reshape((p_tile,) + (1,) * (row.ndim - 1))
                        return arr2.at[_t].set(
                            jnp.where(m, val, row).astype(arr2.dtype)
                        )

                    v2 = upd(v2, True)
                    t2 = upd(t2, emt[j])
                    m2 = upd(m2, emm[j])
                    ep2 = upd(ep2, eme[j])
                    a2 = upd(a2, ema[j])
                    p2 = upd(p2, emp[j])
                    if timeline_cap:
                        e2 = upd(e2, now)
                    if causal:
                        pa2 = upd(pa2, seq_i32)
                        pl2 = upd(pl2, lam_new)
                ev_valid = v2.reshape(e_slots)
                ev_time = t2.reshape(e_slots)
                ev_meta = m2.reshape(e_slots)
                ev_epoch = ep2.reshape(e_slots)
                ev_args = a2.reshape(e_slots, aw)
                ev_pay = p2.reshape(e_slots, w)
                ev_emit = (
                    e2.reshape(e_slots) if timeline_cap else st.ev_emit
                )
                if causal:
                    ev_parent = pa2.reshape(e_slots)
                    ev_lam = pl2.reshape(e_slots)
                else:
                    ev_parent, ev_lam = st.ev_parent, st.ev_lam
            else:
                ev_valid = ev_valid_mid.at[slot].set(e_valid, mode="drop")
                ev_time = ev_time_mid.at[slot].set(e_time, mode="drop")
                ev_meta = ev_meta_mid.at[slot].set(e_meta, mode="drop")
                ev_epoch = st.ev_epoch.at[slot].set(e_epoch, mode="drop")
                ev_args = st.ev_args.at[slot].set(em.args, mode="drop")
                ev_pay = st.ev_pay.at[slot].set(em.pay, mode="drop")
                if timeline_cap:
                    ev_emit = st.ev_emit.at[slot].set(
                        jnp.broadcast_to(now, (k1,)), mode="drop"
                    )
                else:
                    ev_emit = st.ev_emit
                if causal:
                    ev_parent = st.ev_parent.at[slot].set(
                        jnp.broadcast_to(seq_i32, (k1,)), mode="drop"
                    )
                    ev_lam = st.ev_lam.at[slot].set(
                        jnp.broadcast_to(lam_new, (k1,)), mode="drop"
                    )
                else:
                    ev_parent, ev_lam = st.ev_parent, st.ev_lam
            if pool_index:
                # index maintenance, part 2: fold the insertions into
                # their tiles' summaries (<= k1 scatter-min/add rows),
                # then recompute the popped tile EXACTLY from the
                # final pool rows: the consume can RAISE its minimum,
                # which no incremental min can express, and the
                # .at[wtile].set override also covers any insertion
                # that landed there (set runs after the fold).
                ins_tile = jnp.where(placed, tj, jnp.int32(n_tiles))
                tile_cnt2 = tile_cnt_mid.at[ins_tile].add(1, mode="drop")
                # mask EMPTY tiles back to the +inf sentinel before
                # folding inserts: under time32 the per-step rebase
                # decays every carried value — including the sentinel
                # of a tile that has sat empty — so after ~2.1 sim
                # seconds an unmasked min() against it would pin a
                # freshly filled tile's minimum below its true value
                # and silently pop the wrong event. The pop masks by
                # tile_cnt at ITS use; this is the other use and needs
                # the same mask (tests/test_pool_index.py
                # test_time32_empty_tile_sentinel_decay is the repro).
                tile_min2 = jnp.where(
                    tile_cnt_mid > 0, tile_min_mid, t_inf
                ).at[ins_tile].min(e_time, mode="drop")
                fin_v = ev_valid.reshape(n_tiles, p_tile)[wtile]
                fin_t = ev_time.reshape(n_tiles, p_tile)[wtile]
                tile_min_out = tile_min2.at[wtile].set(
                    jnp.min(jnp.where(fin_v, fin_t, t_inf))
                )
                tile_cnt_out = tile_cnt2.at[wtile].set(
                    jnp.sum(fin_v.astype(jnp.int32))
                )

        if not pool_index:
            n_tiles_in = st.tile_cnt.shape[0]
            if n_tiles_in:
                # index-preserving off-step: this build does not USE
                # the index, but the state carries summaries (e.g. an
                # auto-indexed CPU init feeding a forced dense run, or
                # an indexed checkpoint resumed flat) — rebuild them
                # exactly from the final pool so they can never go
                # stale and poison a later indexed step. One fused
                # O(E) reduce, the same cost class as the flat pop
                # this build already pays.
                tile_min_out, tile_cnt_out = build_pool_index(
                    ev_time, ev_valid, e_slots // n_tiles_in
                )
            else:
                tile_min_out, tile_cnt_out = st.tile_min, st.tile_cnt

        # ---- operation-history append (madsim_tpu.check) ----
        # the j-th valid record takes slot hist_count+j: same compact
        # cumsum placement as the event pool, same dense/scatter duality
        # (values identical either way), no RNG draws — so traces and
        # every existing workload are byte-identical with recording off.
        # A full buffer drops records LOUDLY: hist_drop is the visible
        # overflow flag the checkers (and search_seeds) refuse.
        if hcap > 0:
            r_valid = user_dispatch & uem.rec_valid
            rpos = st.hist_count + jnp.cumsum(r_valid.astype(jnp.int32)) - 1
            fits = rpos < hcap
            keep = r_valid & fits
            # row layout [op, key, arg, client, ok]: client = the node
            # whose handler recorded it, time = the dispatch clock
            rec_client = jnp.broadcast_to(dst, (rr,)).astype(jnp.int32)
            rec_row = jnp.concatenate(
                [uem.rec[:, :3], rec_client[:, None], uem.rec[:, 3:4]],
                axis=1,
            )
            rec_t = jnp.broadcast_to(now, (rr,))
            if dense:
                hist_ids = jnp.arange(hcap, dtype=jnp.int32)
                hmatch = keep[None, :] & (hist_ids[:, None] == rpos[None, :])
                hany = jnp.any(hmatch, axis=1)
                picked = jnp.sum(
                    jnp.where(hmatch[:, :, None], rec_row[None], 0), axis=1
                ).astype(jnp.int32)
                hist_word = jnp.where(hany[:, None], picked, st.hist_word)
                picked_t = jnp.sum(jnp.where(hmatch, rec_t[None], 0), axis=1)
                hist_t = jnp.where(hany, picked_t, st.hist_t)
            elif rank_place:
                # rank-matched cold-bank append: slot hist_count + r
                # takes the r-th KEPT record (drops are a suffix —
                # rpos is nondecreasing, so `fits` is a prefix
                # property and kept ranks stay contiguous). Same
                # unrolled select-chain form as the pool placement —
                # no scatter, no gather.
                n_keep = jnp.sum(keep).astype(jnp.int32)
                rel = jnp.cumsum(r_valid.astype(jnp.int32)) - 1
                hranks = jnp.arange(hcap, dtype=jnp.int32) - st.hist_count
                hist_word = st.hist_word
                for j in range(rr):
                    sel_h = keep[j] & (hranks == rel[j])
                    hist_word = jnp.where(
                        sel_h[:, None], rec_row[j], hist_word
                    )
                take_h = (hranks >= 0) & (hranks < n_keep)
                hist_t = jnp.where(take_h, now, st.hist_t)
            else:
                hslot = jnp.where(keep, rpos, jnp.int32(hcap))
                hist_word = st.hist_word.at[hslot].set(rec_row, mode="drop")
                hist_t = st.hist_t.at[hslot].set(rec_t, mode="drop")
            hist_count = st.hist_count + jnp.sum(keep).astype(jnp.int32)
            hist_drop = st.hist_drop + jnp.sum(r_valid & ~fits).astype(
                jnp.int32
            )
        else:
            hist_count, hist_drop = st.hist_count, st.hist_drop
            hist_word, hist_t = st.hist_word, st.hist_t

        # ---- tail-latency tap (madsim_tpu.obs latency) ----
        # derived state only, the cov_words discipline: handler markers
        # stamp per-op invoke/response clocks and fold completed ops
        # into the per-seed log-linear sketch. Marker slots are few
        # (L ~= 1-2), so each is handled by its own masked write — a
        # static unroll, the same arithmetic in both layouts. Nothing
        # here is ever read back by the trajectory, the RNG or the
        # trace, so latency=None runs are bit-identical.
        lat_feats = []  # (feature, on) pairs for the coverage fold
        if lat_c:
            lat_inv, lat_resp = st.lat_inv, st.lat_resp
            lat_hist = st.lat_hist
            lat_count, lat_drop = st.lat_count, st.lat_drop
            lat_edges = jnp.asarray(LAT_EDGES_NS)
            lat_ids = jnp.arange(lat_c, dtype=jnp.int32)
            for j in range(ll):
                mv = user_dispatch & uem.lat_valid[j]
                oid = uem.lat[j, 0]
                is_end = uem.lat[j, 1] == jnp.int32(1)
                lat_in_r = (oid >= 0) & (oid < lat_c)
                lat_drop = lat_drop + (mv & ~lat_in_r).astype(jnp.int32)
                act = mv & lat_in_r
                if dense:
                    oid_oh = lat_ids == oid  # all-False when out of range
                    inv_o = jnp.sum(jnp.where(oid_oh, lat_inv, 0))
                    resp_o = jnp.sum(jnp.where(oid_oh, lat_resp, 0))
                else:
                    oc = jnp.clip(oid, 0, lat_c - 1)
                    inv_o = jnp.where(lat_in_r, lat_inv[oc], jnp.int64(-1))
                    resp_o = jnp.where(lat_in_r, lat_resp[oc], jnp.int64(-1))
                # first start / first response win: an open-loop army
                # invokes each id once, and a duplicated delivery's
                # second lat_end must not double-count
                do_start = act & ~is_end & (inv_o < 0)
                do_end = act & is_end & (inv_o >= 0) & (resp_o < 0)
                d = now - inv_o
                bkt = jnp.sum((d >= lat_edges).astype(jnp.int32))
                ph = jnp.clip(
                    (inv_o // jnp.int64(lat_phase_ns)).astype(jnp.int32),
                    0, lat_p - 1,
                )
                if dense:
                    lat_inv = jnp.where(oid_oh & do_start, now, lat_inv)
                    lat_resp = jnp.where(oid_oh & do_end, now, lat_resp)
                    hsel = (
                        (jnp.arange(lat_p, dtype=jnp.int32)[:, None] == ph)
                        & (jnp.arange(N_LAT_BUCKETS, dtype=jnp.int32)[None, :] == bkt)
                        & do_end
                    )
                    lat_hist = lat_hist + hsel.astype(jnp.int32)
                else:
                    lat_inv = lat_inv.at[
                        jnp.where(do_start, oc, jnp.int32(lat_c))
                    ].set(now, mode="drop")
                    lat_resp = lat_resp.at[
                        jnp.where(do_end, oc, jnp.int32(lat_c))
                    ].set(now, mode="drop")
                    lat_hist = lat_hist.at[
                        jnp.where(do_end, ph, jnp.int32(lat_p)), bkt
                    ].add(jnp.int32(1), mode="drop")
                lat_count = lat_count + do_end.astype(jnp.int32)
                # latency-bucket coverage feature: (window, bucket) —
                # a schedule that pushes ops into a new bucket of a new
                # window is NEW behavior, the guidance signal that lets
                # the hunt chase "blow the tail" (folded in the cov
                # block below, gated on cov_words like every feature)
                lat_feats.append((
                    bkt.astype(jnp.uint32)
                    | (ph.astype(jnp.uint32) << jnp.uint32(8))
                    | jnp.uint32(5 << 24),
                    do_end,
                ))
        else:
            lat_inv, lat_resp = st.lat_inv, st.lat_resp
            lat_hist = st.lat_hist
            lat_count, lat_drop = st.lat_count, st.lat_drop

        # ---- client-retry books (RetrySpec; CORE state — rt_done
        # gates the deliver/suppress decision above) ----
        if rt_c:
            # response bookkeeping: the model's lat_end marker for an op
            # (phase word 1) disarms its timer — first-response-wins,
            # the same discipline the latency tap applies, which is why
            # a retry build requires lat_markers. Markers carry the
            # STRIPPED op id (models strip attempt bits), so the slot
            # index is id - op_base whatever the delivered attempt was.
            rt_done = st.rt_done
            for j in range(ll):
                rt_mv = (
                    user_dispatch
                    & uem.lat_valid[j]
                    & (uem.lat[j, 1] == jnp.int32(1))
                )
                rt_done = rt_done | (
                    (rt_ids == (uem.lat[j, 0] - rt_base)) & rt_mv
                )
            # the delivered-attempt ledger and the armed deadline
            # (absolute ns even under time32 — forensics columns never
            # feed the pool clock)
            rt_attempt = jnp.where(rt_oh & rt_arm, rt_att, st.rt_attempt)
            rt_deadline = jnp.where(
                rt_oh & rt_arm, now_after + rt_delay, st.rt_deadline
            )
        else:
            rt_done = st.rt_done
            rt_attempt, rt_deadline = st.rt_attempt, st.rt_deadline

        # ---- coverage taps (madsim_tpu.explore) ----
        # derived state only: features of the event just dispatched are
        # hashed into an AFL-style bitmap. Nothing here feeds back into
        # the trajectory, the RNG, or the trace, so cov_words=0 (no
        # arrays, no ops) and cov_words>0 produce identical traces.
        if cov_words:
            cb_mask = jnp.uint32(cov_words * 32 - 1)
            cw_ids = jnp.arange(cov_words, dtype=jnp.uint32)

            def _cov_mix(x):
                # 32-bit finalizer (splitmix-style): pure uint32 ALU,
                # bit-identical across backends like everything else
                x = jnp.asarray(x).astype(jnp.uint32)
                x = (x ^ (x >> jnp.uint32(16))) * jnp.uint32(0x7FEB352D)
                x = (x ^ (x >> jnp.uint32(15))) * jnp.uint32(0x846CA68B)
                return x ^ (x >> jnp.uint32(16))

            def _cov_set(cov_acc, feat, on):
                bit = _cov_mix(feat) & cb_mask
                sel = cw_ids == (bit >> jnp.uint32(5))
                m = jnp.uint32(1) << (bit & jnp.uint32(31))
                return cov_acc | jnp.where(sel & on, m, jnp.uint32(0))

            if cov_hitcount:
                # AFL-style bucketing (madsim_tpu.obs): a saturating
                # per-seed counter per bitmap bit position; the bit a
                # feature sets is keyed by (feature, bucket class), so
                # crossing 1 -> 2 -> 4-7 -> ... occurrences keeps
                # setting fresh bits. Same dense/scatter duality and
                # derived-state-only rule as everything here.
                cb_n = cov_words * 32
                cb_ids = jnp.arange(cb_n, dtype=jnp.uint32)
                # AFL's class boundaries: 1,2,3,4-7,8-15,16-31,32-127,128+
                _cls_edges = (1, 2, 3, 4, 8, 16, 32, 128)

                def _tap(cov_acc, hits, feat, on):
                    ci = _cov_mix(feat) & jnp.uint32(cb_n - 1)
                    if dense:
                        cur = jnp.sum(
                            jnp.where(cb_ids == ci, hits, 0)
                        ).astype(jnp.int32)
                    else:
                        cur = hits[ci].astype(jnp.int32)
                    newc = jnp.minimum(cur + 1, 255)
                    cls = (
                        sum(
                            (newc >= t).astype(jnp.uint32)
                            for t in _cls_edges
                        )
                        - jnp.uint32(1)
                    )
                    if dense:
                        hits = jnp.where(
                            (cb_ids == ci) & on,
                            newc.astype(jnp.uint8),
                            hits,
                        )
                    else:
                        hits = hits.at[
                            jnp.where(on, ci, jnp.uint32(cb_n))
                        ].set(newc.astype(jnp.uint8), mode="drop")
                    feat2 = feat ^ (
                        (cls + jnp.uint32(1)) * jnp.uint32(0x9E3779B9)
                    )
                    return _cov_set(cov_acc, feat2, on), hits

            else:

                def _tap(cov_acc, hits, feat, on):
                    return _cov_set(cov_acc, feat, on), hits

            cov_hits = st.cov_hits
            # per-node event-kind transition pair (prev -> kind at dst)
            if dense:
                prev_kind = jnp.sum(
                    jnp.where(dst_oh, st.cov_last, 0)
                ).astype(jnp.int32)
            else:
                prev_kind = jnp.where(in_range, st.cov_last[dst_c], 0)
            f_user = (
                kind.astype(jnp.uint32)
                | (prev_kind.astype(jnp.uint32) << jnp.uint32(8))
                | (jnp.maximum(dst, 0).astype(jnp.uint32) << jnp.uint32(16))
            )
            cov, cov_hits = _tap(st.cov, cov_hits, f_user, user_dispatch)
            # coarse time phase (~134 ms buckets): behaviors that recur
            # in NEW phases are new bits, which keeps long/late
            # trajectories distinguishable from early ones
            phase = jnp.minimum(now >> jnp.int64(27), 31).astype(jnp.uint32)
            # engine/chaos kind x phase: crash/partition/heal phases of
            # an injected plan are coverage features, so a mutated
            # fault time that lands in a new phase is "interesting"
            # even before the protocol reacts
            f_chaos = (
                kind.astype(jnp.uint32)
                | (phase << jnp.uint32(8))
                | jnp.uint32(1 << 24)
            )
            cov, cov_hits = _tap(cov, cov_hits, f_chaos, dispatch & is_engine)
            # message edge (kind, src -> dst): which protocol messages
            # flowed between which nodes — partitions and gray failures
            # reshape exactly this
            f_edge = (
                kind.astype(jnp.uint32)
                | (jnp.maximum(src, 0).astype(jnp.uint32) << jnp.uint32(8))
                | (jnp.maximum(dst, 0).astype(jnp.uint32) << jnp.uint32(16))
                | jnp.uint32(3 << 24)
            )
            cov, cov_hits = _tap(cov, cov_hits, f_edge, user_dispatch & is_msg)
            # user kind x phase: WHEN the protocol did something, not
            # just that it did — a second election at 500 ms is a
            # different behavior than the first at 200 ms
            f_when = (
                kind.astype(jnp.uint32)
                | (phase << jnp.uint32(8))
                | jnp.uint32(4 << 24)
            )
            cov, cov_hits = _tap(cov, cov_hits, f_when, user_dispatch)
            if causal:
                # causal depth/width feature (tag 7): log2 bucket of the
                # folded Lamport clock x log2 bucket of the cross-node
                # causal JUMP (how far the arriving event's clock was
                # ahead of the node's own — a big jump is a long
                # causal chain crossing nodes). A schedule reaching a
                # new depth or jump class is new behavior the guided
                # hunt can chase — "deeper causality" as coverage.
                _pow2 = jnp.asarray(
                    np.power(2, np.arange(1, 32, dtype=np.uint64)).astype(
                        np.uint32
                    )
                )
                depth_b = jnp.sum((lam_new >= _pow2).astype(jnp.uint32))
                # int64 difference, clipped: the uint32 subtraction
                # would be a wrap surface when the node is AHEAD of the
                # arriving event (the common same-node case)
                jump = jnp.clip(
                    evlam_i.astype(jnp.int64) - lam_prev.astype(jnp.int64),
                    0,
                    None,
                ).astype(jnp.uint32)
                jump_b = jnp.sum((jump >= _pow2).astype(jnp.uint32))
                f_causal = (
                    depth_b
                    | (jump_b << jnp.uint32(8))
                    | jnp.uint32(7 << 24)
                )
                cov, cov_hits = _tap(cov, cov_hits, f_causal, dispatch)
            # appended history records: (op, key, arg, ok) words — term
            # bumps, elected leaders, committed (index, value) pairs
            for j in range(rr):
                f_rec = (
                    (uem.rec[j, 0].astype(jnp.uint32) * jnp.uint32(0x9E3779B1))
                    ^ (uem.rec[j, 1].astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
                    ^ (uem.rec[j, 2].astype(jnp.uint32) * jnp.uint32(0xC2B2AE35))
                    ^ uem.rec[j, 3].astype(jnp.uint32)
                    ^ jnp.uint32(2 << 24)
                )
                cov, cov_hits = _tap(
                    cov, cov_hits, f_rec, user_dispatch & uem.rec_valid[j]
                )
            # completed client-army ops: (measurement window, latency
            # bucket) features computed in the latency block above
            for f_lat, on_lat in lat_feats:
                cov, cov_hits = _tap(cov, cov_hits, f_lat, on_lat)
            # workload-contributed protocol features (Workload.
            # cov_features): post-dispatch fleet state -> (feature, on)
            # pairs, namespaced under tag 6 so they can never collide
            # with the engine's own feature families
            if wl.cov_features is not None:
                for f_wl, on_wl in wl.cov_features(node_state, now):
                    # mask to the 24-bit feature payload BEFORE tagging:
                    # a hook word with high bits set must alias other
                    # tag-6 features, never another family's namespace
                    f_wl = (
                        jnp.asarray(f_wl).astype(jnp.uint32)
                        & jnp.uint32((1 << 24) - 1)
                    ) | jnp.uint32(6 << 24)
                    cov, cov_hits = _tap(
                        cov, cov_hits, f_wl, user_dispatch & on_wl
                    )
            if dense or rank_place:
                cov_last = jnp.where(
                    dst_oh & user_dispatch, kind, st.cov_last
                ).astype(jnp.int32)
            else:
                cov_last = st.cov_last.at[
                    jnp.where(in_range & user_dispatch, dst_c, jnp.int32(n))
                ].set(kind, mode="drop")
        else:
            cov, cov_last, cov_hits = st.cov, st.cov_last, st.cov_hits

        # ---- fleet metrics (madsim_tpu.obs) ----
        # every operand below is a value the step already computed, and
        # nothing written here is ever read by the trajectory — the
        # derived-state-only rule the obs-off identity test pins.
        if metrics:
            i32 = lambda b: jnp.sum(b).astype(jnp.int32)  # noqa: E731
            sent_m = dispatch & em.valid & em.send
            inc = [jnp.int32(0)] * N_METRICS
            inc[MET_SENT] = i32(sent_m)
            inc[MET_DELIVERED] = (dispatch & is_msg).astype(jnp.int32)
            inc[MET_LOST] = i32(sent_m & lost)
            inc[MET_DEAD_DROP] = i32(sent_m & ~lost & ~alive_at_dst)
            if dup_rows:
                # shadow rows are the K emit slots after the restart row
                # (the retry timer row, when compiled, follows them)
                inc[MET_DUP] = i32(e_valid[k + 1 : 2 * k + 1])
            inc[MET_CRASH] = (dispatch & (kind == KIND_KILL)).astype(jnp.int32)
            inc[MET_RESTART] = (
                dispatch & (kind == KIND_RESTART)
            ).astype(jnp.int32)
            inc[MET_PAUSE] = (dispatch & (kind == KIND_PAUSE)).astype(jnp.int32)
            inc[MET_CLOG_BLOCK] = (active & clogged).astype(jnp.int32)
            inc[MET_TIMER] = (user_dispatch & ~is_msg).astype(jnp.int32)
            if hcap > 0:
                inc[MET_RECORD] = i32(keep)
            # threefry blocks per active event step: the poll-cost/jitter
            # pair + one latency/loss block per emit slot (+ the dup
            # shadow slots when compiled, + the torn-prefix draw under
            # the sync discipline) — a static count, so this is
            # bookkeeping, not instrumentation of the RNG itself
            blocks = 1 + (k + 1) + (k if dup_rows else 0) + (
                1 if sync_on else 0
            ) + (1 if rt_c else 0)
            inc[MET_RNG] = jnp.where(active, jnp.int32(blocks), 0)
            if sync_on:
                inc[MET_SYNC] = do_sync.astype(jnp.int32)
                inc[MET_SYNC_LOST] = sync_lied.astype(jnp.int32)
                inc[MET_TORN] = tore.astype(jnp.int32)
            if rt_c:
                # a re-delivery = a DELIVERED army row past attempt 0; a
                # give-up = the max_attempts sentinel popping with the
                # op still unanswered. (A sentinel row that dies with a
                # killed client incarnation is an undercount — the epoch
                # gate drops it before the books see it.)
                inc[MET_RETRY] = (rt_arm & (rt_att > 0)).astype(jnp.int32)
                inc[MET_RETRY_GIVEUP] = (
                    is_army & ~rt_done_i & (rt_att == rt_max)
                ).astype(jnp.int32)
            met = st.met + jnp.stack(inc)
            new_halt = halted & ~st.halted
            code = jnp.where(
                dispatch & (kind == KIND_HALT),
                jnp.int32(HALT_DONE),
                jnp.int32(HALT_TIME_LIMIT),
            )
            cur_code = met[MET_HALT_CODE]
            # an empty pool on an unhalted seed is terminal (nothing
            # pending can create events): record it as a deadlock code
            idle = ~has_event & ~st.halted
            met = met.at[MET_HALT_CODE].set(
                jnp.where(
                    new_halt,
                    code,
                    jnp.where(
                        idle & (cur_code == HALT_RUNNING),
                        jnp.int32(HALT_IDLE),
                        cur_code,
                    ),
                )
            )
        else:
            met = st.met

        # ---- timeline ring (madsim_tpu.obs) ----
        # the dispatched-event stream, one row per dispatch: exactly the
        # (now, kind, node, src, args) tuple the trace hash folds, so a
        # decoded timeline refolds to the certified trace (obs.refold,
        # payload-free workloads). Same compact-append duality as the
        # history columns; a full ring counts drops loudly in tl_drop.
        if timeline_cap:
            tfits = st.tl_count < timeline_cap
            t_do = dispatch & tfits
            if dense:
                tl_ids = jnp.arange(timeline_cap, dtype=jnp.int32)
                t_sel = (tl_ids == st.tl_count) & t_do
                tl_t = jnp.where(t_sel, now, st.tl_t)
                tl_meta = jnp.where(t_sel, meta_i, st.tl_meta)
                tl_args = jnp.where(t_sel[:, None], args[None, :], st.tl_args)
                tl_pay = jnp.where(t_sel[:, None], pay_i[None, :], st.tl_pay)
                tl_emit = jnp.where(t_sel, emit_i, st.tl_emit)
                if causal:
                    tl_seq = jnp.where(t_sel, seq_i32, st.tl_seq)
                    tl_parent = jnp.where(t_sel, parent_i, st.tl_parent)
                    tl_lam = jnp.where(t_sel, lam_new, st.tl_lam)
            else:
                t_slot = jnp.where(t_do, st.tl_count, jnp.int32(timeline_cap))
                tl_t = st.tl_t.at[t_slot].set(now, mode="drop")
                tl_meta = st.tl_meta.at[t_slot].set(meta_i, mode="drop")
                tl_args = st.tl_args.at[t_slot].set(args, mode="drop")
                tl_pay = st.tl_pay.at[t_slot].set(pay_i, mode="drop")
                tl_emit = st.tl_emit.at[t_slot].set(emit_i, mode="drop")
                if causal:
                    tl_seq = st.tl_seq.at[t_slot].set(seq_i32, mode="drop")
                    tl_parent = st.tl_parent.at[t_slot].set(
                        parent_i, mode="drop"
                    )
                    tl_lam = st.tl_lam.at[t_slot].set(lam_new, mode="drop")
            if not causal:
                tl_seq, tl_parent, tl_lam = (
                    st.tl_seq, st.tl_parent, st.tl_lam
                )
            tl_count = st.tl_count + t_do.astype(jnp.int32)
            tl_drop = st.tl_drop + (dispatch & ~tfits).astype(jnp.int32)
        else:
            tl_count, tl_drop = st.tl_count, st.tl_drop
            tl_t, tl_meta, tl_args = st.tl_t, st.tl_meta, st.tl_args
            tl_pay, tl_emit = st.tl_pay, st.tl_emit
            tl_seq, tl_parent, tl_lam = st.tl_seq, st.tl_parent, st.tl_lam

        # ---- trace + clock ----
        trace = jnp.where(
            dispatch,
            _trace_fold(st.trace, now, kind, dst, args, pay_i),
            st.trace,
        )
        out = SimState(
            seed=st.seed,
            now=now_after,
            step=st.step + jnp.uint32(1),
            halted=halted,
            halt_time=halt_time,
            trace=trace,
            overflow=overflow,
            msg_count=msg_count,
            ev_time=ev_time,
            ev_valid=ev_valid,
            ev_meta=ev_meta,
            ev_epoch=ev_epoch,
            ev_args=ev_args,
            ev_pay=ev_pay,
            alive=alive,
            paused=paused,
            epoch=epoch,
            node_state=node_state,
            clog=clog,
            slow=slow,
            dup=dup,
            skew=skew,
            disk=disk,
            wmask=wmask,
            sync_loss=sync_loss,
            sync_eio=sync_eio,
            torn=torn,
            hist_count=hist_count,
            hist_drop=hist_drop,
            hist_word=hist_word,
            hist_t=hist_t,
            cov=cov,
            cov_last=cov_last,
            cov_hits=cov_hits,
            met=met,
            tl_count=tl_count,
            tl_drop=tl_drop,
            tl_t=tl_t,
            tl_meta=tl_meta,
            tl_args=tl_args,
            tl_pay=tl_pay,
            ev_emit=ev_emit,
            tl_emit=tl_emit,
            lam=lam,
            ev_parent=ev_parent,
            ev_lam=ev_lam,
            tl_seq=tl_seq,
            tl_parent=tl_parent,
            tl_lam=tl_lam,
            lat_inv=lat_inv,
            lat_resp=lat_resp,
            lat_hist=lat_hist,
            lat_count=lat_count,
            lat_drop=lat_drop,
            rt_done=rt_done,
            rt_attempt=rt_attempt,
            rt_deadline=rt_deadline,
            tile_min=tile_min_out,
            tile_cnt=tile_cnt_out,
        )
        return out

    return step


def make_run(
    wl: Workload, cfg: EngineConfig, n_steps: int, build: Build = Build()
):
    """Build ``run(state) -> state``: n_steps of vmapped lockstep advance.

    The returned function is jit-friendly and sharding-friendly: every
    array's leading axis is the seed axis, so a NamedSharding over that
    axis turns this into pure data-parallel work across chips with zero
    collectives in the hot loop (results are combined host-side).

    time32 contract: under the int32 time representation a timer delay
    past the int32 horizon (``cfg.delay_bound_ns`` eligibility) is
    clamped and counted in ``state.overflow`` — the run continues on a
    trajectory that may diverge from the int64 layout. Callers must
    check ``overflow == 0`` before trusting per-seed results (bench.py
    and engine.search do; direct callers are responsible themselves).
    """
    step = jax.vmap(make_step(wl, cfg, build))

    def run(state: SimState) -> SimState:
        def body(s, _):
            return step(s), None

        final, _ = lax.scan(body, state, None, length=n_steps)
        return final

    return run


def make_run_while(
    wl: Workload, cfg: EngineConfig, max_steps: int, build: Build = Build()
):
    """Like :func:`make_run` but stops as soon as every seed has halted.

    ``lax.while_loop`` on device: no wasted lockstep iterations once the
    slowest seed finishes — the bench path for halting workloads (e.g.
    raft elections, where the tail of seeds needing a second election
    round would otherwise cost every seed the full max_steps). Under
    ``parallel.shard_over_seeds`` each device loops until its own shard
    has halted.

    The :func:`make_run` time32 contract applies here too: horizon-
    clamped timer delays are counted in ``state.overflow`` and the run
    silently continues — check ``overflow == 0`` before trusting
    per-seed results.

    ``run.counted(state) -> (final, row_steps)`` is the same loop that
    also returns ``row_steps``, int64 ``[paid, live]``: rows x loop
    iterations, and those in which the row had not yet halted. The
    loop exits on the live count it carries, so counting costs no
    reduction beyond the one the exit test makes anyway.
    """
    step = jax.vmap(make_step(wl, cfg, build))

    def counted(state: SimState):
        def cond(carry):
            s, i, live, acc = carry
            return (i < max_steps) & (live > 0)

        def body(carry):
            s, i, live, acc = carry
            s = step(s)
            live2 = jnp.sum(~s.halted).astype(jnp.int64)
            return s, i + 1, live2, acc + live

        live0 = jnp.sum(~state.halted).astype(jnp.int64)
        final, iters, _, live_steps = lax.while_loop(
            cond, body, (state, jnp.int64(0), live0, jnp.int64(0))
        )
        return final, jnp.stack([iters * state.seed.shape[0], live_steps])

    def run(state: SimState) -> SimState:
        return counted(state)[0]

    # jitted on its own, the counting loop keeps the runner's program
    # name (``jit_run``) in device traces
    counted.__name__ = counted.__qualname__ = "run"
    run.counted = counted
    return run
