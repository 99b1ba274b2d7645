"""Program profiler: per-program trace/lower/compile/execute attribution.

A campaign's wall clock hides four very different costs inside every
"dispatch": Python tracing, StableHLO lowering, XLA compilation, and
the actual device execution. jit reports none of them — worse, a fresh
closure per call silently re-pays the first three (the ``run_device``
re-trace cost ROADMAP item 1 flagged). This module makes the split a
measured quantity:

* :class:`AotProgram` — a jit-compatible callable built through the
  explicit ``jax.stages`` pipeline (``jit(fn).trace -> .lower ->
  .compile``), executing through the compiled artifact. Every build is
  timed per phase and counted, so *retraces per cache key* is a
  counter, not a guess; the most recent call's build share is exposed
  as :attr:`AotProgram.last_build_s` so drivers can split
  ``compile_wall_s`` out of their dispatch telemetry. The compiled
  program's HLO cost analysis (flops, bytes accessed) and memory
  footprint (argument/output/temp bytes) are recorded at build time.
  Values are bit-identical to ``jax.jit(fn)(*args)`` — the same XLA
  program runs either way; only the host-side bookkeeping differs.
* :class:`ProgramProfiler` — the session registry: enable one
  (:func:`enable` / :func:`profiled`) and every ``AotProgram`` build
  and execution in the process reports into it, giving the
  campaign-wide program table (``report()``) and the retrace
  certificate (``retraces()``). With no profiler active the only
  overhead is a None check per call.
* :func:`device_memory` — the live-buffer footprint: every live jax
  array summed (plus the backend allocator's ``memory_stats`` where
  the platform provides one — TPU/GPU HBM; CPU returns only the
  live-array view).
* :func:`span` — a named host span on the profiler's own clock, for an
  operator who runs ``jax.profiler.trace`` (or ``start_trace``) around
  a sweep or a campaign: the spans land in the host plane of the same
  trace as the device planes, so an idle stretch of the chip is named
  by what the program was doing on the host. With no profiler running
  a span costs one TraceMe check.

The program's spans, nested as they nest in the trace, with what each
covers and the metadata it carries:

* ``madsim.search`` — one ``engine.search_seeds`` call; at exit it
  carries ``d2h_bytes``, ``d2h_transfers`` (device arrays copied to
  the host), ``row_steps_paid`` and ``row_steps_live``, the call's
  ``SearchReport`` counters.

  * ``madsim.search.plan`` — ``plan.compile_batch`` and the plan-row
    checks.
  * ``madsim.search.init`` — the (init, run) program lookup and the
    dispatch of the compiled ``init(seeds[, rows])`` (``jit_init``).
  * ``madsim.search.run`` — dispatch of the runner up to
    ``block_until_ready``.
  * ``madsim.search.fetch`` — the device→host copies: a compacted
    sweep's one packed buffer, already in seed order, and its trimmed
    history columns (the lockstep path's fields and screen verdict
    words).
  * ``madsim.search.check`` — invariant, screen verdicts, the
    flagged-history gather, ``history_invariant`` and the report.

* ``madsim.explore.start`` — campaign session construction through
  ``start()``: validation, program-cache lookup, carry init.
* ``madsim.explore.generation`` — one generation of
  ``explore.run_device``; carries ``g``.

  * ``madsim.explore.sync`` — the summary ``device_get``, the fleet
    fold and ``consume``; at exit it carries the generation's
    ``row_steps_paid`` and ``row_steps_live``.

* ``madsim.explore.materialize`` — the one ``device_get`` of the
  corpus and violation stores for the report or a checkpoint, and
  their ``EntryView``s; a checkpoint also builds every entry in it. At
  exit it carries ``entries`` (corpus plus violations reported) and
  ``built`` (the entries constructed inside the span: 0 for a report,
  the entries new since the last checkpoint for a checkpoint).

The pipelined driver (``farm.run_pipelined``) opens ``start``,
``sync`` (with no generation parent: it consumes behind its
dispatches) and ``materialize``; a per-generation checkpoint nests
``materialize`` inside ``sync``.

Everything here is host-side bookkeeping over wall clocks and compiled
artifacts; nothing enters traced code (the lint matrix pins this — see
``lint.noninterference.FLIGHT_AXES``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from contextlib import contextmanager

import jax

__all__ = [
    "AotProgram",
    "ProgramProfiler",
    "ProgramRecord",
    "current",
    "device_memory",
    "disable",
    "enable",
    "profiled",
    "program_cost",
    "span",
]


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` (``madsim.*``, module docstring) on the
    profiler's clock, carrying ``meta`` as its metadata. Use it as
    ``with span("madsim.search") as sp: ...; sp.set_metadata(k=v)`` to
    attach counters known only at exit."""
    return jax.profiler.TraceAnnotation(name, **meta)


def digest(key) -> str:
    """Short stable digest of a cache key (any repr-able object)."""
    return hashlib.sha1(repr(key).encode()).hexdigest()[:12]


def _signature(args) -> tuple:
    """Structure + aval signature of a call's arguments: the identity a
    compiled executable is pinned to (jit's retrace key, minus
    shardings — a sharding drift surfaces as an executable rejection
    and is handled by a counted rebuild)."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (
        treedef,
        tuple(
            (getattr(a, "shape", None), str(getattr(a, "dtype", type(a))))
            for a in leaves
        ),
    )


def program_cost(compiled) -> dict:
    """HLO cost analysis + memory footprint of a compiled program.

    Returns whatever the backend exposes: ``flops`` and
    ``bytes_accessed`` from XLA's cost analysis, and the
    argument/output/temp/code byte sizes from the compiled memory
    stats (the per-program device-memory budget — on TPU this is the
    HBM the program itself pins, distinct from the live-buffer pool
    :func:`device_memory` reports)."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            out["flops"] = float(ca.get("flops", 0.0))
            out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception:
        pass
    try:
        ms = compiled.memory_analysis()
        if ms is not None:
            out["arg_bytes"] = int(ms.argument_size_in_bytes)
            out["out_bytes"] = int(ms.output_size_in_bytes)
            out["temp_bytes"] = int(ms.temp_size_in_bytes)
            out["code_bytes"] = int(ms.generated_code_size_in_bytes)
    except Exception:
        pass
    return out


def device_memory() -> dict:
    """Live device-memory accounting: every live jax array summed.

    ``live_buffer_bytes`` is the logical byte count of all live arrays
    (a replicated array counts once); ``allocator_bytes_in_use`` joins
    when the backend exposes per-device ``memory_stats`` (TPU/GPU HBM
    allocators do; CPU does not)."""
    arrs = jax.live_arrays()
    total = 0
    for a in arrs:
        try:
            total += a.nbytes
        except Exception:
            pass
    out = {"live_buffers": len(arrs), "live_buffer_bytes": int(total)}
    in_use = 0
    have = False
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            in_use += int(stats["bytes_in_use"])
            have = True
    if have:
        out["allocator_bytes_in_use"] = in_use
    return out


@dataclasses.dataclass
class ProgramRecord:
    """One program's accumulated profile (per (name, key))."""

    name: str
    key: str  # cache-key digest — same key twice means a RETRACE
    traces: int = 0  # trace+lower+compile events (the retrace counter)
    calls: int = 0
    trace_wall_s: float = 0.0
    lower_wall_s: float = 0.0
    compile_wall_s: float = 0.0
    execute_wall_s: float = 0.0
    # last build's HLO cost analysis + memory footprint (program_cost)
    flops: float = 0.0
    bytes_accessed: float = 0.0
    arg_bytes: int = 0
    out_bytes: int = 0
    temp_bytes: int = 0
    code_bytes: int = 0

    @property
    def build_wall_s(self) -> float:
        return self.trace_wall_s + self.lower_wall_s + self.compile_wall_s

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class ProgramProfiler:
    """Session-wide program registry: builds and executions of every
    :class:`AotProgram` report here while the profiler is active
    (:func:`enable` / :func:`profiled`).

    ``programs`` maps (name, key-digest) to :class:`ProgramRecord`;
    ``pop_events()`` drains the build-event stream (one dict per
    trace/lower/compile, in build order) — the flight recorder turns
    these into ``compile`` telemetry records and Perfetto instants.
    """

    def __init__(self):
        self.programs: dict = {}
        self.events: list = []

    def record(self, name: str, key: str) -> ProgramRecord:
        rec = self.programs.get((name, key))
        if rec is None:
            rec = self.programs[(name, key)] = ProgramRecord(name, key)
        return rec

    def note_build(self, name, key, trace_s, lower_s, compile_s, cost):
        rec = self.record(name, key)
        rec.traces += 1
        rec.trace_wall_s += trace_s
        rec.lower_wall_s += lower_s
        rec.compile_wall_s += compile_s
        for f in ("flops", "bytes_accessed", "arg_bytes", "out_bytes",
                  "temp_bytes", "code_bytes"):
            if f in cost:
                setattr(rec, f, cost[f])
        self.events.append({
            "program": name, "key": key, "retrace": rec.traces,
            "trace_s": round(trace_s, 4), "lower_s": round(lower_s, 4),
            "compile_s": round(compile_s, 4), **cost,
        })

    def note_execute(self, name, key, seconds):
        rec = self.record(name, key)
        rec.calls += 1
        rec.execute_wall_s += seconds

    def pop_events(self) -> list:
        ev, self.events = self.events, []
        return ev

    def retraces(self, prefix: str = "") -> dict:
        """(name, key) -> trace count, optionally filtered by a name
        prefix — the retrace certificate reads this (== 1 per key)."""
        return {
            nk: rec.traces
            for nk, rec in sorted(self.programs.items())
            if nk[0].startswith(prefix)
        }

    def to_dicts(self) -> list:
        return [rec.to_dict() for _, rec in sorted(self.programs.items())]

    def report(self) -> str:
        """Text table of every profiled program (the artifact form)."""
        lines = [
            f"{'program':<28} {'key':<13} {'tr':>3} {'calls':>5} "
            f"{'trace_s':>8} {'lower_s':>8} {'compile_s':>9} {'exec_s':>8} "
            f"{'GFLOP':>8} {'MB_acc':>8} {'MB_tmp':>7}"
        ]
        for _, r in sorted(self.programs.items()):
            lines.append(
                f"{r.name:<28} {r.key:<13} {r.traces:>3} {r.calls:>5} "
                f"{r.trace_wall_s:>8.3f} {r.lower_wall_s:>8.3f} "
                f"{r.compile_wall_s:>9.3f} {r.execute_wall_s:>8.3f} "
                f"{r.flops / 1e9:>8.3f} {r.bytes_accessed / 1e6:>8.1f} "
                f"{r.temp_bytes / 1e6:>7.1f}"
            )
        return "\n".join(lines)


_ACTIVE: ProgramProfiler | None = None


def enable(profiler: ProgramProfiler | None = None) -> ProgramProfiler:
    """Install ``profiler`` (or a fresh one) as the session profiler."""
    global _ACTIVE
    _ACTIVE = profiler if profiler is not None else ProgramProfiler()
    return _ACTIVE


def disable() -> None:
    global _ACTIVE
    _ACTIVE = None


def current() -> ProgramProfiler | None:
    return _ACTIVE


@contextmanager
def profiled(profiler: ProgramProfiler | None = None):
    """Scope a profiler: ``with profiled() as p: ...; p.report()`` —
    restores whatever was active before on exit."""
    global _ACTIVE
    prev = _ACTIVE
    p = enable(profiler)
    try:
        yield p
    finally:
        _ACTIVE = prev


class AotProgram:
    """A to-be-jitted function, built through the explicit AOT pipeline.

    Call it exactly like ``jax.jit(fn)``. The first call per argument
    signature pays trace → lower → compile with each phase timed
    (:attr:`last_build_s` carries the most recent call's build share —
    0.0 on warm calls, so ``dispatch_wall - last_build_s`` is pure
    execution); later calls run the compiled executable directly.
    ``builds`` counts compilations over the program's lifetime — the
    retrace counter the generation-program caches are certified by.

    A sharding or aval drift on the inputs (the executable is pinned
    to what it compiled under; jit would silently recompile) triggers
    ONE counted rebuild and retries — visible in the profile instead
    of hidden in dispatch wall.
    """

    def __init__(self, name: str, key, fn):
        self.name = name
        self.key = digest(key)
        # the untransformed function, for callers that trace it into a
        # larger program (engine.search.make_sweep)
        self.fn = fn
        self._jit = jax.jit(fn)
        self._exes: dict = {}
        self.builds = 0
        self.trace_wall_s = 0.0
        self.lower_wall_s = 0.0
        self.compile_wall_s = 0.0
        self.last_build_s = 0.0
        self.cost: dict = {}

    def _build(self, sig, args):
        t0 = time.monotonic()  # lint: allow(wall-clock)
        traced = self._jit.trace(*args)
        t1 = time.monotonic()  # lint: allow(wall-clock)
        lowered = traced.lower()
        t2 = time.monotonic()  # lint: allow(wall-clock)
        exe = lowered.compile()
        t3 = time.monotonic()  # lint: allow(wall-clock)
        self._exes[sig] = exe
        self.builds += 1
        self.trace_wall_s += t1 - t0
        self.lower_wall_s += t2 - t1
        self.compile_wall_s += t3 - t2
        self.last_build_s += t3 - t0
        self.cost = program_cost(exe)
        if _ACTIVE is not None:
            _ACTIVE.note_build(
                self.name, self.key, t1 - t0, t2 - t1, t3 - t2, self.cost
            )
        return exe

    def __call__(self, *args):
        self.last_build_s = 0.0
        sig = _signature(args)
        exe = self._exes.get(sig)
        if exe is None:
            exe = self._build(sig, args)
        p = _ACTIVE
        if p is None:
            try:
                return exe(*args)
            except (TypeError, ValueError):
                exe = self._build(sig, args)
                return exe(*args)
        t0 = time.monotonic()  # lint: allow(wall-clock)
        try:
            out = exe(*args)
        except (TypeError, ValueError):
            exe = self._build(sig, args)
            t0 = time.monotonic()  # lint: allow(wall-clock)
            out = exe(*args)
        jax.block_until_ready(out)
        p.note_execute(
            self.name, self.key, time.monotonic() - t0  # lint: allow(wall-clock)
        )
        return out

    def call_async(self, *args):
        """``__call__`` without the profiler's completion barrier.

        The profiled ``__call__`` blocks on the outputs so
        ``execute_wall_s`` measures device time — which would serialize
        a pipelined schedule right back into the blocking one. This
        path ENQUEUES only (jax async dispatch; the caller owns the
        ``block_until_ready`` at its consume point): builds are still
        timed and retrace-counted identically, calls are still counted,
        but the profiler's per-call execute wall is recorded as the
        enqueue cost (~0), with the real device wall visible in the
        caller's queue/idle split instead.
        """
        self.last_build_s = 0.0
        sig = _signature(args)
        exe = self._exes.get(sig)
        if exe is None:
            exe = self._build(sig, args)
        t0 = time.monotonic()  # lint: allow(wall-clock)
        try:
            out = exe(*args)
        except (TypeError, ValueError):
            exe = self._build(sig, args)
            t0 = time.monotonic()  # lint: allow(wall-clock)
            out = exe(*args)
        if _ACTIVE is not None:
            _ACTIVE.note_execute(
                self.name, self.key,
                time.monotonic() - t0,  # lint: allow(wall-clock)
            )
        return out
