"""Interval abstract interpretation over jaxprs — range proofs.

Two of this repo's confirmed bug classes are invisible to BOTH the
bit-identity tests and the taint walker (lint.taint): silent integer
wraparound on the time32 layout (a decayed +inf sentinel, an overflowed
reduction) and threefry purpose-lane collisions (two draw sites sharing
a ``(purpose, counter)`` lane and silently correlating "independent"
streams). Both are *value-range* properties — exactly what a forward
interval domain proves. This module walks the same first-order dataflow
programs the taint proof walks (scan/while fixpoint with widening, cond
join, jit recursion, conservative top for unknown primitives), but
carries per-var integer ranges instead of labels, seeded from the
SimState column contracts declared in ``engine.column_contracts``.

Two provers ride the walk:

* **Overflow certification** (:func:`check_ranges`) — every ``add``/
  ``sub``/``mul`` (and shift-left/scatter-add/cumsum, the same
  operation in other clothes) whose operands carry a *time* or
  *counter* tag must produce a mathematical result interval that fits
  the result dtype. The signed/unsigned rule mirrors C's: unsigned
  arithmetic is modular by definition (the threefry rounds, the trace
  hash, the coverage folds, packed meta words — all deliberately
  uint32/uint64), so only signed results are overflow surfaces.
  Findings cite the offending equation chain in SimState field
  vocabulary (``time:ev_time``, ``counter:hist_count``), the way
  ``noninterference`` leak reports do.

* **Lane disjointness** — under :func:`engine.rng.lane_site_tracing`
  every threefry application appears as one named call-site equation;
  the walker records each site's resolved ``(x0, x1)`` operand ranges
  (the counter and purpose words — exact vectors when the purposes are
  the engine's static lane stack) and requires (a) every purpose to
  lie inside a registered :data:`engine.rng.PURPOSE_LANES` block,
  (b) no site to draw one purpose twice, and (c) every pair of
  non-branch-exclusive sites with overlapping counters to have
  pairwise-disjoint purposes. Sites in sibling ``cond`` branches are
  mutually exclusive by construction and exempt from (c).

Soundness posture (stated, not hidden):

* **Contracts are assumptions.** The column contracts are the declared
  runtime invariants (eligibility bounds, insertion clamps, capacity
  saturation, the halt discipline); loop carries that map to contract
  columns are narrowed back into their contract at each fixpoint
  iteration — the assume half of an assume-guarantee proof. The
  certified statement is therefore: *within the declared horizon, and
  for states satisfying the column contracts, no tracked arithmetic
  can wrap and no two live lanes can alias.* The guarantee half is the
  engine's runtime backstops plus the bit-identity pins.
* **The masked-sum pick idiom is trusted.** ``sum(where(m, x, 0))`` is
  this engine's "pick one element" (one-hot match matrices, rank
  placement); a non-relational domain cannot prove the one-hot-ness,
  so with ``onehot_sums=True`` (default) the sum is modeled as the
  hull of {0, x} instead of ``n*x``. Every such site in the engine is
  one-hot by cumsum-rank construction.
* **Relational facts need pragmas.** A handful of sites wrap by
  design (the time32 stale-slot rebases); they carry per-site
  ``# lint: allow(absint-overflow)`` pragmas, and the allowlist is
  checked — a pragma no traced program exercises is reported stale
  (:func:`stale_absint_pragmas`), the ``unused-allow`` rule extended
  to this analysis.
"""

from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path

import numpy as np

import jax
from jax.extend import core as jax_core

from ..engine.core import (
    ABSINT_HORIZON_NS,
    Build,
    EngineConfig,
    LatencySpec,
    Workload,
    column_contracts,
    make_init,
    make_run,
    make_step,
)
from ..engine import rng as _rng
from .noninterference import (
    BASE_LOWERING,
    LAYOUT_AXES,
    _leaf_names,
    lowering_eligible,
    report_flags,
    split_axis,
)
from .rules import DEFAULT_PATHS, _pragma_entries

__all__ = [
    "AbsintReport",
    "LaneSite",
    "OVERFLOW_RULE",
    "LANE_RULE",
    "ABSINT_AXES",
    "absint_matrix",
    "absint_model_matrix",
    "absint_pragma_inventory",
    "analyze_intervals",
    "check_lane_sites",
    "check_ranges",
    "plant_lane_collision",
    "plant_time32_sentinel_decay",
    "run_mutant_controls",
    "stale_absint_pragmas",
]

OVERFLOW_RULE = "absint-overflow"
LANE_RULE = "absint-lane"
_TRACKED = ("time:", "counter:")
_REPO_ROOT = str(Path(__file__).resolve().parents[2])
_CONST_MAX = 4096  # largest array kept as an exact constant
_WIDEN_AFTER = 2  # fixpoint iterations before widening unstable bounds
_MAX_ITERS = 8


# ---------------------------------------------------------------------------
# The abstract domain: integer intervals + contract-family tags.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AVal:
    """One var's abstract value: ``[lo, hi]`` (None = unbounded, the
    float case), the contract-family tags that flowed into it, an
    optional exactly-known constant, and the narrowing contract a
    loop carry re-assumes at each iteration."""

    lo: object = None
    hi: object = None
    tags: frozenset = frozenset()
    const: object = None
    contract: tuple = None

    def key(self):
        return (self.lo, self.hi, self.tags)


def _dtype_range(dt):
    dt = np.dtype(dt)
    if dt == np.bool_:
        return 0, 1
    if np.issubdtype(dt, np.integer):
        info = np.iinfo(dt)
        return int(info.min), int(info.max)
    return None, None


def _top_for(var, tags=frozenset()):
    lo, hi = _dtype_range(var.aval.dtype)
    return AVal(lo, hi, tags)


def _from_concrete(val):
    arr = np.asarray(val)
    if arr.dtype == np.bool_:
        lo, hi = (int(arr.min()), int(arr.max())) if arr.size else (0, 0)
        return AVal(lo, hi, frozenset(), arr if arr.size <= _CONST_MAX else None)
    if np.issubdtype(arr.dtype, np.integer):
        if arr.size == 0:
            return AVal(0, 0)
        return AVal(
            int(arr.min()), int(arr.max()), frozenset(),
            arr if arr.size <= _CONST_MAX else None,
        )
    return AVal(None, None)


def _join(a: AVal, b: AVal) -> AVal:
    lo = None if a.lo is None or b.lo is None else min(a.lo, b.lo)
    hi = None if a.hi is None or b.hi is None else max(a.hi, b.hi)
    const = a.const if (
        a.const is not None and b.const is not None
        and np.array_equal(a.const, b.const)
    ) else None
    return AVal(lo, hi, a.tags | b.tags, const, a.contract)


def _narrow(a: AVal, contract) -> AVal:
    """Assume-narrow a loop carry back into its declared contract."""
    if contract is None:
        return a
    clo, chi = contract
    lo = clo if a.lo is None else max(a.lo, clo)
    hi = chi if a.hi is None else min(a.hi, chi)
    if hi < lo:  # contradiction: keep the contract (the assumption)
        lo, hi = clo, chi
    return dataclasses.replace(a, lo=lo, hi=hi)


def _tracked(tags) -> bool:
    return any(t.startswith(_TRACKED) for t in tags)


def _corners(a: AVal, b: AVal, op):
    if None in (a.lo, a.hi, b.lo, b.hi):
        return None, None
    cs = [op(a.lo, b.lo), op(a.lo, b.hi), op(a.hi, b.lo), op(a.hi, b.hi)]
    return min(cs), max(cs)


# ---------------------------------------------------------------------------
# Lane sites.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LaneSite:
    """One threefry application in a traced program."""

    path: str
    src: tuple  # (repo-relative file, line) or (None, 0)
    purposes: object  # exact np.ndarray of purpose words, or None
    p_lo: int
    p_hi: int
    x0_lo: int
    x0_hi: int
    x0_tags: tuple

    def describe(self) -> str:
        if self.purposes is not None:
            vals = sorted(int(v) for v in np.unique(self.purposes))
            shown = ", ".join(f"{v:#x}" for v in vals[:8])
            if len(vals) > 8:
                shown += f", ... ({len(vals)} lanes)"
            p = f"purposes {{{shown}}}"
        else:
            p = f"purposes [{self.p_lo:#x}, {self.p_hi:#x}]"
        where = f"{self.src[0]}:{self.src[1]}" if self.src[0] else self.path
        return f"{where} {p}"

    def purpose_set(self):
        if self.purposes is None:
            return None
        return {int(v) for v in np.unique(self.purposes)}


def _branch_exclusive(pa: str, pb: str) -> bool:
    """True when the two equation paths live in SIBLING branches of one
    cond/switch — at most one executes per dispatch, so their draws
    can never coexist at the same counter."""
    for x, y in zip(pa.split("."), pb.split(".")):
        if x != y:
            return x.startswith("branch") and y.startswith("branch")
    return False


# ---------------------------------------------------------------------------
# The walker.
# ---------------------------------------------------------------------------


def _unclose(j):
    return j.jaxpr if isinstance(j, jax_core.ClosedJaxpr) else j


def _sub_jaxprs(params):
    out = []
    for key, val in params.items():
        if isinstance(val, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
            out.append((key, val))
        elif isinstance(val, (tuple, list)):
            for i, item in enumerate(val):
                if isinstance(item, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                    out.append((f"{key}[{i}]", item))
    return out


def _is_drop(v) -> bool:
    return type(v).__name__ == "DropVar"


class _Walker:
    """One forward interval pass over a (closed) jaxpr."""

    def __init__(self, closed, in_vals, *, onehot_sums=True,
                 root=_REPO_ROOT):
        self.onehot_sums = onehot_sums
        self.root = root
        self.findings: list = []
        self.sites: list = []
        self.checked_ops = 0
        self.n_eqns = 0
        self.out = self._walk(closed, list(in_vals), "", report=True)

    # -- source attribution ---------------------------------------------
    def _src(self, eqn, skip_rng=False):
        tb = getattr(eqn.source_info, "traceback", None)
        frames = getattr(tb, "frames", None) if tb is not None else None
        rng_file = os.path.join("engine", "rng.py")
        for fr in frames or ():
            fn = getattr(fr, "file_name", "")
            if fn.startswith(self.root):
                if skip_rng and fn.endswith(rng_file):
                    # lane sites cite the DRAW SITE (Draw's caller),
                    # not the cipher plumbing inside rng.py
                    continue
                return os.path.relpath(fn, self.root), int(fr.line_num)
        return None, 0

    # -- the walk -------------------------------------------------------
    def _walk(self, closed, in_vals, path, report):
        jaxpr = _unclose(closed)
        if len(in_vals) != len(jaxpr.invars):
            raise ValueError(
                f"{len(in_vals)} abstract values for "
                f"{len(jaxpr.invars)} invars at {path or '<top>'}"
            )
        env, defs = {}, {}
        for v, a in zip(jaxpr.invars, in_vals):
            env[v] = a
        consts = getattr(closed, "consts", None) or []
        for v, c in zip(jaxpr.constvars, consts):
            env[v] = _from_concrete(c)
        for v in jaxpr.constvars[len(consts):]:
            env[v] = _top_for(v)
        level = (jaxpr, env, defs, path)

        for idx, eqn in enumerate(jaxpr.eqns):
            if report:
                self.n_eqns += 1
            ivals = [self._read(env, v) for v in eqn.invars]
            name = eqn.primitive.name
            epath = f"{path}eqns[{idx}]"
            outs = None

            if name == "cond":
                branches = eqn.params["branches"]
                op_vals = ivals[1:]
                per = [
                    self._walk(
                        br, list(op_vals), f"{epath}.branch{bi}.", report
                    )
                    for bi, br in enumerate(branches)
                ]
                outs = [
                    _join_many([b[i] for b in per])
                    for i in range(len(eqn.outvars))
                ]
            elif name == "scan":
                outs = self._scan(eqn, ivals, epath, report)
            elif name == "while":
                outs = self._while(eqn, ivals, epath, report)
            elif name == "jit" and eqn.params.get("name") == _rng.LANE_SITE_NAME:
                outs = self._lane_site(eqn, ivals, epath, report)
            elif name == "shard_map":
                sub = eqn.params.get("jaxpr")
                if sub is not None:
                    sub_o = _unclose(sub)
                    if len(sub_o.invars) == len(ivals):
                        outs = self._walk(
                            sub, ivals, f"{epath}.shard_map.", report
                        )
                        if len(outs) != len(eqn.outvars):
                            outs = None
                if outs is None:
                    outs = [
                        _top_for(v, _union_tags(ivals)) for v in eqn.outvars
                    ]
            else:
                subs = _sub_jaxprs(eqn.params)
                if name in _CALL_PRIMS and len(subs) == 1:
                    key, sub = subs[0]
                    if len(_unclose(sub).invars) == len(ivals):
                        outs = self._walk(
                            sub, ivals, f"{epath}.{name}.", report
                        )
                        if len(outs) > len(eqn.outvars):
                            outs = outs[: len(eqn.outvars)]
                        elif len(outs) < len(eqn.outvars):
                            outs = None
                if outs is None:
                    outs = self._transfer(
                        eqn, ivals, epath, report, level
                    )

            for v, a in zip(eqn.outvars, outs):
                if _is_drop(v):
                    continue
                env[v] = a
                defs[v] = idx

        return [self._read(env, v) for v in jaxpr.outvars]

    def _read(self, env, v):
        if isinstance(v, jax_core.Literal):
            return _from_concrete(v.val)
        return env.get(v, AVal(None, None))

    # -- loops ----------------------------------------------------------
    def _scan(self, eqn, ivals, epath, report):
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        body = eqn.params["jaxpr"]
        consts = ivals[:nc]
        carry0 = ivals[nc : nc + ncar]
        # xs enter the body one leading-axis element at a time: the
        # interval is unchanged, the exact constant is not (shape)
        xs = [dataclasses.replace(a, const=None) for a in ivals[nc + ncar :]]
        carry = self._fixpoint(
            body, consts, carry0, xs, f"{epath}.body."
        )
        outs = self._walk(
            body, consts + carry + xs, f"{epath}.body.", report
        )
        final_carry = [_join(c0, o) for c0, o in zip(carry0, outs[:ncar])]
        ys = [dataclasses.replace(a, const=None) for a in outs[ncar:]]
        return final_carry + ys

    def _while(self, eqn, ivals, epath, report):
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond_j = eqn.params["cond_jaxpr"]
        body_j = eqn.params["body_jaxpr"]
        cconst = ivals[:cn]
        bconst = ivals[cn : cn + bn]
        carry0 = ivals[cn + bn :]
        carry = self._fixpoint(
            body_j, bconst, carry0, [], f"{epath}.body."
        )
        self._walk(cond_j, cconst + carry, f"{epath}.cond.", report)
        outs = self._walk(
            body_j, bconst + carry, f"{epath}.body.", report
        )
        return [_join(c0, o) for c0, o in zip(carry0, outs)]

    def _fixpoint(self, body, consts, carry0, xs, path):
        body_o = _unclose(body)
        n = len(carry0)
        drs = [
            _dtype_range(v.aval.dtype)
            for v in body_o.invars[len(consts) : len(consts) + n]
        ]
        carry = list(carry0)
        for it in range(_MAX_ITERS):
            outs = self._walk(body, consts + carry + xs, path, report=False)
            stable = True
            new = []
            for c, o, dr in zip(carry, outs[:n], drs):
                j = _narrow(_join(c, o), c.contract)
                if it >= _WIDEN_AFTER:
                    j = _widen(j, c, dr)
                if j.key() != c.key():
                    stable = False
                new.append(j)
            carry = new
            if stable:
                break
        return carry

    # -- lane sites ------------------------------------------------------
    def _lane_site(self, eqn, ivals, epath, report):
        x0, x1 = ivals[2], ivals[3]
        if report:
            purposes = None
            if x1.const is not None:
                purposes = np.asarray(x1.const).astype(np.uint64)
            self.sites.append(
                LaneSite(
                    path=epath,
                    src=self._src(eqn, skip_rng=True),
                    purposes=purposes,
                    p_lo=0 if x1.lo is None else int(x1.lo),
                    p_hi=(1 << 32) - 1 if x1.hi is None else int(x1.hi),
                    x0_lo=0 if x0.lo is None else int(x0.lo),
                    x0_hi=(1 << 32) - 1 if x0.hi is None else int(x0.hi),
                    x0_tags=tuple(sorted(x0.tags)),
                )
            )
        # the cipher is modular by definition: outputs are uniform
        # uint32 words carrying no range information and no tags
        return [AVal(0, (1 << 32) - 1) for _ in eqn.outvars]

    # -- findings --------------------------------------------------------
    def _flag(self, eqn, epath, level, math_lo, math_hi, ivals, report):
        """Record a potential-wrap finding for a tracked signed op."""
        if not report:
            return
        dt = np.dtype(eqn.outvars[0].aval.dtype)
        tags = frozenset().union(*[a.tags for a in ivals]) if ivals else frozenset()
        if not (np.issubdtype(dt, np.signedinteger) and _tracked(tags)):
            return
        lo, hi = _dtype_range(dt)
        src = self._src(eqn)
        self.findings.append(
            {
                "rule": OVERFLOW_RULE,
                "path": epath,
                "prim": eqn.primitive.name,
                "dtype": dt.name,
                "math": [math_lo, math_hi],
                "dtype_range": [lo, hi],
                "sources": sorted(t for t in tags if t.startswith(_TRACKED)),
                "file": src[0],
                "line": src[1],
                "chain": self._chain(level, eqn),
            }
        )

    def _chain(self, level, eqn, max_len=12):
        jaxpr, env, defs, path = level
        chain = []
        cur = eqn
        seen = set()
        for _ in range(max_len):
            ivals = [self._read(env, v) for v in cur.invars]
            tags = frozenset().union(*[a.tags for a in ivals]) if ivals else frozenset()
            chain.append(
                {
                    "path": f"{path}eqns[{jaxpr.eqns.index(cur)}]"
                    if cur in jaxpr.eqns else path,
                    "prim": cur.primitive.name,
                    "sources": sorted(t for t in tags if t.startswith(_TRACKED)),
                }
            )
            nxt = None
            for v, a in zip(cur.invars, ivals):
                if (
                    isinstance(v, jax_core.Var)
                    and _tracked(a.tags)
                    and v not in seen
                ):
                    nxt = v
                    break
            if nxt is None or nxt not in defs:
                break
            seen.add(nxt)
            cur = jaxpr.eqns[defs[nxt]]
        chain.reverse()
        return chain

    # -- first-order transfer functions ---------------------------------
    def _transfer(self, eqn, ivals, epath, report, level):
        name = eqn.primitive.name
        outv = eqn.outvars
        tags = _union_tags(ivals)

        def top_all():
            return [_top_for(v, tags) for v in outv]

        def one(aval: AVal):
            return [dataclasses.replace(aval, tags=aval.tags | tags)]

        def checked(mlo, mhi, const=None):
            """An arithmetic result: exact when it fits the dtype,
            wrapped (and flagged when tracked+signed) when it can't."""
            dr = _dtype_range(outv[0].aval.dtype)
            if dr[0] is None:
                return [AVal(None, None, tags)]
            if mlo is None or mhi is None:
                self._flag(eqn, epath, level, mlo, mhi, ivals, report)
                return [AVal(dr[0], dr[1], tags)]
            self.checked_ops += 1 if report and _tracked(tags) else 0
            if dr[0] <= mlo and mhi <= dr[1]:
                return [AVal(int(mlo), int(mhi), tags, const)]
            self._flag(eqn, epath, level, int(mlo), int(mhi), ivals, report)
            return [AVal(dr[0], dr[1], tags)]

        if name in ("add", "sub", "mul"):
            a, b = ivals
            op = {
                "add": lambda x, y: x + y,
                "sub": lambda x, y: x - y,
                "mul": lambda x, y: x * y,
            }[name]
            mlo, mhi = _corners(a, b, op)
            const = None
            if (
                a.const is not None and b.const is not None
                and np.asarray(a.const).size == 1
                and np.asarray(b.const).size == 1
            ):
                const = op(int(np.asarray(a.const).ravel()[0]),
                           int(np.asarray(b.const).ravel()[0]))
            return checked(mlo, mhi, const)
        if name == "neg":
            a = ivals[0]
            if a.lo is None or a.hi is None:
                return top_all()
            return checked(-a.hi, -a.lo)
        if name == "integer_pow":
            a = ivals[0]
            y = int(eqn.params["y"])
            if a.lo is None or a.hi is None or y < 0 or y > 8:
                return top_all()
            cs = [a.lo ** y, a.hi ** y] + ([0] if a.lo < 0 < a.hi else [])
            return checked(min(cs), max(cs))
        if name == "shift_left":
            a, s = ivals
            if None in (a.lo, a.hi, s.lo, s.hi) or s.lo < 0 or s.hi > 64:
                return top_all()
            m = AVal(1 << s.lo, 1 << s.hi)
            mlo, mhi = _corners(a, m, lambda x, y: x * y)
            return checked(mlo, mhi)
        if name == "cumsum":
            a = ivals[0]
            n = int(np.prod(outv[0].aval.shape)) or 1
            if a.lo is None or a.hi is None:
                return top_all()
            return checked(min(a.lo, a.lo * n), max(a.hi, a.hi * n))
        if name == "reduce_sum":
            return self._reduce_sum(eqn, ivals, epath, report, level, checked)
        if name == "scatter-add":
            op, _idx, upd = ivals
            n = int(np.prod(eqn.invars[2].aval.shape)) or 1
            if None in (op.lo, op.hi, upd.lo, upd.hi):
                return top_all()
            return checked(
                op.lo + min(0, upd.lo) * n, op.hi + max(0, upd.hi) * n
            )
        if name in ("scatter", "scatter-min", "scatter-max",
                    "dynamic_update_slice"):
            # index operands pick WHERE the update lands, not its
            # magnitude: value range = hull(operand, updates) only
            op = ivals[0]
            upd = ivals[2] if name.startswith("scatter") else ivals[1]
            return [
                AVal(
                    *_hull2(op, upd),
                    op.tags | upd.tags,
                )
            ]
        if name in ("max", "min"):
            a, b = ivals
            f = max if name == "max" else min
            if None in (a.lo, a.hi, b.lo, b.hi):
                return top_all()
            return one(AVal(f(a.lo, b.lo), f(a.hi, b.hi), tags))
        if name == "clamp":
            # clamp(a, x, c) = min(max(x, a), c), monotone in every
            # operand: the sound hull takes max-then-min per corner.
            # (A variable LOWER bound can RAISE x — ignoring a.hi here
            # would under-approximate and silently certify a wrap.)
            a, x, c = ivals

            def _mx(p, q):
                return None if p is None or q is None else max(p, q)

            def _mn(p, q):
                return None if p is None or q is None else min(p, q)

            return one(
                AVal(_mn(_mx(x.lo, a.lo), c.lo), _mn(_mx(x.hi, a.hi), c.hi),
                     tags)
            )
        if name == "select_n":
            cases = ivals[1:]
            out = cases[0]
            for c in cases[1:]:
                out = _join(out, c)
            # the predicate steers which value, not its range: implicit
            # flows are the taint walker's concern, not the interval's
            return [dataclasses.replace(out, contract=None)]
        if name == "convert_element_type":
            a = ivals[0]
            dr = _dtype_range(outv[0].aval.dtype)
            if dr[0] is None:
                return [AVal(None, None, tags)]
            if a.lo is not None and a.hi is not None and (
                dr[0] <= a.lo and a.hi <= dr[1]
            ):
                const = a.const
                return [AVal(a.lo, a.hi, tags, const)]
            return [AVal(dr[0], dr[1], tags)]
        if name in ("broadcast_in_dim", "reshape", "squeeze", "transpose",
                    "rev", "copy", "expand_dims", "stop_gradient",
                    "reduce_precision", "device_put",
                    "sharding_constraint"):
            a = ivals[0]
            const = _reshape_const(name, eqn, a.const)
            return [AVal(a.lo, a.hi, a.tags | tags, const)]
        if name == "slice":
            a = ivals[0]
            const = None
            if a.const is not None:
                try:
                    sl = tuple(
                        slice(b, e, s)
                        for b, e, s in zip(
                            eqn.params["start_indices"],
                            eqn.params["limit_indices"],
                            eqn.params["strides"]
                            or (1,) * len(eqn.params["start_indices"]),
                        )
                    )
                    const = np.asarray(a.const)[sl]
                except Exception:
                    const = None
            return [AVal(a.lo, a.hi, a.tags | tags, const)]
        if name == "concatenate":
            out = ivals[0]
            for a in ivals[1:]:
                out = _join(out, a)
            const = None
            if all(a.const is not None for a in ivals):
                try:
                    const = np.concatenate(
                        [np.asarray(a.const) for a in ivals],
                        axis=eqn.params["dimension"],
                    )
                except Exception:
                    const = None
            return [dataclasses.replace(out, tags=tags, const=const,
                                        contract=None)]
        if name == "pad":
            return one(_join(ivals[0], ivals[1]))
        if name in ("gather", "dynamic_slice"):
            # the indices pick WHICH element, not its range: only the
            # operand's magnitude (and tags) flow — implicit index
            # flows are the taint walker's jurisdiction, and tagging
            # them here would smear `time:` onto every popped value
            a = ivals[0]
            return [AVal(a.lo, a.hi, a.tags)]
        if name == "iota":
            n = int(eqn.params["shape"][eqn.params["dimension"]])
            return [AVal(0, max(0, n - 1))]
        if name in ("eq", "ne", "lt", "le", "gt", "ge", "lt_to", "le_to",
                    "is_finite", "reduce_and", "reduce_or"):
            return [AVal(0, 1, tags)]
        if name == "not":
            if np.dtype(outv[0].aval.dtype) == np.bool_:
                return [AVal(0, 1, tags)]
            return top_all()
        if name in ("and", "or", "xor"):
            a, b = ivals
            if np.dtype(outv[0].aval.dtype) == np.bool_:
                return [AVal(0, 1, tags)]
            if (
                a.lo is not None and b.lo is not None
                and a.lo >= 0 and b.lo >= 0
                and a.hi is not None and b.hi is not None
            ):
                if name == "and":
                    return [AVal(0, min(a.hi, b.hi), tags)]
                bits = max(int(a.hi).bit_length(), int(b.hi).bit_length())
                return [AVal(0, (1 << bits) - 1, tags)]
            return top_all()
        if name == "shift_right_logical":
            a, s = ivals
            if None in (a.lo, a.hi, s.lo, s.hi) or a.lo < 0:
                return top_all()
            return [AVal(a.lo >> min(s.hi, 64), a.hi >> max(s.lo, 0), tags)]
        if name == "shift_right_arithmetic":
            a, s = ivals
            if None in (a.lo, a.hi, s.lo, s.hi) or s.lo < 0:
                return top_all()
            cs = [a.lo >> s.lo, a.lo >> min(s.hi, 64),
                  a.hi >> s.lo, a.hi >> min(s.hi, 64)]
            return [AVal(min(cs), max(cs), tags)]
        if name == "div":
            a, b = ivals
            if None in (a.lo, a.hi, b.lo, b.hi) or (b.lo <= 0 <= b.hi):
                return top_all()
            cs = [_trunc_div(x, y) for x in (a.lo, a.hi)
                  for y in (b.lo, b.hi)]
            return one(AVal(min(cs), max(cs), tags))
        if name == "rem":
            a, b = ivals
            if None in (b.lo, b.hi) or (b.lo <= 0 <= b.hi):
                return top_all()
            m = max(abs(b.lo), abs(b.hi)) - 1
            if a.lo is not None and a.lo >= 0:
                hi = m if a.hi is None else min(a.hi, m)
                return [AVal(0, hi, tags)]
            return [AVal(-m, m, tags)]
        if name in ("reduce_min", "reduce_max", "cummax", "cummin", "sort"):
            return [
                AVal(a.lo, a.hi, a.tags | tags)
                for a in (ivals if name == "sort" else [ivals[0]])
            ][: len(outv)] or top_all()
        if name in ("argmin", "argmax"):
            # the result is a POSITION in [0, n): its magnitude carries
            # nothing of the operand's value range (the operand's
            # influence is an implicit flow, the taint walker's beat)
            axes = eqn.params.get("axes", ())
            shape = eqn.invars[0].aval.shape
            n = max((int(shape[ax]) for ax in axes), default=1)
            return [AVal(0, max(0, n - 1))]
        if name == "abs":
            a = ivals[0]
            if a.lo is None or a.hi is None:
                return top_all()
            lo = 0 if a.lo < 0 else a.lo
            return one(AVal(lo, max(abs(a.lo), abs(a.hi)), tags))
        if name == "sign":
            return [AVal(-1, 1, tags)]
        if name == "population_count":
            return [AVal(0, 64, tags)]
        if name == "clz":
            return [AVal(0, 64, tags)]
        if name == "optimization_barrier":
            return [dataclasses.replace(a, contract=None) for a in ivals]
        # unknown primitive: conservative top (full dtype range for
        # integers, unbounded for floats), tags flow through
        return top_all()

    def _reduce_sum(self, eqn, ivals, epath, report, level, checked):
        a = ivals[0]
        axes = eqn.params.get("axes", ())
        shape = eqn.invars[0].aval.shape
        n = 1
        for ax in axes:
            n *= int(shape[ax])
        n = max(n, 1)
        if a.lo is None or a.hi is None:
            return [_top_for(eqn.outvars[0], a.tags)]
        if self.onehot_sums:
            # the masked-sum pick idiom: sum(where(m, x, 0)) with the
            # mask one-hot by cumsum-rank construction — modeled as a
            # pick (hull with 0) instead of n*x. See the module
            # docstring's trust statement.
            picked = self._onehot_operand(eqn, level)
            if picked is not None:
                lo = min(0, picked.lo if picked.lo is not None else 0)
                hi = max(0, picked.hi if picked.hi is not None else 0)
                if picked.lo is None or picked.hi is None:
                    return [_top_for(eqn.outvars[0], a.tags | picked.tags)]
                return [AVal(lo, hi, a.tags | picked.tags)]
        return checked(min(a.lo * n, a.lo), max(a.hi * n, a.hi))

    def _onehot_operand(self, eqn, level):
        """If the summed operand is ``where(m, x, 0)`` (a jit-wrapped
        select_n with a zero case), return x's abstract value."""
        jaxpr, env, defs, _path = level
        v = eqn.invars[0]
        if not isinstance(v, jax_core.Var) or v not in defs:
            return None
        d = jaxpr.eqns[defs[v]]
        if d.primitive.name == "jit" and d.params.get("name") == "_where":
            cases = d.invars[1:]
        elif d.primitive.name == "select_n":
            cases = d.invars[1:]
        else:
            return None
        vals = [self._read(env, c) for c in cases]
        zero = [
            i for i, (c, a) in enumerate(zip(cases, vals))
            if _is_zero(c, a)
        ]
        if len(zero) != 1 or len(vals) != 2:
            return None
        return vals[1 - zero[0]]


_CALL_PRIMS = frozenset({
    "jit", "closed_call", "core_call", "remat", "remat2", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
})


def _hull2(a: AVal, b: AVal):
    lo = None if a.lo is None or b.lo is None else min(a.lo, b.lo)
    hi = None if a.hi is None or b.hi is None else max(a.hi, b.hi)
    return lo, hi


def _union_tags(ivals):
    return frozenset().union(*[a.tags for a in ivals]) if ivals else frozenset()


def _join_many(vals):
    out = vals[0]
    for v in vals[1:]:
        out = _join(out, v)
    return dataclasses.replace(out, contract=None)


def _widen(j: AVal, prev: AVal, dr) -> AVal:
    """Threshold widening: a contract acts as the first threshold (the
    narrowing step applies it before this runs); a bound still
    unstable here jumps straight to the dtype bound — monotone over a
    finite chain, so fixpoints always terminate."""
    lo, hi = j.lo, j.hi
    if prev.lo is not None and (lo is None or lo < prev.lo):
        lo = dr[0]
    if prev.hi is not None and (hi is None or hi > prev.hi):
        hi = dr[1]
    return dataclasses.replace(j, lo=lo, hi=hi, const=None)


def _reshape_const(name, eqn, const):
    if const is None:
        return None
    try:
        arr = np.asarray(const)
        if name == "broadcast_in_dim":
            shape = eqn.params["shape"]
            bdims = tuple(eqn.params["broadcast_dimensions"])
            tmp = [1] * len(shape)
            for i, d in enumerate(bdims):
                tmp[d] = arr.shape[i]
            out = np.broadcast_to(np.reshape(arr, tmp), shape)
            return out if out.size <= _CONST_MAX else None
        if name == "reshape":
            return np.reshape(arr, eqn.params["new_sizes"])
        if name == "squeeze":
            return np.squeeze(arr, axis=tuple(eqn.params["dimensions"]))
        if name == "transpose":
            return np.transpose(arr, eqn.params["permutation"])
        if name == "rev":
            return np.flip(arr, axis=tuple(eqn.params["dimensions"]))
        if name in ("copy", "stop_gradient", "reduce_precision",
                    "expand_dims", "device_put", "sharding_constraint"):
            return arr
    except Exception:
        return None
    return None


def _is_zero(var, aval: AVal) -> bool:
    if isinstance(var, jax_core.Literal):
        try:
            return float(np.asarray(var.val).ravel()[0]) == 0.0
        except Exception:
            return False
    return aval.lo == 0 and aval.hi == 0


def _trunc_div(a, b):
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b >= 0) else -q


# ---------------------------------------------------------------------------
# Entry points.
# ---------------------------------------------------------------------------


def analyze_intervals(closed, in_vals, *, onehot_sums=True) -> _Walker:
    """Run one interval pass over a (closed) jaxpr.

    ``in_vals`` is one :class:`AVal` per invar. Returns the walker,
    whose ``out`` holds per-outvar abstract values and whose
    ``findings``/``sites`` hold the raw overflow findings and threefry
    lane sites (pragma filtering is the caller's job — tests use this
    raw form directly)."""
    return _Walker(closed, in_vals, onehot_sums=onehot_sums)


def check_lane_sites(sites) -> list:
    """The lane-disjointness obligations over recorded threefry sites."""
    findings = []

    def _f(msg, involved):
        findings.append(
            {
                "rule": LANE_RULE,
                "message": msg,
                "sites": [s.describe() for s in involved],
                "file": involved[0].src[0],
                "line": involved[0].src[1],
                "paths": [s.path for s in involved],
            }
        )

    resolved = []
    for s in sites:
        pset = s.purpose_set()
        if pset is not None:
            if len(pset) != np.asarray(s.purposes).size:
                _f(
                    "one site draws the same purpose twice in one block "
                    "— identical cipher values, correlated lanes",
                    [s],
                )
            lanes = {}
            for p in pset:
                ln = _rng.lane_of(p)
                if ln is None:
                    _f(
                        f"purpose {p:#x} lies in unassigned space — "
                        f"register a PURPOSE_LANES block (engine/rng.py)",
                        [s],
                    )
                else:
                    lanes.setdefault(ln.name, set()).add(p)
            resolved.append((s, pset, lanes))
        else:
            ln_lo = _rng.lane_of(s.p_lo)
            ln_hi = _rng.lane_of(s.p_hi)
            if ln_lo is None or ln_lo is not ln_hi:
                _f(
                    f"dynamic purpose interval [{s.p_lo:#x}, {s.p_hi:#x}] "
                    f"is not contained in one registered lane — the draw "
                    f"cannot be proven disjoint",
                    [s],
                )
            resolved.append((s, None, {ln_lo.name: set()} if ln_lo else {}))

    for i in range(len(resolved)):
        for j in range(i + 1, len(resolved)):
            a, pa, _la = resolved[i]
            b, pb, _lb = resolved[j]
            if _branch_exclusive(a.path, b.path):
                continue
            if a.x0_hi < b.x0_lo or b.x0_hi < a.x0_lo:
                continue  # counters can never coincide
            shared = _shared_purposes(a, pa, b, pb)
            if shared:
                shown = ", ".join(
                    f"{p:#x}" for p in sorted(shared)[:6]
                ) if isinstance(shared, set) else shared
                _f(
                    f"two live draw sites share purpose lane(s) {shown} "
                    f"at overlapping counters — the streams are "
                    f"IDENTICAL, not independent",
                    [a, b],
                )
    return findings


def _shared_purposes(a, pa, b, pb):
    if pa is not None and pb is not None:
        return pa & pb
    ia = (a.p_lo, a.p_hi)
    ib = (b.p_lo, b.p_hi)
    if pa is not None:
        hit = {p for p in pa if ib[0] <= p <= ib[1]}
        return hit
    if pb is not None:
        return {p for p in pb if ia[0] <= p <= ia[1]}
    lo = max(ia[0], ib[0])
    hi = min(ia[1], ib[1])
    return f"[{lo:#x}, {hi:#x}]" if lo <= hi else None


# ---------------------------------------------------------------------------
# Pragma plumbing (the checked allowlist, extended to jaxpr findings).
# ---------------------------------------------------------------------------


_PRAGMA_CACHE: dict = {}


def _file_pragmas(rel_path, root=_REPO_ROOT):
    key = (root, rel_path)
    if key not in _PRAGMA_CACHE:
        entries = []
        full = Path(root) / rel_path
        try:
            entries = _pragma_entries(full.read_text(encoding="utf-8"))
        except OSError:
            pass
        _PRAGMA_CACHE[key] = entries
    return _PRAGMA_CACHE[key]


def _apply_pragmas(findings, root=_REPO_ROOT):
    """Split raw findings into (kept, allowed, used-pragma keys)."""
    kept, allowed, used = [], [], set()
    for f in findings:
        rel, line = f.get("file"), f.get("line", 0)
        suppressed = False
        if rel:
            for p in _file_pragmas(rel, root):
                if line in p["covers"] and f["rule"] in p["rules"]:
                    used.add((rel, p["anchor"], f["rule"]))
                    suppressed = True
        (allowed if suppressed else kept).append(f)
    return kept, allowed, used


def absint_pragma_inventory(paths=None, root=None) -> list:
    """Every ``absint-*`` pragma on the lint surface, as
    ``(repo-relative path, anchor line, rule)`` tuples."""
    root = Path(root) if root else Path(_REPO_ROOT)
    out = []
    targets = paths if paths is not None else [
        root / p for p in DEFAULT_PATHS if (root / p).exists()
    ]
    files = []
    for p in targets:
        p = Path(p)
        files.extend(sorted(p.rglob("*.py")) if p.is_dir() else [p])
    for f in files:
        if "__pycache__" in f.parts:
            continue
        try:
            entries = _pragma_entries(f.read_text(encoding="utf-8"))
        except OSError:
            continue
        try:
            rel = str(f.relative_to(root))
        except ValueError:
            rel = str(f)
        for p in entries:
            for rule in sorted(p["rules"]):
                if rule.startswith("absint-"):
                    out.append((rel, p["anchor"], rule))
    return out


def stale_absint_pragmas(used, paths=None, root=None) -> list:
    """Inventory minus exercised: each stale entry is a finding, the
    ``unused-allow`` rule applied to this analysis. Judged against the
    set of proofs the CALLER ran — the repo gates run the full lowering
    sweep for at least one model, which exercises every in-engine
    pragma site."""
    used = set(used)
    stale = []
    for rel, line, rule in absint_pragma_inventory(paths, root):
        if (rel, line, rule) not in used:
            stale.append(
                {
                    "rule": "unused-allow",
                    "file": rel,
                    "line": line,
                    "message": (
                        f"pragma allows [{rule!r}] but no traced program "
                        f"exercised it — stale allowlist entry"
                    ),
                }
            )
    return stale


# ---------------------------------------------------------------------------
# The provers over real engine programs.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AbsintReport:
    """Verdict of one range proof over a traced (wl, cfg, flags)."""

    workload: str
    config_hash: str
    entry: str
    flags: dict
    horizon_ns: int
    findings: list  # unsuppressed finding dicts (overflow + lane)
    allowed: list  # pragma-suppressed findings (the allowlist in use)
    used_pragmas: list  # sorted (file, line, rule) keys
    lane_sites: list  # site descriptions
    lanes: list  # sorted names of registry lanes with live draws
    n_eqns: int
    checked_ops: int

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    def summary(self) -> str:
        what = (
            f"{self.workload} [{self.entry}] flags="
            f"{{{', '.join(f'{k}={v}' for k, v in sorted(self.flags.items()) if v)}}}"
        )
        if self.ok:
            return (
                f"OK   {what}: {self.n_eqns} eqns, {self.checked_ops} "
                f"tracked ops in range, {len(self.lane_sites)} threefry "
                f"site(s) over lanes {{{', '.join(self.lanes)}}} disjoint"
                + (f", {len(self.allowed)} allowlisted" if self.allowed else "")
            )
        lines = [f"FAIL {what}: {len(self.findings)} finding(s)"]
        for f in self.findings:
            if f["rule"] == OVERFLOW_RULE:
                lines.append(
                    f"  {OVERFLOW_RULE} {f['file']}:{f['line']} "
                    f"{f['prim']}:{f['dtype']} math={f['math']} "
                    f"exceeds {f['dtype_range']} (sources {f['sources']})"
                )
                for hop in f["chain"]:
                    lines.append(
                        f"    via {hop['path']}:{hop['prim']} "
                        f"(sources {hop['sources']})"
                    )
            else:
                lines.append(f"  {f['rule']}: {f['message']}")
                for s in f.get("sites", []):
                    lines.append(f"    site {s}")
        return "\n".join(lines)


def check_ranges(
    wl: Workload,
    cfg: EngineConfig,
    build: Build = BASE_LOWERING,
    *,
    entry: str = "step",
    horizon_ns: int | None = None,
    n_steps: int = 4,
    n_seeds: int = 2,
    mutate=None,
    onehot_sums: bool = True,
) -> AbsintReport:
    """Prove (or refute) overflow-freedom + lane disjointness for one
    build. ``entry="step"`` walks the single-seed step with inputs
    seeded at the column contracts; ``entry="run"`` walks the vmapped
    ``make_run`` scan (the loop-carry fixpoint path, carries narrowed
    to their contracts — the assume-guarantee boundary). ``mutate``
    wraps the traced function, the planted-mutant hook shared with the
    taint proof."""
    flags = report_flags(build)
    init = make_init(wl, cfg, build)
    state = init(np.zeros(max(n_seeds, 1), np.uint64))
    if entry == "step":
        fn = make_step(wl, cfg, build)
        template = jax.tree.map(lambda a: a[0], state)
    elif entry == "run":
        fn = make_run(wl, cfg, n_steps, build)
        template = state
    else:
        raise ValueError(f"unknown entry {entry!r} (step or run)")
    if mutate is not None:
        fn = mutate(fn)

    with _rng.lane_site_tracing():
        closed = jax.make_jaxpr(fn)(template)

    names = _leaf_names(template)
    contracts = column_contracts(
        wl, cfg, time32=bool(build.time32), horizon_ns=horizon_ns
    )
    in_vals = []
    for name, var in zip(names, closed.jaxpr.invars):
        dr = _dtype_range(var.aval.dtype)
        cc = contracts.get(name)
        if cc is None or dr[0] is None:
            in_vals.append(AVal(dr[0], dr[1]))
            continue
        lo, hi = max(cc.lo, dr[0]), min(cc.hi, dr[1])
        tags = frozenset({f"{cc.family}:{name}"}) if cc.family else frozenset()
        in_vals.append(AVal(lo, hi, tags, None, (lo, hi)))

    walker = analyze_intervals(closed, in_vals, onehot_sums=onehot_sums)
    raw = walker.findings + check_lane_sites(walker.sites)
    kept, allowed, used = _apply_pragmas(raw)
    live = set()
    for s in walker.sites:
        pset = s.purpose_set()
        if pset is None:
            ln = _rng.lane_of(s.p_lo)
            if ln is not None:
                live.add(ln.name)
        else:
            for p in pset:
                ln = _rng.lane_of(p)
                if ln is not None:
                    live.add(ln.name)
    h = horizon_ns if horizon_ns is not None else (
        cfg.time_limit_ns or ABSINT_HORIZON_NS
    )
    return AbsintReport(
        workload=wl.name,
        config_hash=cfg.hash(),
        entry=entry,
        flags=flags,
        horizon_ns=int(h),
        findings=kept,
        allowed=allowed,
        used_pragmas=sorted(used),
        lane_sites=[s.describe() for s in walker.sites],
        lanes=sorted(live),
        n_eqns=walker.n_eqns,
        checked_ops=walker.checked_ops,
    )


# ---------------------------------------------------------------------------
# Planted positive controls.
# ---------------------------------------------------------------------------


def plant_time32_sentinel_decay(step_fn):
    """Re-create the PR-13 time32 sentinel-decay bug class as a mutant.

    The carried ``tile_min`` of an EMPTY tile holds the +inf sentinel;
    the clean step re-masks empty tiles to a FRESH sentinel before any
    arithmetic touches them. This mutant applies the per-step rebase to
    the carried column directly — the decayed sentinel keeps shrinking
    and, once the accumulated advance exceeds the int32 range
    (~2.1 sim-seconds), the subtraction wraps: exactly the silent
    divergence the PR-13 review caught. Value-plausible (each single
    step is in range), invisible to one-shot runtime checks — and a
    certain catch for the interval prover, whose finding cites the
    ``time:tile_min`` chain at THIS (un-pragma'd) site."""
    import jax.numpy as jnp

    def mutant(st):
        out = step_fn(st)
        if out.tile_min.ndim != 1 or out.tile_min.shape[0] == 0:
            raise ValueError(
                "plant_time32_sentinel_decay needs a step built with "
                "pool_index=True (the tile summary columns)"
            )
        if out.tile_min.dtype != jnp.int32:
            raise ValueError(
                "plant_time32_sentinel_decay is a time32 mutant: the "
                "decay wrap exists only in the int32 offset form"
            )
        adv = (out.now - st.now).astype(jnp.int32)
        return dataclasses.replace(out, tile_min=st.tile_min - adv)

    return mutant


def plant_lane_collision(step_fn):
    """Plant a threefry draw that re-uses the engine's first per-emit
    latency lane (``PURPOSE_LATENCY + 0``) at the same ``(seed, step)``
    counter. The value is folded into the trace hash xor-masked to
    zero, so the mutant is value-identical on every input — no runtime
    test can see it — yet the two draw sites now share a live
    ``(purpose, counter)`` lane: the stream the handler thinks is
    independent is bit-for-bit the engine's latency draw."""
    import jax.numpy as jnp

    from ..engine.rng import PURPOSE_LATENCY, Draw

    def mutant(st):
        out = step_fn(st)
        d = Draw(st.seed, st.step)
        x = d.bits(PURPOSE_LATENCY + 0)
        poison = x.astype(jnp.uint64) & jnp.uint64(0)
        return dataclasses.replace(out, trace=out.trace ^ poison)

    return mutant


def run_mutant_controls() -> list:
    """Run both planted positive controls against the canonical small
    raft/record build and judge them: returns
    ``[(name, report, caught), ...]`` — THE one declaration of the
    control recipe, shared by tools/lint_soak.py cert 5,
    tools/absint_soak.py cert 2 and the test suite, so the catch
    criteria cannot drift between gates."""
    from ..models import make_raft

    wl = make_raft(record=True)
    cfg = EngineConfig(
        pool_size=40, loss_p=0.02, clog_backoff_max_ns=2_000_000_000
    )
    rep_sd = check_ranges(
        wl, cfg, Build(layout="scatter", time32=True, pool_index=True),
        entry="step", mutate=plant_time32_sentinel_decay,
    )
    caught_sd = not rep_sd.ok and any(
        f["rule"] == OVERFLOW_RULE
        and any(t.endswith("tile_min") for t in f["sources"])
        and f["chain"]
        for f in rep_sd.findings
    )
    rep_lc = check_ranges(
        wl, cfg, entry="step", mutate=plant_lane_collision,
    )
    caught_lc = not rep_lc.ok and any(
        f["rule"] == LANE_RULE and len(f.get("sites", [])) == 2
        for f in rep_lc.findings
    )
    return [
        ("time32-sentinel-decay", rep_sd, caught_sd),
        ("lane-collision", rep_lc, caught_lc),
    ]


# ---------------------------------------------------------------------------
# The certified matrix.
# ---------------------------------------------------------------------------

# absint build axes: "base" is the lean program, "dup" compiles the
# duplication shadow lanes (the dup purpose block goes live), "all"
# turns every observability tap on (the widest arithmetic surface —
# timeline/latency/metrics each add tracked adds).
ABSINT_AXES = {
    "base": {},
    "dup": dict(dup_rows=True),
    # the causal-provenance counters (ISSUE 19): the Lamport fold
    # (max + 1 per dispatch) and the int32 dispatch-sequence stamp both
    # grow with the step count, so their overflow-freedom rests on the
    # step-budget contract (column_contracts bounds lam and seq by
    # ABSINT_STEP_MAX) — this row makes the prover actually walk that
    # arithmetic rather than trusting the bound.
    "causal": dict(causal=True, timeline_cap=8),
    "all": dict(
        metrics=True, timeline_cap=8, cov_words=8, cov_hitcount=True,
        latency=LatencySpec(ops=8, phases=2), causal=True,
    ),
}


def absint_model_matrix() -> list:
    """(tag, workload, config, horizon_ns) rows from each recorded
    model's own ``absint_entries()`` declaration (models/*.py — the
    range-entry analog of ``lint_entries``)."""
    from ..models import kvchaos, leasekv, paxos, raft, raftlog, shardkv

    entries = []
    for mod in (raft, kvchaos, paxos, raftlog, leasekv, shardkv):
        for tag, wl, cfg_kw, horizon in mod.absint_entries():
            entries.append((tag, wl, EngineConfig(**cfg_kw), horizon))
    return entries


def absint_matrix(
    models=None,
    axes=None,
    layouts: tuple = LAYOUT_AXES,
    *,
    entry: str = "step",
    log=None,
    onehot_sums: bool = True,
) -> list:
    """Run the range proof over a model x build-flag x lowering matrix.

    ``layouts`` takes the same :class:`Build` lowerings as
    ``noninterference.check_matrix`` (``LAYOUT_AXES``, the default, is
    the full set); ineligible (model, lowering) pairs are skipped, not
    failed."""
    if models is not None and not models:
        raise ValueError("absint_matrix: models is empty")
    reports = []
    for tag, wl, cfg, horizon in (
        models if models is not None else absint_model_matrix()
    ):
        for lowering in layouts:
            if not lowering_eligible(lowering, wl, cfg):
                continue
            for axis, fl in (axes or ABSINT_AXES).items():
                build, kw = split_axis(lowering, fl)
                rep = check_ranges(
                    wl, cfg, build, entry=entry, horizon_ns=horizon,
                    onehot_sums=onehot_sums, **kw,
                )
                rep.flags["axis"] = axis
                rep.workload = tag
                if log is not None:
                    log(rep.summary())
                reports.append(rep)
    return reports
