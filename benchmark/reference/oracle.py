"""ctypes bridge to the benchmark's plain reference simulator.

Imports nothing of madsim_tpu: the deployment comes from the
configuration file, the fault plan is compiled here from its parameters
(``plan.py``). The library is built from ``oracle.cpp`` (the engine) and
every ``workloads/*.cpp`` (one workload each, registered under its own
name) into ``.build/`` of the benchmark's directory. Its file name
carries a hash of every source, so an added or changed file is built
anew and a build is paid once per checkout.

A configuration's ``reference.workload`` names ``workloads/<name>.cpp``
and ``workloads/<name>.py``; the latter's ``pack(params)`` gives the
configuration's ``reference.params`` in the order the former reads them.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess

import numpy as np

from harness import BENCH, BenchError
from reference import named

_CXX = ["g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-fvisibility=hidden"]
_I64P = ctypes.POINTER(ctypes.c_int64)
_I32P = ctypes.POINTER(ctypes.c_int32)
_libs: dict[str, ctypes.CDLL] = {}
_REFUSED = {1: "no workload is registered under that name",
            2: "the plan has more rows than the pool holds",
            3: "a plan row has a user kind",
            4: "the workload does not take that many params",
            5: "its payload of {0} words is wider than the {1} words the "
               "reference holds",
            6: "its durable column {0} lies outside the state row"}


def _sources(bench: str) -> list[str]:
    ref = os.path.join(bench, "reference")
    return ([os.path.join(ref, "sim.h"), os.path.join(ref, "oracle.cpp")]
            + sorted(glob.glob(os.path.join(ref, "workloads", "*.cpp"))))


def _load(bench: str) -> ctypes.CDLL:
    """The library built from ``bench``'s sources, built if need be."""
    srcs = _sources(bench)
    h = hashlib.sha256(" ".join(_CXX).encode())
    for p in srcs:
        with open(p, "rb") as f:
            h.update(os.path.basename(p).encode() + b"\0" + f.read() + b"\0")
    build = os.path.join(bench, ".build")
    lib_path = os.path.join(build, f"libbenchref-{h.hexdigest()[:16]}.so")
    if lib_path not in _libs:
        if not os.path.exists(lib_path):
            os.makedirs(build, exist_ok=True)
            tmp = f"{lib_path}.tmp{os.getpid()}"
            subprocess.run(_CXX + ["-o", tmp] + srcs[1:], check=True)
            os.replace(tmp, lib_path)
        lib = ctypes.CDLL(lib_path)
        lib.ref_run.restype = ctypes.c_int32
        lib.ref_run.argtypes = [
            ctypes.c_char_p, _I64P, ctypes.c_int64, ctypes.c_uint64,
            ctypes.c_int64, _I64P, ctypes.c_int64, ctypes.c_int64, _I64P,
            _I32P, _I32P, _I32P, _I32P, ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int64, _I32P, _I64P, ctypes.POINTER(ctypes.c_uint64),
            _I64P]
        _libs[lib_path] = lib
    return _libs[lib_path]


def loss_threshold(p: float) -> int:
    """Loss probability -> 32-bit threshold (p=1 drops every packet)."""
    if p <= 0.0:
        return 0
    if p >= 1.0:
        return 1 << 32
    return int(p * (1 << 32))


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


class Reference:
    """One configuration's reference: ``run(seed, plan_rows)`` -> result.

    ``time_quantum`` > 1 is the control: the same simulation with every
    scheduled event time held at that resolution. ``bench`` is the
    benchmark directory whose ``reference/`` holds the sources."""

    def __init__(self, config: dict, time_quantum: int = 1,
                 bench: str = BENCH):
        self.ref = config["reference"]
        name = self.ref["workload"]
        files = [named.path("workloads", name, ext, bench)
                 for ext in (".cpp", ".py")]
        if not all(os.path.isfile(p) for p in files):
            raise BenchError(f"no reference workload {name!r}: it needs "
                             f"both {files[0]} and {files[1]}")
        pack = named.load("workloads", name, bench).pack
        self.params = np.array(pack(self.ref["params"]), np.int64)
        self.name = name
        self.bench = bench
        self.lib = _load(bench)
        e = config["engine"]
        self.cfg = np.array([
            e["pool_size"], e["lat_min_ns"], e["lat_max_ns"],
            loss_threshold(e["loss_p"]), e["proc_min_ns"], e["proc_max_ns"],
            e["clog_backoff_min_ns"], e["clog_backoff_max_ns"],
            e["time_limit_ns"]], np.int64)
        self.max_steps = int(config["max_steps"])
        self.hist_cap = int(self.ref.get("hist_capacity", 0))
        self.quantum = int(time_quantum)

    def run(self, seed: int, plan_rows: dict | None = None) -> dict:
        """``plan_rows``: {time, kind, node, a0, a1, valid} 1-D columns."""
        rows = plan_rows or {"time": [], "kind": [], "node": [], "a0": [],
                             "a1": [], "valid": []}
        t = np.ascontiguousarray(rows["time"], np.int64)
        k = np.ascontiguousarray(rows["kind"], np.int32)
        nd = np.ascontiguousarray(rows["node"], np.int32)
        a0 = np.ascontiguousarray(rows["a0"], np.int32)
        a1 = np.ascontiguousarray(rows["a1"], np.int32)
        v = np.ascontiguousarray(rows["valid"], np.uint8)
        if not len(t) == len(k) == len(nd) == len(a0) == len(a1) == len(v):
            raise ValueError("plan row columns differ in length")
        cap = self.hist_cap
        hw = np.zeros((max(cap, 1), 5), np.int32)
        ht = np.zeros((max(cap, 1),), np.int64)
        trace = ctypes.c_uint64()
        out = np.zeros(7, np.int64)
        rc = self.lib.ref_run(
            self.name.encode(), _ptr(self.params, ctypes.c_int64),
            len(self.params), ctypes.c_uint64(int(seed) & (2**64 - 1)),
            self.max_steps, _ptr(self.cfg, ctypes.c_int64), self.quantum,
            len(t), _ptr(t, ctypes.c_int64), _ptr(k, ctypes.c_int32),
            _ptr(nd, ctypes.c_int32), _ptr(a0, ctypes.c_int32),
            _ptr(a1, ctypes.c_int32), _ptr(v, ctypes.c_uint8), cap,
            _ptr(hw, ctypes.c_int32), _ptr(ht, ctypes.c_int64),
            ctypes.byref(trace), _ptr(out, ctypes.c_int64))
        if rc != 0:
            why = _REFUSED.get(rc, f"rc {rc}").format(*out.tolist())
            raise BenchError(f"the reference refused {self.name!r}: {why}")
        n = int(out[5])
        return {"trace": trace.value, "now": int(out[0]),
                "halted": bool(out[1]), "halt_time": int(out[2]),
                "overflow": int(out[3]), "msg_count": int(out[4]),
                "hist": hw[:n].tolist(), "hist_drop": int(out[6])}
