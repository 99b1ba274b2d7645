"""Checkpoint / resume for batched simulation state.

The reference has no checkpointing — reproducibility comes from
replaying the seed (SURVEY.md §5 "Checkpoint/resume: none"). For the
batched engine a checkpoint is just the state arrays, so saving and
resuming a 65k-seed run is cheap and worth having: long chaos searches
can snapshot progress, and a snapshot plus the (workload, config) pair
deterministically resumes to the same trajectory as the uninterrupted
run (the test asserts that).

Format: a single .npz with one entry per SimState field plus a manifest
entry recording the config hash, so resuming under a different config —
which would silently change the trajectory — is rejected.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np

import jax.numpy as jnp

from .core import (
    POOL_INDEX_STATE_FIELDS,
    Build,
    EngineConfig,
    SimState,
    Workload,
    build_pool_index,
    pool_tile,
)

__all__ = ["save", "load"]

_MANIFEST_KEY = "__madsim_manifest__"
# format 2: ev_kind/ev_node/ev_src/ev_retry merged into packed ev_meta
# (core.py byte-layout note); format 3: operation-history columns
# (hist_word/hist_t/hist_count/hist_drop, madsim_tpu.check); format 4:
# extended chaos state (slow/dup/skew, madsim_tpu.chaos); format 5:
# coverage fingerprint (cov/cov_last, madsim_tpu.explore); format 6:
# observability columns (cov_hits/met/tl_*, madsim_tpu.obs); format 7:
# storage sync-discipline columns (disk/wmask/sync_loss/torn,
# madsim_tpu.chaos disk faults); format 8: the observable fsync-EIO
# window column (sync_eio, ctx.sync_err); format 9: the tail-latency
# columns (lat_inv/lat_resp/lat_hist/lat_count/lat_drop) and the
# emit-time sidecar (ev_emit/tl_emit, madsim_tpu.obs latency);
# format 10: the causal-provenance columns (lam/ev_parent/ev_lam and
# the ring's tl_seq/tl_parent/tl_lam, causal=True) — unlike the pool
# index these ACCUMULATE (a Lamport clock is history, not a pure
# function of the pool), so they are part of the format, not rebuilt
# on load. Older checkpoints are rejected with the designed mismatch
# error rather than a KeyError mid-load; format 11: the client-retry
# columns (rt_done/rt_attempt/rt_deadline, retry=RetrySpec) — CORE
# state like the causal clocks (rt_done feeds the deliver gate and the
# armed deadlines are history), zero-size off-policy so off-policy
# checkpoints stay byte-comparable modulo the empty entries.
#
# The readiness-index tile summaries (POOL_INDEX_STATE_FIELDS, ISSUE
# 13) are NOT part of the format: they are derived by construction
# (a pure function of ev_time/ev_valid — engine.build_pool_index is
# the definition), so save() skips them and load() rebuilds them for
# whatever pool_index resolution the resumed run uses.
_FORMAT = 11


def save(path: str, state: SimState, cfg: EngineConfig) -> None:
    """Write a batched SimState to ``path`` (.npz)."""
    arrays = {
        f.name: np.asarray(getattr(state, f.name))
        for f in dataclasses.fields(state)
        if f.name not in POOL_INDEX_STATE_FIELDS  # derived: rebuilt on load
    }
    # ev_time dtype records the time representation (int32 = time32
    # offset form, int64 = absolute): time32 auto-resolution depends on
    # the config *and* the builder arguments, so the config hash alone
    # can't catch a checkpoint resumed under the other representation
    manifest = json.dumps(
        {
            "format": _FORMAT,
            "config_hash": cfg.hash(),
            "ev_time_dtype": str(np.asarray(state.ev_time).dtype),
        }
    )
    arrays[_MANIFEST_KEY] = np.frombuffer(manifest.encode(), dtype=np.uint8)
    # write through a file handle so the given path is used verbatim
    # (np.savez(path_str) would append .npz and break load symmetry)
    with open(path, "wb") as fh:
        np.savez(fh, **arrays)


def load(
    path: str, wl: Workload, cfg: EngineConfig, build: Build = Build()
) -> SimState:
    """Load a SimState for a run built from ``build`` (resolved here, so
    pass the spec the run is built from); refuses a checkpoint taken
    under another config or another build.

    ``time32``: the saved ``ev_time`` dtype must be the resolved
    representation. time32 auto-resolution is platform-dependent (int32
    on accelerators when eligible, int64 on CPU), so a checkpoint saved
    on one platform can mismatch the build on another; the refusal here
    names it rather than a step-time dtype error later.

    ``pool_index``: the readiness-index tile summaries are never read
    from the file — they are REBUILT here from the loaded pool columns
    (``engine.build_pool_index``) when the resolved build carries them,
    which is what makes them derived state: the checkpoint format
    carries only ground truth.

    ``retry``: the retry columns are CORE state (armed deadlines are
    history), so a checkpoint taken under one policy shape cannot
    resume under another: a mismatch between the saved ``rt_done``
    width and ``retry.n_ops`` is refused here with the shape named,
    rather than surfacing as a jit shape error mid-resume.
    """
    b = build.resolve(wl, cfg)
    with np.load(path) as data:
        manifest = json.loads(bytes(data[_MANIFEST_KEY]).decode())
        if manifest.get("format") != _FORMAT:
            raise ValueError(f"unknown checkpoint format {manifest.get('format')}")
        if manifest["config_hash"] != cfg.hash():
            raise ValueError(
                "checkpoint was taken under a different EngineConfig "
                f"({manifest['config_hash']} != {cfg.hash()}); resuming would "
                "silently change the simulation trajectory"
            )
        fields = {
            f.name: jnp.asarray(data[f.name])
            for f in dataclasses.fields(SimState)
            if f.name not in POOL_INDEX_STATE_FIELDS
        }
    if b.pool_index:
        fields["tile_min"], fields["tile_cnt"] = build_pool_index(
            fields["ev_time"], fields["ev_valid"], pool_tile(cfg.pool_size)
        )
    else:
        s = fields["ev_valid"].shape[:-1] + (0,)
        fields["tile_min"] = jnp.zeros(s, fields["ev_time"].dtype)
        fields["tile_cnt"] = jnp.zeros(s, jnp.int32)
    state = SimState(**fields)
    saved_dt = manifest.get("ev_time_dtype", str(np.asarray(state.ev_time).dtype))
    want_dt = "int32" if b.time32 else "int64"
    if saved_dt != want_dt:
        raise ValueError(
            f"checkpoint ev_time dtype is {saved_dt} but the resumed run "
            f"resolves time32={b.time32} ({want_dt}); pass the build the "
            "checkpoint was saved under (auto-resolution is "
            "platform-dependent, so a checkpoint saved on another platform "
            "will not resume under the default)"
        )
    saved_ops = int(np.asarray(state.rt_done).shape[-1])
    retry = b.retry
    want_ops = 0 if retry is None else int(retry.n_ops)
    if saved_ops != want_ops:
        raise ValueError(
            f"checkpoint carries retry columns for {saved_ops} ops but the "
            f"resumed run declared "
            f"{'no retry policy' if retry is None else f'retry.n_ops={want_ops}'}"
            "; armed retry deadlines are core state, so resume with the "
            "checkpoint's own RetrySpec (or an off-policy checkpoint "
            "off-policy) — pass a Build with the matching retry here and "
            "to the run"
        )
    return state
