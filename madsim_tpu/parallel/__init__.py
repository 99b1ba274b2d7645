"""Seed-axis sharding over TPU device meshes.

The reference scales by running `MADSIM_TEST_NUM` seeds across
`MADSIM_TEST_JOBS` OS threads, one runtime per thread (reference
madsim/src/sim/runtime/builder.rs:110-148). The TPU-native scaling axis
is the same logical thing mapped to hardware: the seed batch is sharded
over a `jax.sharding.Mesh`, every chip advances its shard of seeds in
lockstep, and XLA inserts zero collectives in the hot loop because the
work is embarrassingly parallel along the seed axis — ICI/DCN are only
touched when results are gathered.

A 2D ('host', 'chip') mesh mirrors the DCN x ICI hierarchy: the seed
axis is sharded over both, so placement composes with multi-host
deployments the way data parallelism does in the scaling playbook.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.core import Build


def shard_map_nocheck(fn, mesh, in_specs, out_specs):
    """``shard_map`` with the replication checker off — the repo's one
    spelling of the pattern (handler branches legitimately mix
    mesh-constant emits with shard-varying values, which the varying-
    axes checker rejects; correctness is asserted value-wise by the
    sharded == unsharded tests instead). Returns the unjitted mapped
    function; callers jit it themselves."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


__all__ = [
    "make_mesh",
    "merge_coverage",
    "merge_latency",
    "merge_metrics",
    "merge_verdicts",
    "seed_sharding",
    "shard_map_nocheck",
    "shard_state",
    "shard_over_seeds",
    "shard_run_compacted",
]


def make_mesh(devices=None, hosts: int | None = None) -> Mesh:
    """Build a ('host', 'chip') mesh over the given (default: all) devices.

    ``hosts`` defaults to the actual process/host count when running
    multi-host, else 1; the remaining factor becomes the chip axis.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if hosts is None:
        hosts = getattr(jax, "process_count", lambda: 1)()
        if n % hosts != 0:
            hosts = 1
    grid = np.asarray(devices).reshape(hosts, n // hosts)
    return Mesh(grid, axis_names=("host", "chip"))


def seed_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding that splits the leading (seed) axis across every mesh axis."""
    return NamedSharding(mesh, P(mesh.axis_names))


def shard_state(state, mesh: Mesh):
    """Place a batched SimState so its seed axis is split across the mesh.

    Every leaf of the state pytree has seeds leading, so one sharding
    applies uniformly.
    """
    sh = seed_sharding(mesh)
    return jax.device_put(state, sh)


def shard_over_seeds(fn, mesh: Mesh):
    """Compile ``fn(state) -> state`` with the seed axis sharded over ``mesh``.

    ``shard_map`` runs ``fn`` on each device's shard of independent
    simulations, with no cross-device communication (an early-exit loop
    exits per shard). Seeds are independent rows, so the result equals
    the unsharded run. Left to the partitioner instead
    (``jit(fn, in_shardings=..., out_shardings=...)``), the TPU compiler
    of libtpu 0.0.34 aborts on the engine's nested calls.
    """
    # a single spec is a valid pytree prefix: it covers every leaf of
    # the SimState, all of which lead with the seed axis
    spec = P(mesh.axis_names)
    return jax.jit(shard_map_nocheck(fn, mesh, spec, spec))


def merge_coverage(bitmaps, mesh: Mesh | None = None) -> np.ndarray:
    """OR-fold per-seed coverage bitmaps (S, CW) into one (CW,) map.

    With a ``mesh``, each device OR-folds its local seed shard
    (``shard_map``, no cross-device traffic — XLA's collective reducers
    don't implement bitwise OR, so the final fold of the D per-device
    rows happens on the host, D*CW words of transfer). The sharded
    coverage merge of a multi-chip exploration sweep
    (madsim_tpu.explore): a 65k-seed generation's bitmaps reduce on the
    mesh and only device-count rows reach the host. Without a mesh, the
    same reduction runs on the default device. ``S`` must divide over
    the mesh's device count.
    """
    import jax.numpy as jnp
    from jax import lax

    bm = jnp.asarray(bitmaps, jnp.uint32)
    if bm.ndim != 2:
        raise ValueError(f"bitmaps must be (S, CW), got shape {bm.shape}")

    def fold(b):
        return lax.reduce(b, jnp.uint32(0), lax.bitwise_or, (0,))

    if mesh is None:
        return np.asarray(jax.jit(fold)(bm))
    n_dev = mesh.devices.size
    if bm.shape[0] % n_dev:
        raise ValueError(
            f"{bm.shape[0]} bitmap rows do not split over {n_dev} devices"
        )
    spec = P(mesh.axis_names)
    local = lambda b: fold(b)[None, :]  # noqa: E731 — (1, CW) per device
    per_dev = jax.jit(
        shard_map_nocheck(local, mesh, spec, spec)
    )(bm)
    return np.bitwise_or.reduce(np.asarray(per_dev, np.uint32), axis=0)


def merge_metrics(met, mesh: Mesh | None = None) -> np.ndarray:
    """Sum-fold per-seed fleet-metric columns (S, M) into (M,) totals.

    The metrics analog of :func:`merge_coverage`: with a ``mesh``, each
    device sums its local seed shard (``shard_map``, zero cross-device
    traffic) and only device-count rows reach the host — a 65k-seed
    sweep's fleet totals cost D*M words of transfer. int64 accumulation
    so 32-bit per-seed counters can't overflow the fleet sum. The
    MET_HALT_CODE slot is summed like any other (meaningless as a
    total); use ``obs.fleet_reduce`` when the halt-code distribution or
    histograms are wanted.
    """
    import jax.numpy as jnp

    mm = jnp.asarray(met)
    if mm.ndim != 2:
        raise ValueError(f"met must be (S, M), got shape {mm.shape}")

    def fold(m):
        return jnp.sum(m.astype(jnp.int64), axis=0)

    if mesh is None:
        return np.asarray(jax.jit(fold)(mm))
    n_dev = mesh.devices.size
    if mm.shape[0] % n_dev:
        raise ValueError(
            f"{mm.shape[0]} metric rows do not split over {n_dev} devices"
        )
    spec = P(mesh.axis_names)
    local = lambda m: fold(m)[None, :]  # noqa: E731 — (1, M) per device
    per_dev = jax.jit(
        shard_map_nocheck(local, mesh, spec, spec)
    )(mm)
    return np.asarray(per_dev, np.int64).sum(axis=0)


def merge_latency(lat_hist, mesh: Mesh | None = None) -> np.ndarray:
    """Sum-fold per-seed latency sketches (S, P, B) into (P, B) totals.

    The tail analog of :func:`merge_metrics`: with a ``mesh``, each
    device sums its local seed shard (``shard_map``, zero cross-device
    traffic) and only device-count sketch pages reach the host — the
    ladder histogram is *exactly mergeable* (integer addition), so the
    sharded fold equals the sketch of the concatenated batch bit for
    bit, which is what lets pod-scale campaigns keep fleet tail
    analysis device-resident. int64 accumulation so 32-bit per-seed
    counts cannot overflow the fleet sum.
    """
    import jax.numpy as jnp

    hh = jnp.asarray(lat_hist)
    if hh.ndim != 3:
        raise ValueError(f"lat_hist must be (S, P, B), got shape {hh.shape}")

    def fold(h):
        return jnp.sum(h.astype(jnp.int64), axis=0)

    if mesh is None:
        return np.asarray(jax.jit(fold)(hh))
    n_dev = mesh.devices.size
    if hh.shape[0] % n_dev:
        raise ValueError(
            f"{hh.shape[0]} sketch rows do not split over {n_dev} devices"
        )
    spec = P(mesh.axis_names)
    local = lambda h: fold(h)[None]  # noqa: E731 — (1, P, B) per device
    per_dev = jax.jit(
        shard_map_nocheck(local, mesh, spec, spec)
    )(hh)
    return np.asarray(per_dev, np.int64).sum(axis=0)


def merge_verdicts(ok, mesh: Mesh | None = None) -> np.ndarray:
    """Pack per-seed verdicts (S,) bool into (S/32,) uint32 words.

    The verdict analog of :func:`merge_metrics`: with a ``mesh``, each
    device packs its LOCAL seed shard's verdicts
    (``check.device.pack_verdicts`` under ``shard_map``, zero
    cross-device traffic — seed shards are contiguous, so the word
    arrays concatenate in seed order) and only S/32 words reach the
    host — a 65k-seed sweep's history verdicts cost 2 KiB of transfer.
    Seeds must split over the devices in multiples of 32 (word
    alignment); unpack host-side with
    ``check.device.unpack_verdicts``.
    """
    import jax.numpy as jnp

    from ..check.device import pack_verdicts

    okb = jnp.asarray(ok, jnp.bool_)
    if okb.ndim != 1:
        raise ValueError(f"ok must be (S,), got shape {okb.shape}")
    if mesh is None:
        return np.asarray(jax.jit(pack_verdicts)(okb))
    n_dev = mesh.devices.size
    local = okb.shape[0] // n_dev if n_dev else 0
    if n_dev == 0 or okb.shape[0] % n_dev or local % 32:
        raise ValueError(
            f"{okb.shape[0]} verdicts do not split over {n_dev} devices "
            f"in word-aligned (multiple-of-32) shards"
        )
    spec = P(mesh.axis_names)
    per_dev = jax.jit(
        shard_map_nocheck(pack_verdicts, mesh, spec, spec)
    )(okb)
    return np.asarray(per_dev, np.uint32)


def shard_run_compacted(
    wl,
    cfg,
    max_steps: int,
    mesh: Mesh,
    build: Build = Build(),
    *,
    shrink: int = 4,
    min_size: int = 2048,
    fields: tuple | None = None,
    hist_screen=None,
):
    """Multi-chip form of :func:`engine.make_run_compacted`.

    ``shard_map`` runs the whole phase program *per device*: each chip
    compacts its local seed shard independently (its while_loops trip
    on local live counts), so there is zero cross-device traffic in the
    hot loop — the reference's one-thread-per-seed "finished seeds stop
    consuming CPU" economy, at mesh scale. Local phase boundaries fall
    at different steps than a global run's would, but rows are
    independent, so per-seed results are bit-identical to the unsharded
    runner (tests/test_parallel.py asserts it).

    Returns ``run(state) -> SimpleNamespace`` of per-original-seed
    numpy arrays, like the single-device runner; ``build`` (an
    ``engine.Build``) as for it. ``state`` should be
    placed with :func:`shard_state` (an unsharded state works too — jit
    reshards it to the declared input sharding).

    ``hist_screen`` runs the device history detectors + prefix-
    compaction at bank time PER DEVICE (the ``make_run_compacted``
    contract, inside ``shard_map``): each chip screens and folds its
    own banked rows with zero cross-device traffic, and the assembled
    host result carries the same ``hist_ok``/``hist_fold`` columns —
    bit-identical to the unsharded screened runner.
    """
    from ..engine import compact as _compact

    kw = {} if fields is None else {"fields": fields}
    base = _compact.make_run_compacted(
        wl, cfg, max_steps, build,
        shrink=shrink, min_size=min_size, hist_screen=hist_screen, **kw,
    )
    n_dev = mesh.devices.size
    spec = P(mesh.axis_names)

    def local(state):
        # global row offset of this device's shard: axis_index over the
        # full axis tuple is the major-order linearized device id, the
        # same order seed_sharding splits the seed axis in — works for
        # any mesh rank
        dev = jax.lax.axis_index(mesh.axis_names)
        local_rows = state.seed.shape[0]
        return base.phases(state, idx_offset=dev * local_rows)

    # check_vma=False: handler branches legitimately mix mesh-constant
    # emits (static rows from EmitBuilder) with shard-varying values;
    # the varying-axes checker would reject those lax.switch branches.
    # Correctness is asserted value-wise instead (sharded == unsharded,
    # tests/test_parallel.py)
    sharded = jax.jit(
        shard_map_nocheck(local, mesh, spec, spec)
    )

    def compute(state):
        if state.seed.shape[0] % n_dev:
            raise ValueError(
                f"{state.seed.shape[0]} seeds do not split over "
                f"{n_dev} devices"
            )
        return sharded(state)

    def run(state):
        return base.assemble_banks(jax.block_until_ready(compute(state)))

    run.compute = compute
    run.assemble = base.assemble_banks
    return run
