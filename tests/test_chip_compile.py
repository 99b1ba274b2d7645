"""Compile the main path for a described TPU v5e chip, at real size.

Nothing runs: the TPU compiler installed here lowers and compiles each
program for one chip of a ``v5e:2x2`` topology that is described, not
attached, so what the chip's compiler would refuse fails here at no chip
time. The code under test sees the CPU backend, so the TPU lowering
(``layout="dense", time32=True``) is asked for explicitly.

The topology is described in a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file. Keep these tests in this one file.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from madsim_tpu.check import device as dc
from madsim_tpu.engine import (
    Build,
    EngineConfig,
    make_init,
    make_run_while,
    time32_eligible,
)
from madsim_tpu.models import BENCH_SPECS, make_kvchaos

N_SEEDS = 65536


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def four_chips(topo, one_chip):
    from madsim_tpu.parallel import make_mesh

    return make_mesh(list(topo.devices))


def _state_shapes(wl, cfg, sharding, time32, n_seeds=N_SEEDS):
    init = make_init(wl, cfg, Build(time32=time32))
    shapes = jax.eval_shape(init, jax.ShapeDtypeStruct((n_seeds,),
                                                       jnp.uint64))
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        shapes,
    )


def test_raft_run_while_compiles_for_v5e(one_chip):
    factory, cfg_kw, n_seeds, n_steps = BENCH_SPECS["raft"]
    assert n_seeds == N_SEEDS
    wl, cfg = factory(), EngineConfig(**cfg_kw)
    run = make_run_while(wl, cfg, n_steps, Build(layout="dense", time32=True))
    st = _state_shapes(wl, cfg, one_chip, True)
    compiled = jax.jit(run).lower(st).compile()
    mem = compiled.memory_analysis()
    # the whole 65k-seed batch is far inside one chip's 16 GB
    assert 0 < mem.argument_size_in_bytes < 1 << 30
    assert mem.temp_size_in_bytes < 1 << 30


def test_history_screen_compiles_for_v5e(one_chip):
    wl = make_kvchaos(writes=10, record=True, bug=True, chaos=False)
    cfg = EngineConfig(pool_size=192, loss_p=0.05)
    # the campaign config of chip_smoke.py: int64 event times on the
    # chip too, since its bounds do not fit time32
    assert not time32_eligible(wl, cfg)
    st = _state_shapes(wl, cfg, one_chip, False)
    screens = (dc.stale_reads(), dc.read_your_writes())
    compiled = jax.jit(lambda w, t, c, d: dc.screen_ok(screens, w, t, c, d)
                       ).lower(st.hist_word, st.hist_t, st.hist_count,
                               st.hist_drop).compile()
    assert "pred[65536]" in compiled.as_text()


def test_sharded_run_compiles_for_v5e_2x2(four_chips):
    # the partitioner-driven form, jit(in_/out_shardings), aborts this
    # compiler on the engine's nested calls; shard_over_seeds must not
    from madsim_tpu.parallel import seed_sharding, shard_over_seeds

    factory, cfg_kw, n_seeds, n_steps = BENCH_SPECS["raft"]
    wl, cfg = factory(), EngineConfig(**cfg_kw)
    n = n_seeds * four_chips.devices.size
    st = _state_shapes(wl, cfg, seed_sharding(four_chips), True, n)
    run = shard_over_seeds(
        make_run_while(wl, cfg, n_steps, Build(layout="dense", time32=True)),
        four_chips,
    )
    mem = run.lower(st).compile().memory_analysis()
    # each device holds one chip's 65,536 seeds (plus TPU tile
    # padding), not the whole 262,144
    single = _state_shapes(wl, cfg, None, True)
    per_chip = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(single))
    assert per_chip <= mem.argument_size_in_bytes < 2 * per_chip
