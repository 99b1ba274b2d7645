"""No batched scatter into a bool array on the chip's paths.

On the TPU (jax 0.9.0, libtpu 0.0.34) a vmapped ``x.at[i].set(v)`` on a
bool ``x`` loses its writes while the int32 form is right (chip runs,
PR 21). The device campaign's mutator bred wrong children through one,
so its campaigns found other violations than the CPU backend. Such
writes are spelled as one-hot selects; this walks the traced programs
and fails on any bool scatter with batching dimensions.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend import core as jax_core

from madsim_tpu import explore, models
from madsim_tpu.chaos import CrashStorm, FaultPlan
from madsim_tpu.check import device as dc
from madsim_tpu.engine import (
    Build,
    EngineConfig,
    make_init,
    make_run_compacted,
    make_run_while,
)
from madsim_tpu.models import BENCH_SPECS, make_kvchaos
from madsim_tpu.obs import prof

SCREENS = (dc.stale_reads(), dc.read_your_writes())


def batched_bool_scatters(jaxpr) -> list:
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name.startswith("scatter"):
            dn = e.params["dimension_numbers"]
            if (e.invars[0].aval.dtype == jnp.bool_
                    and dn.operand_batching_dims):
                found.append(f"{e.primitive.name} {e.invars[0].aval}")
        for p in e.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(sub, jax_core.ClosedJaxpr):
                    found += batched_bool_scatters(sub.jaxpr)
                elif isinstance(sub, jax_core.Jaxpr):
                    found += batched_bool_scatters(sub)
    return found


def kvchaos():
    wl = make_kvchaos(writes=10, record=True, bug=True, chaos=False)
    return wl, EngineConfig(pool_size=192, loss_p=0.05)


def test_the_walker_finds_one():
    f = jax.vmap(lambda m, i: m.at[i].set(False))
    jaxpr = jax.make_jaxpr(f)(jnp.ones((4, 4), bool), jnp.zeros(4, jnp.int32))
    assert batched_bool_scatters(jaxpr.jaxpr) == ["scatter bool[4,4]"]


@pytest.mark.parametrize("layout", ["dense", "scatter"])
@pytest.mark.parametrize("name", sorted(BENCH_SPECS) + [
    "twophase", "paxos", "snapshot", "leasekv", "shardkv"])
def test_engine_runner(name, layout):
    if name in BENCH_SPECS:
        factory, cfg_kw, _, _ = BENCH_SPECS[name]
        wl, cfg = factory(), EngineConfig(**cfg_kw)
    else:
        wl, cfg = getattr(models, f"make_{name}")(), EngineConfig(pool_size=64)
    st = jax.eval_shape(make_init(wl, cfg), np.arange(8, dtype=np.uint64))
    run = make_run_while(wl, cfg, 10, Build(layout=layout))
    jaxpr = jax.make_jaxpr(run)(st)
    assert batched_bool_scatters(jaxpr.jaxpr) == []


@pytest.mark.parametrize("name", sorted(BENCH_SPECS) + ["kvchaos_screened"])
def test_compacted_runner(name):
    # the sweep's phase program with its banks put back in seed order
    # and packed on the device; 8 rows bank three times
    if name == "kvchaos_screened":
        (wl, cfg), kw = kvchaos(), {"hist_screen": SCREENS}
    else:
        factory, cfg_kw, _, _ = BENCH_SPECS[name]
        (wl, cfg), kw = (factory(), EngineConfig(**cfg_kw)), {}
    st = jax.eval_shape(make_init(wl, cfg), np.arange(8, dtype=np.uint64))
    run = make_run_compacted(wl, cfg, 10, shrink=2, min_size=2, **kw)
    jaxpr = jax.make_jaxpr(run.compute)(st)
    assert batched_bool_scatters(jaxpr.jaxpr) == []


@pytest.mark.parametrize("name", sorted(BENCH_SPECS) + ["kvchaos_plan"])
def test_sweep_init(name):
    # search_seeds runs the batch's init as one compiled program
    seeds = np.arange(8, dtype=np.uint64)
    if name == "kvchaos_plan":
        wl, cfg = kvchaos()
        plan = FaultPlan((CrashStorm(targets=(1, 2, 3, 4), n=2),), name="kv")
        args = (seeds, plan.compile_batch(seeds, wl=wl))
        init = make_init(wl, cfg, Build(plan_slots=plan.slots))
    else:
        factory, cfg_kw, _, _ = BENCH_SPECS[name]
        args, init = (seeds,), make_init(factory(), EngineConfig(**cfg_kw))
    jaxpr = jax.make_jaxpr(init)(*args)
    assert batched_bool_scatters(jaxpr.jaxpr) == []


def test_history_fold():
    wl, cfg = kvchaos()
    st = jax.eval_shape(make_init(wl, cfg), np.arange(8, dtype=np.uint64))
    jaxpr = jax.make_jaxpr(
        lambda w, t, c, d: dc.fold_verified(w, t, c, d, c >= 0)
    )(st.hist_word, st.hist_t, st.hist_count, st.hist_drop)
    assert batched_bool_scatters(jaxpr.jaxpr) == []


def test_campaign_programs(monkeypatch):
    found = {}
    build = prof.AotProgram._build

    def walk_then_build(self, sig, args):
        traced = self._jit.trace(*args)
        found[self.name] = batched_bool_scatters(traced.jaxpr.jaxpr)
        return build(self, sig, args)

    monkeypatch.setattr(prof.AotProgram, "_build", walk_then_build)
    wl, cfg = kvchaos()
    plan = FaultPlan((
        CrashStorm(targets=(1, 2, 3, 4), n=2, t_min_ns=20_000_000,
                   t_max_ns=400_000_000, down_min_ns=50_000_000,
                   down_max_ns=250_000_000),
    ), name="kv-nemesis")
    explore.run_device(wl, cfg, plan, invariant=None, history_check=SCREENS,
                       generations=2, batch=8, max_steps=50, cov_words=8)
    assert found == {"explore.device.uniform": [],
                     "explore.device.breed": []}
