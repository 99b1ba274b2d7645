"""engine/measure.py: the jitter-proof throughput harness.

The measurement must be *about* the same computation the engine runs:
the repeat program's scalar reductions have to equal what separate
compacted runs of the same seed blocks produce, or the sweep's numbers
describe a different program than the one shipped.
"""

import numpy as np

from madsim_tpu.engine import EngineConfig, make_init, make_run_compacted
from madsim_tpu.engine.measure import (
    make_repeat_program,
    measure_latency,
    measure_throughput,
)
from madsim_tpu.models import make_microbench, make_raft


def test_repeat_program_matches_separate_runs():
    # packing equivalence is model-agnostic; microbench's small step
    # body compiles ~3x faster than raft's (the raft repeat program is
    # exercised for real by every bench.py run)
    wl = make_microbench(rounds=40)
    cfg = EngineConfig(pool_size=16)
    n_seeds, repeats, seed_mod = 32, 3, 64
    program = make_repeat_program(wl, cfg, 400, n_seeds, seed_mod, min_size=8)
    sim_ns, ovf, halted = (int(x) for x in program(np.uint64(5), repeats))

    init = make_init(wl, cfg)
    run = make_run_compacted(
        wl, cfg, 400, min_size=8, fields=("now", "overflow", "halted")
    )
    want_ns = want_ovf = want_halted = 0
    for r in range(repeats):
        seeds = (5 + r * n_seeds + np.arange(n_seeds, dtype=np.uint64)) % seed_mod
        out = run(init(seeds))
        want_ns += int(np.asarray(out.now).sum())
        want_ovf += int(np.asarray(out.overflow).sum())
        want_halted += int(np.asarray(out.halted).sum())
    assert (sim_ns, ovf, halted) == (want_ns, want_ovf, want_halted)
    assert halted == repeats * n_seeds


def test_measure_throughput_reports_quotable_cell():
    wl = make_microbench(rounds=5)
    cfg = EngineConfig(pool_size=8)
    rec = measure_throughput(
        wl, cfg, 200, 64, target_wall_s=0.2, n_measure=2,
        seed_mod=128, min_size=16,
    )
    assert rec["overflow"] == 0
    assert rec["all_halted"]
    assert rec["sim_s_per_s_median"] > 0
    assert rec["sim_s_per_s_min"] <= rec["sim_s_per_s_median"] <= rec["sim_s_per_s_max"]
    assert len(rec["dispatch_walls_s"]) == 2
    assert rec["repeats"] >= 1


def test_measure_latency_reports_quotable_cell():
    # the single-seed latency analog (bench.py's pingpong quote)
    wl = make_microbench(rounds=5)
    cfg = EngineConfig(pool_size=8)
    rec = measure_latency(
        wl, cfg, 200, target_wall_s=0.2, n_measure=2, seed_mod=128
    )
    assert rec["overflow"] == 0
    assert rec["all_halted"]
    assert rec["n_seeds"] == 1
    assert rec["wall_us_per_sim_median"] > 0
    assert rec["sim_s_per_s"] > 0
    assert len(rec["dispatch_walls_s"]) == 2
    assert rec["repeats"] >= 32


def test_bench_configs_mirror_bench_specs():
    """bench.py's parent stays jax-free by mirroring the seed/step table
    of models.BENCH_SPECS; drift between the two would silently measure
    a different spec than the one certified by the oracle artifacts."""
    import importlib.util
    import os

    from madsim_tpu.models import BENCH_SPECS

    path = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    spec = importlib.util.spec_from_file_location("bench_mod", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert set(bench.CONFIGS) == set(BENCH_SPECS)
    for name, (n_seeds, n_steps) in bench.CONFIGS.items():
        _f, _cfg, spec_seeds, spec_steps = BENCH_SPECS[name]
        assert (n_seeds, n_steps) == (spec_seeds, spec_steps), name
