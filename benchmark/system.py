"""The system under test, built from a configuration file.

This is the one module of the benchmark that imports madsim_tpu: it
turns the configuration's ``program`` section into the workload, engine
config, fault plan and device screens the program takes, and refuses a
build in which the program would depart from what the configuration
states (its event-time width). A ``screens`` entry names a screen of
``madsim_tpu.check.device``, as a name or as ``{"name": ..., "args":
{...}}`` for a screen that takes arguments (``election_safety``'s op
code).
"""

from __future__ import annotations

from dataclasses import dataclass

from harness import BenchError
from reference import named


@dataclass
class System:
    wl: object
    cfg: object
    plan: object
    screens: tuple | None
    max_steps: int
    require_halt: bool
    cov_words: int


def build(config: dict) -> System:
    from madsim_tpu import chaos, models
    from madsim_tpu.check import device as dc
    from madsim_tpu.engine import EngineConfig
    from madsim_tpu.engine.core import resolve_time32

    prog = config["program"]
    wl = getattr(models, prog["factory"])(**prog["model_kwargs"])
    cfg = EngineConfig(**config["engine"])
    plan = None
    if config["plan"]:
        specs = tuple(
            getattr(chaos, s["type"])(**{
                k: tuple(v) if isinstance(v, list) else v
                for k, v in s.items() if k != "type"})
            for s in config["plan"]["specs"])
        plan = chaos.FaultPlan(specs, name=config["plan"]["name"])
    screens = tuple(_screen(dc, s) for s in config["screens"]) or None
    import jax

    # the stated width is the chip's; the CPU backend (tests) resolves
    # int64 times for every configuration, with identical results
    t32 = resolve_time32(wl, cfg, None)
    if jax.default_backend() == "tpu" and t32 != prog["time32"]:
        raise BenchError(
            f"the program resolves time32={t32} for {config['name']}, but "
            f"the configuration states event times as {config['event_time']}")
    return System(wl, cfg, plan, screens, int(config["max_steps"]),
                  bool(config["require_halt"]), int(config.get("cov_words", 0)))


def _screen(dc, entry):
    name, args = named.entry(entry)
    make = getattr(dc, name, None)
    if not callable(make):
        raise BenchError(f"no screen {name!r} in madsim_tpu.check.device")
    return make(**args)
