"""``CrashStorm``: ``n`` kills of a node drawn from ``targets`` at a time in
[t_min_ns, t_max_ns), each restarted after [down_min_ns, down_max_ns)."""

from reference.plan import KIND_KILL, KIND_RESTART, Stream


def rows(spec: dict, seed: int, slot: int) -> list:
    st = Stream(seed, slot)
    out = []
    for i in range(spec["n"]):
        who = st.pick(spec["targets"], 3 * i)
        at = st.uniform(spec["t_min_ns"], spec["t_max_ns"], 3 * i + 1)
        down = st.uniform(spec["down_min_ns"], spec["down_max_ns"], 3 * i + 2)
        out.append((at, KIND_KILL, 0, who, 0, True))
        out.append((at + down, KIND_RESTART, 0, who, 0, True))
    return out


def slots(spec: dict) -> int:
    return 2 * spec["n"]
