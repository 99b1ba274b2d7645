"""Parameters of the ``raftlog`` reference workload (raftlog.cpp), in its
order."""


def pack(params: dict) -> list[int]:
    return [params["n_nodes"], params["n_writes"], params["timeout_min_ns"],
            params["timeout_max_ns"], params["propose_ns"], params["retx_ns"],
            int(params["chaos"]), int(params["durable"]),
            int(params["record"])]
