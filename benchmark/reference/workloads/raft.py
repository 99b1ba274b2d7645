"""Parameters of the ``raft`` reference workload (raft.cpp), in its order."""


def pack(params: dict) -> list[int]:
    return [params["n_nodes"], params["timeout_min_ns"],
            params["timeout_max_ns"]]
