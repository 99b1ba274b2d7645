"""Parameters of the ``kvchaos`` reference workload (kvchaos.cpp), in its
order."""


def pack(params: dict) -> list[int]:
    return [params["writes"], params["n_replicas"], params["retx_ns"],
            params["client_retx_ns"], int(params["chaos"]),
            int(params["record"]), int(params["bug"])]
