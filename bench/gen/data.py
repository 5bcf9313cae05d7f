"""The deployment's data, made from the configuration and the run's seed."""
from __future__ import annotations

import numpy as np

from bench.gen.graphs import hash_partition, snb_graph


def build(config: dict, seed: int) -> dict:
    """Graph and sharding function of a configuration:
    ``{"snb", "shard", "n_objects", "n_servers", "relationships"}``."""
    g = config["graph"]
    if g["kind"] != "snb":
        raise ValueError(f"unknown graph kind {g['kind']!r}")
    if config["sharding"] != "hash":
        raise ValueError(f"unknown sharding {config['sharding']!r}")
    gseed = int(np.random.default_rng([seed, 0]).integers(2**62))
    snb = snb_graph(g["counts"], int(g["knows_mean_deg"]), seed=gseed,
                    ldbc_reads=True)
    n_servers = int(config["servers"])
    return {"snb": snb, "n_servers": n_servers,
            "shard": hash_partition(snb.graph.n_nodes, n_servers,
                                    int(config.get("hash_seed", 0))),
            "n_objects": snb.graph.n_nodes,
            "relationships": snb.relationships()}
