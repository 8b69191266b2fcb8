"""Exact kNN (FAISS IndexFlatL2): the ops one launch runs, and the FLOPs
one query needs (``2 N d``: one multiply-add per coordinate per row)."""
import numpy as np


def counters(estimator, pool, config: dict) -> dict:
    return {}


def launch_ops(bucket: int, pool_idx, config: dict, counters: dict) -> dict:
    d = config["data"]
    return {"distance_topk": {"N": int(d["n_base"]), "d": int(d["d"]),
                              "Q": int(bucket),
                              "k": int(config["k"])}}


def query_flops(pool_idx, config: dict, counters: dict):
    d = config["data"]
    return np.full(len(pool_idx), 2.0 * int(d["n_base"]) * int(d["d"]))
