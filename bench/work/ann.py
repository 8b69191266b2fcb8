"""IVF-PQ with exact refine (FAISS ``IVF{C},PQ{m}`` + RFlat): the ops one
launch runs, and the FLOPs one query needs.

A query probes its ``nprobe`` nearest of ``C`` centroids (``2 C d``),
builds its ADC table (``2 * n_codes * d``), scores every valid candidate
of the probed lists (``m`` adds each) and re-ranks ``R`` survivors
exactly (``2 R d``).  The valid candidates of a query are counted from
the fitted index: the sizes of the lists its probe picks.
"""
import numpy as np


def _probe(centroids, X, nprobe: int):
    c = np.asarray(centroids, np.float64)
    x = np.asarray(X, np.float64)
    d = (np.sum(c * c, 1)[None, :] - 2.0 * x @ c.T)
    return np.argsort(d, axis=1, kind="stable")[:, :nprobe]


def counters(estimator, pool, config: dict) -> dict:
    """Valid candidates per pool query, and for an all-zero padding row."""
    p = estimator.params
    sizes = (np.asarray(p.cell_ids) >= 0).sum(axis=1)
    nprobe = int(config["fitted"]["nprobe"])
    rows = np.concatenate([np.asarray(pool, np.float32),
                           np.zeros((1, pool.shape[1]), np.float32)])
    valid = sizes[_probe(p.centroids, rows, nprobe)].sum(axis=1)
    return {"valid": valid[:-1], "valid_pad": int(valid[-1])}


def launch_ops(bucket: int, pool_idx, config: dict, counters: dict) -> dict:
    f, d = config["fitted"], config["data"]
    valid = int(counters["valid"][pool_idx].sum()) \
        + (bucket - len(pool_idx)) * counters["valid_pad"]
    return {
        "distance_topk": {"N": int(f["n_cells"]), "d": int(d["d"]),
                          "Q": int(bucket), "k": int(f["nprobe"])},
        "adc_topk": {"Q": int(bucket), "valid": valid, "m": int(f["pq_m"]),
                     "n_codes": int(f.get("n_codes", 256)),
                     "want": max(int(config["k"]), int(f["refine"]))},
    }


def query_flops(pool_idx, config: dict, counters: dict):
    f, dd = config["fitted"], int(config["data"]["d"])
    fixed = 2.0 * int(f["n_cells"]) * dd \
        + 2.0 * int(f.get("n_codes", 256)) * dd \
        + 2.0 * int(f["refine"]) * dd
    return fixed + int(f["pq_m"]) * counters["valid"][pool_idx]
