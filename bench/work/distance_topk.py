"""Work of one fused distance -> top-k launch: ``N`` rows of width ``d``
against ``Q`` queries, ``k`` kept per query.

What the op needs, whatever arm runs it: the index read once, the
queries read, the answers written; the distance expansion
``|a|^2 - 2 a.q + |q|^2`` for every (row, query) pair.  Padding the
index to a row block is not needed work and is not counted.
"""

# this op's device-trace ops: the Pallas call's HLO name, as a regex
TRACE = r"^%distance_topk(\.\d+)?$"
PEAK = "bf16_flops_per_s"


def work(s: dict):
    """(operations, bytes) of one launch of shape ``s``."""
    N, d, Q, k = s["N"], s["d"], s["Q"], s["k"]
    flops = 2 * N * d * Q + 2 * N * d + 2 * Q * d + 3 * N * Q
    nbytes = 4 * (N * d + Q * d) + 8 * Q * k
    return flops, nbytes
