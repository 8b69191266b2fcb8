"""Work of one IVF-PQ asymmetric-distance (ADC) top-k launch.

What the op needs: for each valid candidate of the probed inverted lists
(not the padded list capacity), ``m`` table lookups and adds, its ``m``
code bytes and its 4-byte id read; each query's integer table
(``m * n_codes`` int32) read once; ``want`` (distance, position) pairs
written per query.
"""

TRACE = r"^%adc_topk(\.\d+)?$"
PEAK = "int8_ops_per_s"


def work(s: dict):
    """(operations, bytes) of one launch of shape ``s``."""
    valid, m, Q = s["valid"], s["m"], s["Q"]
    ops = m * valid
    nbytes = valid * (m + 4) + Q * m * s["n_codes"] * 4 + 8 * Q * s["want"]
    return ops, nbytes
