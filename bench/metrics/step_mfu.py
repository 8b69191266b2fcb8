"""Whole-step share of the chip's bf16 peak: FLOPs the answered queries
need over the summed wall time of the drains that answered them."""
from bench.readings import step_mfu_pct


def read(rec):
    return step_mfu_pct(rec)
