"""Share of its roofline that the fused distance -> top-k kernel
reaches, from the device trace."""
from bench.readings import roofline_pct


def read(rec):
    return roofline_pct(rec, "distance_topk")
