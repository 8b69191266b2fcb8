"""95th percentile latency, due time to result received, over every
answered request of the window."""
from bench.readings import percentile_ms


def read(rec):
    return percentile_ms(rec.run.latency, 95)
