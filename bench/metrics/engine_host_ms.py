"""Mean host time of engine.classify per launch (pad, route, enqueue,
slice), from the benchmark's span around the call."""
from bench.readings import engine_host_ms


def read(rec):
    return engine_host_ms(rec)
