"""Share of the window in which no operation ran on the device."""
from bench.readings import idle_pct


def read(rec):
    return idle_pct(rec)
