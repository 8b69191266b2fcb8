"""95th percentile of due time to submit(): how late the generator ran."""
from bench.readings import percentile_ms


def read(rec):
    run = rec.run
    return percentile_ms((run.submit - run.due)[run.answered], 95)
