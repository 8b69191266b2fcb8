"""The comparison that decides ``correct``.

Every request due in the window is checked: it has to come back, and its
answer (the neighbour ids the timed path returned) is compared with the
exact reference over the same pool query.  Each number has its limit in
the configuration's ``checks``; a run is correct when every number is at
or under its limit.

Numbers (reference in ``bench/reference/knn.py``):

- ``unanswered``: requests that never came back (limit 0, always on);
- ``malformed``: answers that are not k distinct valid row ids;
- ``answers_beyond_tol``: distinct answers holding a row whose exact
  distance lies more than ``tolerance_units`` (the configuration's)
  beyond the exact k-th nearest, in fp32 rounding units (exact search);
- ``order_excess_units``: widest inversion of exact distance order
  inside an answer, in units (re-ranked search);
- ``missed_pct``: percent of the exact k nearest missing from the
  answers, over every answered request (approximate search): a wrong
  candidate set, which the order inside an answer cannot show.

Recall (100 - ``missed_pct``, as a fraction) is also reported as a metric.
"""
from __future__ import annotations

import numpy as np

from bench.reference import knn as ref

# gaps at which the answers' spread is printed beside the checks
SPREAD_UNITS = (0.5, 1.0, 1.5, 2.0, 3.0)


def compare(config: dict, gt, A_host, Q_host, run):
    """-> (checks {name: {"value", "limit"}}, correct, recall, detail).
    ``detail`` is a line on the answers' distance gaps, for the log."""
    limits = dict(config["checks"])
    ok = run.answered
    n_rows = A_host.shape[0]
    pool_idx, ids = ref.unique_answers(run.pool_idx[ok], run.answers[ok])
    bad = ref.malformed(ids, n_rows)
    good_idx, good_ids = pool_idx[~bad], ids[~bad]
    recall = ref.recall_at_k(gt, run.pool_idx[ok], run.answers[ok])
    gap = ref.excess_per_answer(gt, A_host, Q_host, good_idx, good_ids)
    values = {
        "unanswered": int(np.sum(~ok)),
        "malformed": int(np.sum(ref.malformed(run.answers[ok], n_rows))),
    }
    if "answers_beyond_tol" in limits:
        values["answers_beyond_tol"] = ref.beyond(
            gap, float(config["tolerance_units"]))
    if "order_excess_units" in limits:
        values["order_excess_units"] = ref.order_excess_units(
            gt, A_host, Q_host, good_idx, good_ids)
    if "missed_pct" in limits:
        values["missed_pct"] = 100.0 * (1.0 - recall) if ok.any() else 100.0
    limits.setdefault("unanswered", 0)
    checks = {name: {"value": values[name], "limit": limits[name]}
              for name in values}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    detail = (f"{len(gap)} distinct answers; widest gap beyond the exact "
              f"k-th {float(gap.max()) if gap.size else 0.0:.4f} units; "
              f"answers beyond " + ", ".join(
                  f"{t}u: {ref.beyond(gap, t)}" for t in SPREAD_UNITS))
    return checks, correct, recall, detail
