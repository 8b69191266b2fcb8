"""Queries answered inside the window over the window's length."""
import numpy as np


def read(rec):
    run = rec.run
    return float(np.sum(run.answered & (run.recv <= run.seconds))
                 / run.seconds)
