"""Median latency of one ``generate`` task, over every task of the window
(numpy's linear interpolation)."""
import numpy as np


def read(run):
    if not run.done:
        return None
    return float(np.percentile([d.end - d.start for d in run.done], 50))
