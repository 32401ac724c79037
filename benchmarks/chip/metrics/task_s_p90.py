"""90th percentile latency of one ``generate`` task, over every task of the
window (numpy's linear interpolation). Listed only for cells whose window
finishes 100 tasks or more, so that ten or more lie beyond it."""
import numpy as np


def read(run):
    if not run.done:
        return None
    return float(np.percentile([d.end - d.start for d in run.done], 90))
