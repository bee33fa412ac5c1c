"""95th percentile of the host-clock time of every fleet round in the
window, from the call to its return on device-synced results."""
import numpy as np


def read(run):
    if run.cell.unit != "samples" or not run.step_s:
        return None
    return float(np.percentile(np.asarray(run.step_s), 95)) * 1e3
