"""95th percentile, over every decode step of every request in the
window, of the gap between the step's token and the previous token on
the host."""

import numpy as np


def read(run):
    if not run.tpot_s:
        return None
    return float(np.percentile(run.tpot_s, 95)) * 1e3
