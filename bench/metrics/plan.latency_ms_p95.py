"""95th percentile, over every plan request due in the window and
answered, of the time from its due time to its answer, on the client's
side (host clock).  A request the daemon refused counts in ``failed``."""

import numpy as np


def read(run):
    if not run.plan_latency_s:
        return None
    return float(np.percentile(run.plan_latency_s, 95)) * 1e3
