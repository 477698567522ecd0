"""95th percentile over every request whose first token reached the host
in the window, of the time from when the request was due (closed loop:
when its batch was issued) to its first token on the host."""

import numpy as np


def read(run):
    if not run.ttft_s:
        return None
    return float(np.percentile(run.ttft_s, 95)) * 1e3
