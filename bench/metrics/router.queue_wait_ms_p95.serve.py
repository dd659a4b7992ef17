"""95th percentile over the window's requests of the wait from the time a
request was due to its admission to an engine lane (``Request.t_admit``)."""
import numpy as np


def read(run):
    q = run.layer.get("queue_wait_s")
    return 1e3 * float(np.percentile(q, 95)) if q else None
