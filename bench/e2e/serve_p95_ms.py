"""Open loop: the 95th percentile, over every request scheduled in the
window, of its completion time minus its scheduled arrival.  Requests due
in the window are waited for past its close; one that never completes is
counted as failed by the run."""
import numpy as np


def read(run):
    lat = run.window.get("latency_s")
    if not lat:
        return None
    return 1e3 * float(np.percentile(lat, 95))
