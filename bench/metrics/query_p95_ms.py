"""95th percentile, over every query answered in the window, of the time
from its batch's submission to its pairs on the host, in milliseconds.
Every query of a batch has its batch's latency."""
import numpy as np


def read(run):
    ops = run.records.get("ops")
    if not ops:
        return None
    lat = np.repeat([op["end"] - op["start"] for op in ops], [op["attempted"] for op in ops])
    return float(np.percentile(lat, 95)) * 1e3
