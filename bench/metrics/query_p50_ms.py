"""query_p50_ms: median time from when a query was due to when its
answer came back, over every query due in the window."""
import numpy as np

from bench.series import query_latency_ms


def read(run):
    lat = query_latency_ms(run)
    return None if lat is None else float(np.percentile(lat, 50))
