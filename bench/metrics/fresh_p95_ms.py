"""fresh_p95_ms: 95th percentile, over the open-loop ingest batches due
in the window, of the time from a batch's due time to the first
publication whose docs cover it."""
import numpy as np

from bench.series import freshness_ms


def read(run):
    fresh = freshness_ms(run)
    return None if fresh is None else float(np.percentile(fresh, 95))
