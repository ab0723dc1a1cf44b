"""Time to first result, from when each query was due (95th percentile over every query in the window).

A query that failed or was not answered by the drain counts with its wait until the drain gave up."""
import numpy as np


def read(art):
    if not art.queries:
        return None
    return float(np.quantile([q.ttfr_s() for q in art.queries], 0.95))
