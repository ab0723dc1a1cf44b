"""95th percentile of the QueryProfile 'device_step' stage of each query's first result."""
import numpy as np


def read(art):
    vals = [q.stream.profile.device_step_s for q in art.queries
            if q.stream is not None and q.stream.profile.committed]
    return float(np.quantile(vals, 0.95)) if vals else None
