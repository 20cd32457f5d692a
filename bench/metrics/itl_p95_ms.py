"""95th percentile of every gap between consecutive host deliveries of one
request's tokens, over all requests, for deliveries inside the window."""
import numpy as np

from bench.stats import percentile


def read(rec):
    gaps = []
    for q in rec.requests:
        t = np.asarray(q["times"])
        if len(t) < 2:
            continue
        later = t[1:]
        ok = (later >= 0.0) & (later < rec.seconds)
        gaps.extend(((later - t[:-1])[ok] * 1e3).tolist())
    return percentile(gaps, 95) if gaps else None
