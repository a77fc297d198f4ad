"""Client loop: 95th percentile of how late each request was pushed into
the engine after it fell due (the loop is busy inside ``eng.run`` while
the engine computes)."""
import numpy as np


def read(run):
    lags = [r.lag * 1e3 for r in run.recs]
    return float(np.percentile(lags, 95)) if lags else None
