"""Admission and dispatch: 95th percentile, over the HP requests the engine
started, of the wall time from their submission to the start of their
compute (the engine's own stamps ``submitted`` and ``started``)."""
import numpy as np

from bench.layer_metrics._timing import timings


def read(run):
    waits = [(t.started - t.submitted) * 1e3 for t in timings(run, "hp")
             if t.started is not None]
    return float(np.percentile(waits, 95)) if waits else None
