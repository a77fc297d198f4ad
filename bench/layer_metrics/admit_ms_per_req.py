"""Admission and dispatch: wall time inside the engine's ``serve.admit``
spans (placement, preemption and victim reallocation), summed over the
window's submitted requests, per request."""
from bench.layer_metrics._timing import timings


def read(run):
    ts = [t for t in timings(run) if t.submitted is not None]
    return sum(t.admit_s for t in ts) / len(ts) * 1e3 if ts else None
