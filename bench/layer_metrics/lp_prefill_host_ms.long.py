"""Prefill step, LP: wall time inside the engine's ``serve.prefill`` spans
over the prefill calls made in them, summed over the LP requests and every
start of each (``RequestTiming.prefill_s`` / ``prefills``): the host's view
of one long prompt's prefill, its token read included."""
from bench.layer_metrics._timing import timings


def read(run):
    ts = [t for t in timings(run, "lp") if hasattr(t, "prefills")]
    n = sum(t.prefills for t in ts)
    return sum(t.prefill_s for t in ts) / n * 1e3 if n else None
