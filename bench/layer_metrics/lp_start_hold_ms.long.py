"""Decode step, LP: how long one LP start holds the client loop, the
engine's ``serve.prefill`` and ``serve.decode`` seconds summed over the LP
requests and every start of each, over their prefill calls (one per
start that did not resume).  An HP request that falls due meanwhile waits
it out."""
from bench.layer_metrics._timing import timings


def read(run):
    ts = [t for t in timings(run, "lp") if hasattr(t, "prefills")]
    n = sum(t.prefills for t in ts)
    return (sum(t.prefill_s + t.decode_s for t in ts) / n * 1e3 if n
            else None)
