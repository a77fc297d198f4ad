"""Decode step: wall time inside the engine's ``serve.decode`` spans over
the ``serve_step`` calls made in them, summed over the LP requests and
every start of each (the device's step plus the host's sync per token)."""
from bench.layer_metrics._timing import timings


def read(run):
    ts = timings(run, "lp")
    steps = sum(t.decode_steps for t in ts)
    return sum(t.decode_s for t in ts) / steps * 1e3 if steps else None
