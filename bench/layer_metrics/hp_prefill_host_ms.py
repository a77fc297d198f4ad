"""Prefill step: mean, over the HP requests that got their token, of the
wall time from the start of their compute to the token as a host integer
(the engine's stamps ``started`` and ``first_token``, which bound its
``serve.prefill`` span)."""
from bench.layer_metrics._timing import timings


def read(run):
    ms = [(t.first_token - t.started) * 1e3 for t in timings(run, "hp")
          if t.first_token is not None]
    return sum(ms) / len(ms) if ms else None
