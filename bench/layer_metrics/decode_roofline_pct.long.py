"""Decode step of the long-prompt cell: the least time the traced tokens
need over their device time, as ``decode_roofline_pct`` reads it (the
family's ``decode_cost``: for ``dense_swa`` the weights and at most the
window's cached positions), reported where the HP tails are what the
decode step moves."""
from bench.layer_metrics._steps import share


def read(run):
    return share(run, "serve_step", "decode", run.family.decode_cost,
                 roofline=True)
