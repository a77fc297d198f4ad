"""Decode step: the least time the traced tokens need (the larger of
FLOPs over peak and bytes over HBM bandwidth; bytes are the weights, the
K/V up to each token's position and the new K/V) over their device time."""
from bench.layer_metrics._steps import share


def read(run):
    return share(run, "serve_step", "decode", run.family.decode_cost,
                 roofline=True)
