"""Decode step: FLOPs the traced tokens need (from their positions) over
device time times the chip's peak bf16 FLOP/s."""
from bench.layer_metrics._steps import share


def read(run):
    return share(run, "serve_step", "decode", run.family.decode_cost,
                 roofline=False)
