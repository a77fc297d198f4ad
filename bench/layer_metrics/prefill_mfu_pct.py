"""Prefill step: FLOPs the traced prompts need (from their lengths) over
device time times the chip's peak bf16 FLOP/s."""
from bench.layer_metrics._steps import share


def read(run):
    return share(run, "prefill_step", "prefill", run.family.prefill_cost,
                 roofline=False)
