"""Shared arithmetic of the step readers: the traced runs of one program
and the work the harness recorded for them.  Returns None where the trace
and the record disagree, so no reader reports a share it cannot back."""


def traced(run, program, kind):
    t, rec = run.trace, run.window.traced
    if t is None or rec is None:
        return None
    runs, secs = t.program(program)
    calls = rec[kind]
    if runs == 0 or secs <= 0 or runs != len(calls) or (
            kind == "decode" and rec["partial"]):
        return None
    return calls, secs


def share(run, program, kind, cost_fn, roofline):
    got = traced(run, program, kind)
    if got is None:
        return None
    calls, secs = got
    from bench import flops
    pk = run.peaks
    if roofline:
        need = sum(flops.least_seconds(cost_fn(run.model, c),
                                       pk["bf16_flops_per_s"],
                                       pk["hbm_bytes_per_s"]) for c in calls)
    else:
        need = sum(cost_fn(run.model, c).flops
                   for c in calls) / pk["bf16_flops_per_s"]
    return 100.0 * need / secs
