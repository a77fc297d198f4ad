"""Preemption: LP tokens the chip computed and the engine then threw away
(a preemption under lose_work, or a request that failed after computing),
over all LP tokens computed."""


def read(run):
    lp = [r for r in run.recs if r.cls == "lp"]
    computed = sum(r.computed for r in lp)
    if not computed:
        return None
    kept = sum(len(r.req.tokens_out) for r in lp if r.state == "done")
    return 100.0 * (computed - kept) / computed
