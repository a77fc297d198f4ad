"""Output tokens of the LP requests that finished by their deadline, on
the wall clock, over the window's seconds."""


def read(run):
    lp = [r for r in run.recs if r.cls == "lp"]
    if not lp:
        return None
    return sum(r.new_tokens for r in lp if r.met) / run.seconds
