"""Share of the HP requests sent whose first token was on the host by
their deadline, on the wall clock; a refused or failed request misses."""


def read(run):
    hp = [r for r in run.recs if r.cls == "hp"]
    if not hp:
        return None
    return 100.0 * sum(r.met for r in hp) / len(hp)
