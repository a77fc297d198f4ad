"""Admission and dispatch: wall time inside ``eng.run`` that was not the
requests' compute (the wrapped ``on_start``), per request sent."""


def read(run):
    w = run.window
    if not w.recs:
        return None
    return (w.run_s - w.compute_s) / len(w.recs) * 1e3
