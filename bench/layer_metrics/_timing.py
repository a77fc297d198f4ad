"""Shared access of the readers of the engine's own wall-clock record of
each request (``ServeRequest.timing``: ``time.perf_counter()`` stamps and
the seconds summed inside its ``serve.*`` spans).  A program whose
requests carry no such record yields nothing, and its readers return
None."""


def timings(run, cls=None):
    """The timing records of the window's requests of class ``cls``
    ("hp" or "lp"; every class when None)."""
    out = []
    for r in run.recs:
        t = getattr(r.req, "timing", None)
        if t is not None and cls in (None, r.cls):
            out.append(t)
    return out
