"""Decode step: mean device time per ``serve_step`` program run (one
token)."""


def read(run):
    if run.trace is None:
        return None
    runs, secs = run.trace.program("serve_step")
    return secs / runs * 1e3 if runs else None
