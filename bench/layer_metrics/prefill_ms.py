"""Prefill step: mean device time per ``prefill_step`` program run."""


def read(run):
    if run.trace is None:
        return None
    runs, secs = run.trace.program("prefill_step")
    return secs / runs * 1e3 if runs else None
