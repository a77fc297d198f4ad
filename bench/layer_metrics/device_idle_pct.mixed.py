"""Device, in the cells with LP decode: 1 - (union of device operation
intervals / traced window)."""


def read(run):
    t = run.trace
    if t is None or t.n_devices == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
