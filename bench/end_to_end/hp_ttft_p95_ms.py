"""95th percentile, over the HP requests that produced their token, of the
wall time from the due send time to the token on the host; failures are
counted by ``hp_deadline_met_pct`` and ``failed``."""
import numpy as np


def read(run):
    ttft = [(r.ready - r.due) * 1e3 for r in run.recs
            if r.cls == "hp" and r.state == "done" and r.ready is not None]
    return float(np.percentile(ttft, 95)) if ttft else None
