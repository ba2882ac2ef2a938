"""Gateway layer: 95th percentile over the window's requests of the time
from due to the end of the gateway tick that seated the session (seen
through the session's phase); never seated counts as infinite."""

import math

from stats import percentile


def read(run):
    w = run.win
    waits = [r.admitted - (w.t0 + r.due) if not math.isnan(r.admitted)
             else math.inf for r in w.measured]
    return 1e3 * percentile(waits, 95)
