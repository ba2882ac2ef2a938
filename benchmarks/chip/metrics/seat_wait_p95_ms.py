"""Gateway layer: 95th percentile over the window's requests of the time
from due to the program's own seat stamp (``seated_s``, taken when the
pool first seats the session), read from the ``gateway.request`` events
by ``rid``; never seated counts as infinite."""

import math

import hostspans
from stats import percentile


def read(run):
    stamps = hostspans.request_stamps()
    if stamps is None:
        return None
    w = run.win
    waits = []
    for r in w.measured:
        seated = hostspans.stamp(stamps, r.rid, "seated_s")
        waits.append(seated - (w.t0 + r.due) if math.isfinite(seated)
                     else math.inf)
    return 1e3 * percentile(waits, 95)
