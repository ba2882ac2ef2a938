"""Gateway layer: 95th percentile over the window's requests of the time
from the program's seat stamp to its first put on the request's stream
(``first_stream_s - seated_s``, from the ``gateway.request`` events);
never seated or never streamed counts as infinite."""

import math

import hostspans
from stats import percentile


def read(run):
    stamps = hostspans.request_stamps()
    if stamps is None:
        return None
    gaps = []
    for r in run.win.measured:
        gap = (hostspans.stamp(stamps, r.rid, "first_stream_s")
               - hostspans.stamp(stamps, r.rid, "seated_s"))
        gaps.append(gap if math.isfinite(gap) else math.inf)
    return 1e3 * percentile(gaps, 95)
