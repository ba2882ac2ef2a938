"""Device layer: percent of the traced window in which the first chip
runs no operation while the host is inside a ``gateway.tick`` span (the
program's annotation on the profiler's host plane)."""

import hostspans


def read(run):
    s = hostspans.idle_split(run)
    return None if s is None else s.share(s.tick_s)
