"""Device layer: percent of the traced window in which the first chip
runs no operation and the host is inside neither a ``gateway.tick`` nor
a ``gateway.publish`` span.  With the two others it sums to
``idle_share.served``."""

import hostspans


def read(run):
    s = hostspans.idle_split(run)
    return None if s is None else s.share(s.unspanned_s)
