"""95th percentile over every request due in the window of its mean gap
between output tokens at the client; an unfinished request counts as
infinite."""

from stats import percentile, tpot_s


def read(run):
    return 1e3 * percentile([tpot_s(r) for r in run.win.measured], 95)
