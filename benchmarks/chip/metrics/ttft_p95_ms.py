"""95th percentile over every request due in the window of (first token
at the client - due time); an unfinished request counts as infinite."""

from stats import percentile, ttft_s


def read(run):
    return 1e3 * percentile([ttft_s(r, run.win.t0) for r in run.win.measured],
                            95)
