"""Output tokens that reached clients inside the window, per second of
the window (every request's, whenever it was due)."""


def read(run):
    w = run.win
    n = sum(1 for r in w.recs for t in r.times if w.t0 <= t < w.t1)
    return n / w.seconds
