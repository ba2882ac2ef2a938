"""Small shared arithmetic of the metric readers."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (``inf`` counts as a value)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(0, math.ceil(q / 100 * len(xs)) - 1)]


def ttft_s(rec, t0: float) -> float:
    """Due time to the first token at the client; ``inf`` if the request
    failed or never finished."""
    return rec.times[0] - (t0 + rec.due) if rec.done else math.inf


def tpot_s(rec) -> float:
    """Mean gap between the request's output tokens at the client."""
    if not rec.done:
        return math.inf
    n = len(rec.times)
    return (rec.times[-1] - rec.times[0]) / (n - 1) if n > 1 else 0.0


def served_flops(run, start: float, end: float) -> float:
    """Useful FLOPs of every token that reached a client in [start, end):
    a first token stands for its prompt's prefill, a later one for one
    decode step at its real context."""
    c, cfg = run.counts, run.cfg
    total = 0.0
    for r in run.win.recs:
        s = len(r.prompt)
        for j, t in enumerate(r.times):
            if start <= t < end:
                total += (c.prefill_flops(cfg, s) if j == 0
                          else c.decode_flops(cfg, s + j))
    return total
