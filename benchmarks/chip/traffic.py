"""Open-loop request schedules from a traffic file and a seed.

A traffic file (``traffic/<name>.json``) holds parameters only: the
arrival process and its rate, the prompt length classes and their
weights, the output-length law and its clip, the latency limits and the
shapes to warm up.  :func:`schedule` turns it into requests.

Every seed gets the same work in a different order.  The multiset of
inter-arrival gaps, prompt lengths and output lengths is drawn once from
a fixed stream (exact class proportions, gamma gaps, clipped lognormal
outputs); ``seed`` only permutes and draws the prompt token ids.  So two
seeds differ by arrangement, not by the amount of work.

The arrangement is stratified in time: the span is cut into strata of
about ``arrival.stratum_s`` seconds, and each sorted list is dealt to
them in snake order, so every stratum holds one value from each band of
every law, the same for every seed.  The seed orders the values inside a
stratum.  So each stretch of the window carries the same work for every
seed; a fully shuffled list would put a different share of the long
answers into the window's last seconds, where they cannot finish.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
_MULTISET_SEED = 20240417      # fixes the multiset, shared by every seed


@dataclasses.dataclass(frozen=True)
class Req:
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # (prompt_len,) int32 token ids
    budget: int                # output tokens asked for
    in_window: bool            # due inside [0, seconds)


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _class_counts(weights, n: int) -> list[int]:
    """Largest-remainder split of ``n`` requests over the class weights."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    raw = w * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def _gaps(arrival: dict, n: int, rng) -> np.ndarray:
    rate = float(arrival["rate_rps"])
    if arrival["process"] != "gamma":
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    shape = 1.0 / float(arrival["cv"]) ** 2       # CV 1: Poisson arrivals
    return rng.gamma(shape, 1.0 / (shape * rate), n)


def _outputs(law: dict, n: int, rng) -> np.ndarray:
    if law["law"] != "lognormal":
        raise ValueError(f"unknown output-length law {law['law']!r}")
    x = np.exp(np.log(law["median"]) + law["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), law["min"], law["max"]).astype(int)


def _deal(values, n: int) -> list[np.ndarray]:
    """``values`` sorted and dealt to ``n`` strata in snake order: each
    stratum gets one value from every band of ``n``."""
    out = [[] for _ in range(n)]
    for i, v in enumerate(np.sort(values)):
        band, j = divmod(i, n)
        out[j if band % 2 == 0 else n - 1 - j].append(v)
    return [np.asarray(o) for o in out]


def _block(traffic: dict, m: int, seconds: float, fixed, rng):
    """``m`` requests whose gaps sum to ``seconds``: the multiset comes
    from ``fixed``, its share of each stratum is fixed, and the order
    inside a stratum comes from ``rng``."""
    arrival = traffic["arrival"]
    n = min(m, max(1, round(seconds / float(arrival["stratum_s"]))))
    gaps = _deal(_gaps(arrival, m, fixed), n)
    lens = _deal(np.repeat(traffic["prompt"]["classes"],
                           _class_counts(traffic["prompt"]["weights"], m)), n)
    outs = _deal(_outputs(traffic["output"], m, fixed), n)
    due, start = [], 0.0
    for g in gaps:
        span = seconds * len(g) / m               # exactly the stated rate
        g = rng.permutation(g) * (span / g.sum())
        due.append(start + np.concatenate([[0.0], np.cumsum(g)[:-1]]))
        start += span
    return (np.concatenate(due),
            np.concatenate([rng.permutation(a) for a in lens]),
            np.concatenate([rng.permutation(a) for a in outs]))


def schedule(traffic: dict, seed: int, seconds: float, vocab: int,
             extra: float = 1.0) -> list[Req]:
    """Requests in due order: ``rate * seconds`` of them due in the
    measured window ``[0, seconds)``, then ``extra * seconds`` more at the
    same rate that keep the load on while the window's requests drain."""
    rate = float(traffic["arrival"]["rate_rps"])
    fixed = np.random.default_rng(_MULTISET_SEED)
    rng = np.random.default_rng(seed)
    reqs = []
    for start, span, inside in ((0.0, seconds, True),
                                (seconds, seconds * extra, False)):
        m = max(1, int(round(rate * span)))
        due, lens, outs = _block(traffic, m, span, fixed, rng)
        for t, s, b in zip(due, lens, outs):
            prompt = rng.integers(0, vocab, int(s), dtype=np.int32)
            reqs.append(Req(start + float(t), prompt, int(b), inside))
    return reqs


def warm_shapes(traffic: dict) -> list[tuple[int, int]]:
    """(prompt length, bucket size) pairs the window can admit."""
    w = traffic["warmup"]
    return [(s, k) for s in w["prompt_lens"] for k in w["buckets"]]
