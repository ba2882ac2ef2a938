"""The program's own spans, read beside the device trace.

The program (``repro.obs``) records each span twice: into
``repro.obs.TRACER``, in process memory on the ``perf_counter`` clock the
harness also uses, and as a ``jax.profiler.TraceAnnotation`` of the same
name, which the profiler stamps on the host plane of the trace on the
clock it aligns with the device planes.  This module reads both:

- :func:`idle_split` splits the idle time of the first chip, exactly as
  ``devtrace.summarize`` counts it (the same operation intervals, the
  same window from the first to the last event, every gap however short),
  by what the host had open across it: a ``gateway.tick``, else a
  ``gateway.publish``, else neither.  The three parts sum to the idle
  share;
- :func:`events` returns the ``TRACER``'s events of one name, and
  :func:`request_stamps` the wall stamps of each request by its ``rid``.

A program without these spans gives nothing here, and the readers that
use this module then return ``None``.  Device timestamps are left as
the profiler wrote them; ``shift_ns`` exists to measure how far a
misalignment of the two clocks would move the split.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import math
import os

import devtrace
from harness import ROOT

TICK = "gateway.tick"
PUBLISH = "gateway.publish"


@dataclasses.dataclass(frozen=True)
class Split:
    window_s: float
    idle_s: float
    tick_s: float                  # idle while a gateway.tick was open
    publish_s: float               # ... else while a gateway.publish was

    @property
    def unspanned_s(self) -> float:
        return self.idle_s - self.tick_s - self.publish_s

    def share(self, seconds: float) -> float:
        """Percent of the traced window."""
        return 100.0 * seconds / self.window_s


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _intersect(xs, ys) -> list:
    """Overlap of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if lo < hi:
            out.append([lo, hi])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(xs, ys) -> list:
    """``xs`` less ``ys``, both sorted lists of disjoint intervals."""
    out = []
    for a, b in xs:
        for c, d in ys:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append([a, c])
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append([a, b])
    return out


def split(ops, lo: float, hi: float, host: dict,
          shift_ns: float = 0.0) -> Split:
    """Split the idle time of one chip over the window ``[lo, hi]`` (ns).

    ``ops`` are the chip's operation intervals and ``host`` maps a span
    name to its host intervals.  ``shift_ns`` is added to the device's
    clock (applied to the host intervals, so the idle time itself does
    not move)."""
    busy = devtrace._union(ops)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [[a, b] for a, b in zip(edges[0::2], edges[1::2]) if b > a]

    def spans(name):
        return devtrace._union((a - shift_ns, b - shift_ns)
                               for a, b in host.get(name, ()))

    tick = spans(TICK)
    publish = _subtract(spans(PUBLISH), tick)
    return Split(window_s=(hi - lo) * 1e-9,
                 idle_s=_length(idle) * 1e-9,
                 tick_s=_length(_intersect(idle, tick)) * 1e-9,
                 publish_s=_length(_intersect(idle, publish)) * 1e-9)


@functools.lru_cache(maxsize=4)
def read_trace(path: str) -> tuple:
    """``(ops, lo, hi, host)`` of the ``.xplane.pb`` at ``path``: the
    first chip's operation intervals, the window as ``devtrace.summarize``
    takes it (ns; like it, from one line per line name of each plane),
    and the host intervals of the program's tick and publish spans (from
    every host line: each thread has one, and threads share names)."""
    from jax.profiler import ProfileData
    ops, host = None, {TICK: [], PUBLISH: []}
    lo, hi = math.inf, -math.inf
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(devtrace.DEVICE_PLANE.match(plane.name))
        if not is_dev and plane.name != devtrace.HOST_PLANE:
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        for events in lines.values():
            for e in events:
                lo = min(lo, e.start_ns)
                hi = max(hi, e.start_ns + e.duration_ns)
        if is_dev and ops is None:
            ops = [(e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines.get(devtrace.OPS_LINE, [])]
        elif not is_dev:
            for line in plane.lines:
                for e in line.events:
                    if e.name in host:
                        host[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return ops or [], lo, hi, host


def run_trace(run) -> str | None:
    """The newest ``.xplane.pb`` the run's cell wrote (``run.py`` clears
    the directory before it traces)."""
    files = glob.glob(os.path.join(
        ROOT, ".bench_traces", run.cell["name"] + ".*", "plugins",
        "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def idle_split(run, shift_ns: float = 0.0) -> Split | None:
    """The split of the run's traced window, or ``None`` without a trace
    or without the program's spans in it."""
    if run.trace is None:
        return None
    path = run_trace(run)
    if path is None:
        return None
    ops, lo, hi, host = read_trace(path)
    if not any(host.values()) or not hi > lo:
        return None
    return split(ops, lo, hi, host, shift_ns)


def events(name: str) -> list:
    """The program's recorded events of one name (``[]`` if it records
    none)."""
    from repro.obs import TRACER
    return TRACER.spans(name)


def request_stamps() -> dict | None:
    """``{rid: args}`` of the program's ``gateway.request`` events: the
    request's ``sid`` and its ``submitted_s``, ``seated_s``,
    ``first_stream_s`` and ``finished_s`` (``perf_counter`` seconds,
    ``None`` if never taken)."""
    evs = events("gateway.request")
    return {e.args["rid"]: e.args for e in evs} if evs else None


def stamp(stamps: dict, rid: int, key: str) -> float:
    """One wall stamp of request ``rid``; ``nan`` if it was never taken
    or the request has no event."""
    v = stamps.get(rid, {}).get(key)
    return math.nan if v is None else v


def in_window(evs, run) -> int:
    """How many of ``evs`` started inside the measured window."""
    return sum(run.win.t0 <= e.ts < run.win.t1 for e in evs)
