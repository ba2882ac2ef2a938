"""The split of device idle time by the program's host spans, and the
readers of the program's own events.

``data/kernels.xplane.pb`` (see ``test_trace.py``) is a v5e trace with no
program spans: it checks that the split takes the same operations and
the same window as ``devtrace``.  ``data/served.xplane.pb`` is a served
run on a TPU v5 lite at the harness's rehearsal sizes
(``harness.rehearsal_sizes``: ``serve_window`` over 2 s, traced from
0.5 s for 1 s), cut to the chip's plane and the host plane and to the
events that overlap a 20-ms stretch holding two ``gateway.tick`` spans
and a ``gateway.publish``, with the event names no kept event uses
dropped (86 KB)."""

import math
import pathlib
import types

import pytest

import devtrace
import harness
import hostspans
from repro.obs import tracing

DATA = pathlib.Path(__file__).resolve().parent / "data"
METRICS = harness.HERE / "metrics"


def reader(name):
    return harness.load_module(METRICS / f"{name}.py").read


def test_split_on_synthetic_intervals():
    # idle: [0, 10), [20, 30), [40, 50) = 30 of a 50-ns window
    ops = [(10, 20), (30, 40), (32, 38)]
    host = {"gateway.tick": [(5, 25)],
            "gateway.publish": [(22, 35), (45, 60)]}
    s = hostspans.split(ops, 0, 50, host)
    assert s.window_s == pytest.approx(50e-9)
    assert s.idle_s == pytest.approx(30e-9)
    assert s.tick_s == pytest.approx(10e-9)      # [5, 10) and [20, 25)
    assert s.publish_s == pytest.approx(10e-9)   # [25, 30) and [45, 50)
    assert s.unspanned_s == pytest.approx(10e-9)
    assert s.share(s.tick_s) + s.share(s.publish_s) + \
        s.share(s.unspanned_s) == pytest.approx(60.0)


def test_shift_moves_only_the_attribution():
    ops = [(10, 20), (30, 40)]
    host = {"gateway.tick": [(0, 10)], "gateway.publish": [(20, 30)]}
    s0 = hostspans.split(ops, 0, 40, host)
    assert (s0.tick_s, s0.publish_s, s0.unspanned_s) == pytest.approx(
        (10e-9, 10e-9, 0.0))
    s1 = hostspans.split(ops, 0, 40, host, shift_ns=2)   # device 2 ns late
    assert s1.idle_s == s0.idle_s
    assert (s1.tick_s, s1.publish_s) == pytest.approx((8e-9, 8e-9))
    assert s1.unspanned_s == pytest.approx(4e-9)


def test_interval_helpers():
    xs = [[0, 10], [20, 30]]
    assert hostspans._intersect(xs, [[5, 25]]) == [[5, 10], [20, 25]]
    assert hostspans._subtract(xs, [[5, 25]]) == [[0, 5], [25, 30]]
    assert hostspans._subtract(xs, [[-5, 40]]) == []
    assert hostspans._subtract(xs, []) == xs


def test_same_window_and_operations_as_devtrace():
    path = str(DATA / "kernels.xplane.pb")
    summary = devtrace.summarize(path)
    ops, lo, hi, host = hostspans.read_trace(path)
    assert (hi - lo) * 1e-9 == pytest.approx(summary.window_s, rel=1e-12)
    assert len(ops) == len(summary.ops)
    s = hostspans.split(ops, lo, hi, host)
    assert s.idle_s == pytest.approx(summary.window_s - summary.busy_s,
                                     rel=1e-9)
    assert host == {"gateway.tick": [], "gateway.publish": []}
    # the benchmark's own tick annotation stands in for the program's
    bench = [(a, b) for a, b, name in _host(path) if name == "bench.tick"]
    s = hostspans.split(ops, lo, hi, {"gateway.tick": bench})
    assert 0 < s.tick_s < s.idle_s


def test_served_trace_shares_sum_to_idle_share(monkeypatch):
    path = str(DATA / "served.xplane.pb")
    summary = devtrace.summarize(path)
    assert summary.chips == 1 and summary.ops
    ops, lo, hi, host = hostspans.read_trace(path)
    assert len(host["gateway.tick"]) == 2 and host["gateway.publish"]
    # the readers, pointed at this trace
    monkeypatch.setattr(hostspans, "run_trace", lambda run: path)
    run = types.SimpleNamespace(trace=summary, cell={"name": "served"})
    shares = [reader(n)(run) for n in (
        "idle_during_tick.served", "idle_during_publish.served",
        "idle_unspanned.served")]
    assert all(x > 0 for x in shares)
    assert sum(shares) == pytest.approx(reader("idle_share.served")(run),
                                        abs=1e-9)


def _host(path):
    from jax.profiler import ProfileData
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name == devtrace.HOST_PLANE
            for line in plane.lines for e in line.events]


def _run(t0=100.0, seconds=10.0, recs=(), trace=None):
    win = types.SimpleNamespace(t0=t0, t1=t0 + seconds, measured=list(recs))
    return types.SimpleNamespace(cell={"name": "no-such-cell"}, win=win,
                                 trace=trace)


def test_readers_find_nothing_without_the_programs_events():
    tracing.TRACER.clear()
    run = _run(recs=[types.SimpleNamespace(rid=0, due=1.0)])
    for name in ("seat_wait_p95_ms", "seat_to_stream_p95_ms",
                 "row_reads_per_chunk", "idle_during_tick.served",
                 "idle_during_publish.served", "idle_unspanned.served"):
        assert reader(name)(run) is None, name


def test_request_readers_match_by_rid():
    tracing.TRACER.clear()
    t0 = 100.0
    recs = [types.SimpleNamespace(rid=i, due=float(i)) for i in range(20)]
    for r in recs[:19]:                  # the last one was never seated
        due = t0 + r.due
        tracing.TRACER.instant("gateway.request", args={
            "rid": r.rid, "sid": r.rid, "submitted_s": due + 0.001,
            "seated_s": due + 0.01 * (r.rid + 1),
            "first_stream_s": due + 0.01 * (r.rid + 1) + 0.2,
            "finished_s": due + 1.0})
    tracing.TRACER.instant("gateway.request", args={
        "rid": 19, "sid": 19, "submitted_s": t0 + 19.001,
        "seated_s": None, "first_stream_s": None,
        "finished_s": t0 + 20.0})
    run = _run(t0=t0, seconds=30.0, recs=recs)
    # nearest rank: the 19th of 20 waits (10 ms steps), then inf
    assert reader("seat_wait_p95_ms")(run) == pytest.approx(190.0)
    assert reader("seat_to_stream_p95_ms")(run) == pytest.approx(200.0)
    run = _run(t0=t0, seconds=30.0, recs=recs[10:])   # 100..190 ms, inf
    assert reader("seat_wait_p95_ms")(run) == math.inf
    tracing.TRACER.clear()


def test_row_reads_per_chunk_counts_inside_the_window():
    import time
    tracing.TRACER.clear()
    t0 = time.perf_counter()
    for _ in range(3):
        with tracing.span("pool.decode_chunk"):
            pass
        for _ in range(5):
            with tracing.span("pool.read_row", args={"site": "stream"}):
                pass
    run = _run(t0=t0, seconds=time.perf_counter() - t0 + 1e-6)
    with tracing.span("pool.read_row"):         # after the window
        pass
    assert reader("row_reads_per_chunk")(run) == pytest.approx(5.0)
    tracing.TRACER.clear()
