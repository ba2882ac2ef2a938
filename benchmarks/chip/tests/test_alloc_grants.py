"""The ``alloc_grants_per_chunk`` reader on a synthetic ``TRACER``: page
grants (``alloc.grant`` spans) per decode chunk, both counted by start
inside the window, and nothing read from a program without the span."""

import time
import types

import pytest

import harness
from repro.obs import tracing

READ = harness.load_module(
    harness.HERE / "metrics" / "alloc_grants_per_chunk.py").read


def _run(t0, seconds):
    win = types.SimpleNamespace(t0=t0, t1=t0 + seconds, measured=[])
    return types.SimpleNamespace(cell={"name": "no-such-cell"}, win=win,
                                 trace=None)


def _chunk(grants):
    for _ in range(grants):
        with tracing.span("alloc.grant", args={"path": "batched"}):
            pass
    with tracing.span("pool.decode_chunk"):
        pass


def test_counts_only_spans_started_in_the_window():
    tracing.TRACER.clear()
    for _ in range(4):                          # before the window
        _chunk(3)
    t0 = time.perf_counter()
    for grants in (1, 2, 1, 2):
        _chunk(grants)
    run = _run(t0, time.perf_counter() - t0 + 1e-6)
    time.sleep(1e-3)
    for _ in range(4):                          # after the window
        _chunk(5)
    assert READ(run) == pytest.approx(6 / 4)
    tracing.TRACER.clear()


def test_none_without_grant_spans():
    tracing.TRACER.clear()
    t0 = time.perf_counter()
    _chunk(0)                                   # the parent: chunks only
    assert READ(_run(t0, 10.0)) is None
    tracing.TRACER.clear()
    assert READ(_run(t0, 10.0)) is None         # nothing recorded at all
