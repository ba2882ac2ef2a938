"""Nestable spans over the serving stack, in wall-clock AND virtual time.

A span is one timed region of the host-side serving loop — gateway tick,
admission bucket, prefill launch, decode chunk, park/restore — recorded
with two clocks:

  * **wall clock** (``time.perf_counter``): what the machine spent.  The
    decode chunk's dispatch is async, so its span measures *dispatch +
    any blocking the caller already does* — the tracer never inserts a
    ``block_until_ready`` of its own (that would add a device sync inside
    the serving loop; ``tests/test_obs.py`` asserts it doesn't).
  * **virtual time** (the pool's ``decode_steps`` counter): the
    deterministic scheduling clock every SLO and benchmark is graded in.
    Callers pass ``vclock=lambda: pool.decode_steps``; the span records
    it at entry and exit, so a Perfetto view can correlate wall hiccups
    with virtual-step progress.

Recording is strictly host-side (list appends + ``perf_counter`` calls)
and happens **between** compiled calls, never inside a trace — the PR-6
trace-safety rule.  With ``REPRO_OBS=0`` every ``span()`` yields a shared
null handle and records nothing, so a disabled tracer costs one env
lookup per call and the event buffer never grows.

Spans nest per-thread (the gateway's tick worker thread gets its own
stack and its events carry its tid), and :mod:`repro.obs.export` renders
the buffer as Chrome/Perfetto ``trace_event`` JSON.

Every span and instant also goes to a second sink: a
``jax.profiler.TraceAnnotation`` of the same name, with its arguments as
scalars or short strings.  While a ``jax.profiler`` trace runs, the
profiler stamps it on its host plane, on the thread that ran it and on
the clock it aligns with the device planes, so a device gap can be
blamed on the host work open across it.  With no trace running an
annotation costs about a microsecond.  ``REPRO_OBS=0`` turns both sinks
off.

Backend compiles are recorded too: a ``jax.monitoring`` listener turns
each (a compile, or a load from the persistent compile cache) into one
``runtime.compile`` instant, with its seconds and the span open on the
compiling thread, and counts it in ``repro_runtime_compiles_total``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable

import jax
from jax.profiler import TraceAnnotation

from .metrics import counter, enabled

_ANNOTATION_STR = 64               # longest string argument an annotation takes


@dataclasses.dataclass
class SpanEvent:
    """One finished span (or instant, ``dur is None``)."""
    name: str
    cat: str
    ts: float                      # perf_counter seconds at entry
    dur: float | None              # wall seconds (None for instants)
    tid: int
    depth: int                     # nesting depth within its thread
    vstep: int | None = None       # virtual decode-step clock at entry
    vdur: int | None = None        # virtual steps elapsed inside the span
    args: dict[str, Any] | None = None


class _SpanHandle:
    """Live span: mutate ``args`` inside the ``with`` to annotate it."""

    __slots__ = ("args",)

    def __init__(self, args: dict[str, Any]):
        self.args = args


_NULL_HANDLE = _SpanHandle({})


class Tracer:
    """The event buffer + per-thread span stacks.

    The buffer is a deque: unbounded by default (post-hoc ``write_trace``
    wants everything), boundable via :meth:`set_limit` for live serving —
    a week-long run then holds at most ``max_events`` completed spans and
    the streaming exporter (:mod:`repro.obs.live`) renders from its own
    bounded ring.  **Sinks** are the live-plane hook: every completed
    event is also pushed to each registered callback (host-side, after
    the span closed — never inside it)."""

    def __init__(self, max_events: int | None = None):
        self.events: collections.deque[SpanEvent] = \
            collections.deque(maxlen=max_events)
        self._sinks: list[Callable[[SpanEvent], None]] = []
        self._local = threading.local()
        self._lock = threading.Lock()

    def _stack(self) -> list[str]:
        """Names of the spans open on this thread, outermost first."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _depth(self) -> int:
        return len(self._stack())

    def open_span(self) -> str | None:
        """The innermost span open on the calling thread, if any."""
        stack = self._stack()
        return stack[-1] if stack else None

    @property
    def max_events(self) -> int | None:
        return self.events.maxlen

    def set_limit(self, max_events: int | None) -> None:
        """Bound (or unbound) the buffer in place, keeping the newest
        events.  The live HTTP plane calls this so the process-global
        tracer cannot grow without bound under continuous traffic."""
        with self._lock:
            self.events = collections.deque(self.events, maxlen=max_events)

    def add_sink(self, sink: Callable[[SpanEvent], None]) -> None:
        """Register a per-event callback (e.g. a ``live.TraceRing``).
        Sinks run on the recording thread between compiled calls — keep
        them O(1) host work."""
        with self._lock:
            if sink not in self._sinks:
                self._sinks.append(sink)

    def remove_sink(self, sink: Callable[[SpanEvent], None]) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)

    def _emit(self, ev: SpanEvent) -> None:
        with self._lock:
            self.events.append(ev)
            sinks = list(self._sinks)
        for sink in sinks:
            sink(ev)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "serve",
             vclock: Callable[[], int] | None = None,
             args: dict[str, Any] | None = None):
        """Record one nested region.  ``vclock`` (if given) is sampled at
        entry and exit on the host — pass a closure over a host mirror,
        never a device read."""
        if not enabled():
            yield _NULL_HANDLE
            return
        stack = self._stack()
        depth = len(stack)
        stack.append(name)
        handle = _SpanHandle(dict(args) if args else {})
        first = args or {}
        v0 = int(vclock()) if vclock is not None else None
        t0 = time.perf_counter()
        ann = _annotation(name, first)
        ann.__enter__()
        try:
            yield handle
        finally:
            dur = time.perf_counter() - t0
            if TraceAnnotation.is_enabled():
                later = {k: v for k, v in handle.args.items()
                         if k not in first or first[k] is not v}
                if later:
                    ann.set_metadata(**_scalars(later))
            ann.__exit__(None, None, None)
            v1 = int(vclock()) if vclock is not None else None
            del stack[depth:]
            ev = SpanEvent(name=name, cat=cat, ts=t0, dur=dur,
                           tid=threading.get_ident(), depth=depth,
                           vstep=v0,
                           vdur=(v1 - v0) if v0 is not None else None,
                           args=handle.args or None)
            self._emit(ev)

    def instant(self, name: str, cat: str = "serve",
                vstep: int | None = None,
                args: dict[str, Any] | None = None) -> None:
        """Record a zero-duration marker (a finished request, a compile)."""
        if not enabled():
            return
        with _annotation(name, args):
            pass
        ev = SpanEvent(name=name, cat=cat, ts=time.perf_counter(),
                       dur=None, tid=threading.get_ident(),
                       depth=self._depth(),
                       vstep=int(vstep) if vstep is not None else None,
                       args=dict(args) if args else None)
        self._emit(ev)

    def counter(self, name: str, value, cat: str = "serve") -> None:
        """Record a Chrome counter-track sample (rendered as ``ph: "C"``)."""
        if not enabled():
            return
        ev = SpanEvent(name=name, cat="__counter__." + cat,
                       ts=time.perf_counter(), dur=None,
                       tid=threading.get_ident(), depth=0,
                       args={"value": value})
        self._emit(ev)

    def spans(self, name: str | None = None) -> list[SpanEvent]:
        """Snapshot of recorded events, optionally filtered by exact name."""
        with self._lock:
            evs = list(self.events)
        if name is None:
            return evs
        return [e for e in evs if e.name == name]

    def clear(self) -> None:
        with self._lock:
            self.events.clear()


def _scalar(v):
    """An annotation argument: a number as it is, anything else as a
    short string (lists space-separated; ``,``, ``#`` and ``=`` would cut
    the profiler's encoding of the arguments)."""
    if isinstance(v, (bool, int, float)):
        return v
    if isinstance(v, (list, tuple)):
        v = " ".join(str(x) for x in v)
    v = str(v)
    for c in ",#=":
        v = v.replace(c, " ")
    return v[:_ANNOTATION_STR]


def _scalars(args: dict[str, Any]) -> dict[str, Any]:
    return {k: _scalar(v) for k, v in args.items() if v is not None}


def _annotation(name: str, args: dict[str, Any] | None) -> TraceAnnotation:
    """The profiler's twin of a span; its arguments are converted only
    while a trace is running."""
    if args and TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **_scalars(args))
    return TraceAnnotation(name)


#: the process-global tracer the serving layers record through
TRACER = Tracer()

_COMPILES = counter("repro_runtime_compiles_total",
                    "backend compiles (or persistent-cache loads)")


def _on_duration(event: str, duration: float, **_) -> None:
    """``jax.monitoring`` listener: one ``runtime.compile`` instant per
    backend compile, naming the span open on the compiling thread."""
    if not event.endswith("backend_compile_duration"):
        return
    _COMPILES.default.inc()
    TRACER.instant("runtime.compile", cat="runtime",
                   args={"seconds": float(duration),
                         "span": TRACER.open_span() or ""})


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def span(name: str, cat: str = "serve",
         vclock: Callable[[], int] | None = None,
         args: dict[str, Any] | None = None):
    """``with tracing.span("pool.decode_chunk", vclock=...):`` — the
    module-level convenience over :data:`TRACER`."""
    return TRACER.span(name, cat=cat, vclock=vclock, args=args)


def instant(name: str, cat: str = "serve", vstep: int | None = None,
            args: dict[str, Any] | None = None) -> None:
    TRACER.instant(name, cat=cat, vstep=vstep, args=args)
