"""The autotune / calibration cache shared by the cost model and kernels.

Both the cost-aware scheduler (``repro.cpm.program.costmodel``) and the
self-tuning pallas layer (``repro.cpm.backends.pallas`` section choice,
``repro.cpm.program.executors`` fused-stream row blocking) need the same
two things:

  * a **memoization surface** keyed by a string the caller derives from
    ``(op-stream-signature, shape, dtype, backend)`` — an in-process dict
    backed by a JSON spill so decisions survive across processes (CI
    uploads the spill next to the BENCH files);
  * a **timing harness** that measures candidate realizations on
    synthesized inputs.  Measurement only happens **outside any active
    trace** (:func:`measurable`): under ``jit``/``make_jaxpr``,
    omnistaging would stage every "timed" dispatch into the caller's
    jaxpr — measuring tracing instead of execution and polluting the
    traced program — so traced callers get cache hits (decisions made
    earlier, eagerly) or their static defaults.

Environment knobs:

  * ``REPRO_CPM_TUNING_CACHE`` — spill path (default
    ``~/.cache/repro/cpm_tuning.json``).  Set it into the workspace in CI
    so the artifact rides along with ``BENCH_*.json``.
  * ``REPRO_CPM_AUTOTUNE=0`` — disable measurement: every lookup misses
    and callers fall back to their static defaults (useful for
    deterministic debugging).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

#: in-process cache: key -> JSON-serializable decision value
_MEM: dict[str, Any] = {}
_LOADED = False


def cache_path() -> str:
    return os.environ.get(
        "REPRO_CPM_TUNING_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "cpm_tuning.json"))


def tuning_enabled() -> bool:
    return os.environ.get("REPRO_CPM_AUTOTUNE", "1") != "0"


def _load_spill() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    try:
        with open(cache_path()) as f:
            spill = json.load(f)
        if isinstance(spill, dict):
            for k, v in spill.items():
                _MEM.setdefault(k, v)
    except (OSError, ValueError):
        pass


def lookup(key: str):
    """Cached decision for ``key`` or None (miss)."""
    _load_spill()
    return _MEM.get(key)


def store(key: str, value) -> None:
    """Record a decision and spill the whole cache to JSON (best effort:
    an unwritable cache path degrades to in-process memoization only)."""
    _load_spill()
    _MEM[key] = value
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(_MEM, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


def clear(in_process_only: bool = True) -> None:
    """Drop cached decisions (tests)."""
    global _LOADED
    _MEM.clear()
    _LOADED = in_process_only   # True keeps the spill from reloading


def entries(prefix: str = "") -> dict:
    """Snapshot of cached decisions whose key starts with ``prefix``
    (benchmarks report the tuner's choices; CI ships them as an artifact)."""
    _load_spill()
    return {k: v for k, v in _MEM.items() if k.startswith(prefix)}


def backend_key(interpret: bool) -> str:
    """The backend axis of every cache key: pallas kernels behave like a
    different machine under the interpreter than compiled on TPU."""
    return (f"pallas-{'interpret' if interpret else 'compiled'}"
            f"-{jax.default_backend()}")


def measurable() -> bool:
    """True when no trace is active, i.e. candidate timing would measure
    real execution.  Inside ``jit``/``vmap``/``make_jaxpr`` tracing, a
    "timed" jit dispatch is *staged* into the enclosing jaxpr instead of
    run (omnistaging), so the wall clock would measure tracing and the
    staged calls would pollute the traced program — callers must skip
    measurement and fall back to cached decisions or static defaults.
    The one "is a trace active" check of the repo (``obs.cycles`` uses it
    too)."""
    return jax.core.trace_ctx.is_top_level()


def synth(shape, dtype):
    """Concrete zeros for candidate timing.  Forced concrete (instead of
    a bare ``jnp.zeros``) so a caller probing the cache from inside a
    trace does not leave staged zero-constants behind in the enclosing
    jaxpr.  Note ``jax.ensure_compile_time_eval`` must stay *out* of any
    pallas dispatch path: an ambient eval trace makes kernel-internal
    index math concrete, which ``pallas_call`` rejects as captured
    constants — hence zeros-only here, and :func:`measurable` gating
    every actual timing."""
    with jax.ensure_compile_time_eval():
        return jnp.zeros(shape, dtype)


def time_call(fn: Callable[[], Any], reps: int = 5) -> float:
    """Best-of-``reps`` wall-clock seconds of ``fn()`` after one warmup
    (the warmup also pays compilation).  Only meaningful when
    :func:`measurable` — callers gate on it."""
    out = fn()
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def pick(key: str, candidates: list, run: Callable[[Any], Any],
         default, reps: int = 3):
    """Cached argmin-time choice among ``candidates``.

    ``run(c)`` executes one candidate on synthesized inputs.  Callers
    offer only candidates the shape can take (a shape rule, applied before
    timing), so a candidate that fails to compile or run is a fault and
    its error propagates.  With tuning disabled or an active trace (see
    :func:`measurable`), returns ``default`` without caching, so the
    decision can be made later under better conditions.
    """
    cached = lookup(key)
    if cached is not None:
        return cached
    if not tuning_enabled() or not measurable() or not candidates:
        return default
    times = {c: time_call(lambda: run(c), reps=reps) for c in candidates}
    best = min(candidates, key=times.__getitem__)
    store(key, best)
    return best
