"""Shared test configuration: vendored `hypothesis` fallback.

`test_core.py` / `test_properties.py` / `test_kv_cache.py` import
`hypothesis` at module scope, which made the whole suite error at
collection in containers that don't ship it.  If the real package is
missing we install a minimal, deterministic shim into ``sys.modules``
before test modules import: `@given` draws a fixed-seed batch of examples
per test (no shrinking, no database — just enough strategy surface for
this repo's property tests).  Installing the real thing
(``pip install -e .[test]``) transparently takes precedence.

The shim caps examples at ``REPRO_SHIM_MAX_EXAMPLES`` (default 10) so the
CPU suite stays fast; the real hypothesis honors each test's own
``max_examples``.
"""

from __future__ import annotations

import functools
import inspect
import os
import random
import sys
import types

try:                                     # real hypothesis wins if installed
    import hypothesis  # noqa: F401
except ImportError:
    import numpy as _np

    _SHIM_CAP = int(os.environ.get("REPRO_SHIM_MAX_EXAMPLES", "10"))

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    def integers(min_value=0, max_value=1 << 16):
        return _Strategy(lambda r: r.randint(min_value, max_value))

    def booleans():
        return _Strategy(lambda r: r.random() < 0.5)

    def floats(min_value=0.0, max_value=1.0, allow_nan=False,
               allow_infinity=False, width=64, allow_subnormal=True):
        ftype = _np.float32 if width == 32 else _np.float64
        tiny = float(_np.finfo(ftype).tiny)

        def draw(r):
            v = r.uniform(min_value, max_value)
            if width == 32:
                v = float(_np.float32(v))
            if not allow_subnormal and 0 < abs(v) < tiny:
                v = 0.0
            return v
        return _Strategy(draw)

    def lists(elements, min_size=0, max_size=None):
        mx = (min_size + 10) if max_size is None else max_size
        return _Strategy(
            lambda r: [elements.draw(r) for _ in range(r.randint(min_size, mx))])

    def text(alphabet="abcdefghij", min_size=0, max_size=None):
        mx = (min_size + 10) if max_size is None else max_size
        chars = list(alphabet)
        return _Strategy(
            lambda r: "".join(r.choice(chars)
                              for _ in range(r.randint(min_size, mx))))

    def sampled_from(seq):
        items = list(seq)
        return _Strategy(lambda r: r.choice(items))

    def tuples(*strats):
        return _Strategy(lambda r: tuple(s.draw(r) for s in strats))

    def given(*strats):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                n = min(getattr(wrapper, "_shim_max_examples", 20), _SHIM_CAP)
                for i in range(n):
                    r = random.Random(f"{fn.__module__}.{fn.__qualname__}:{i}")
                    fn(*args, *[s.draw(r) for s in strats], **kwargs)
            # hide strategy-filled params from pytest's fixture resolution:
            # expose only the leading (e.g. `self`) parameters
            params = list(inspect.signature(fn).parameters.values())
            wrapper.__signature__ = inspect.Signature(
                params[: len(params) - len(strats)])
            del wrapper.__wrapped__
            return wrapper
        return deco

    def settings(max_examples=20, deadline=None, **_kw):
        def deco(fn):
            fn._shim_max_examples = max_examples       # read at call time
            return fn
        return deco

    _h = types.ModuleType("hypothesis")
    _h.__doc__ = "Minimal deterministic shim (see tests/conftest.py)."
    _st = types.ModuleType("hypothesis.strategies")
    for _name, _obj in [("integers", integers), ("booleans", booleans),
                        ("floats", floats), ("lists", lists), ("text", text),
                        ("sampled_from", sampled_from), ("tuples", tuples)]:
        setattr(_st, _name, _obj)
    _h.given = given
    _h.settings = settings
    _h.strategies = _st
    sys.modules["hypothesis"] = _h
    sys.modules["hypothesis.strategies"] = _st


# ---------------------------------------------------------------------------
# telemetry isolation between test modules
# ---------------------------------------------------------------------------
#
# The obs registry and tracer are process-global by design (a serving
# process has exactly one /metrics endpoint).  Under pytest that design
# leaks state across test modules: a counter bumped by test_gateway.py
# would still be non-zero when test_obs.py snapshots the registry.  This
# autouse fixture resets both at every module boundary.  It deliberately
# uses Registry.reset() (zero values in place) rather than clear():
# serving objects hold live series references via series_property, and
# clearing would orphan them.  Pinned by
# tests/test_obs_live.py::TestRegistryReset.

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _obs_module_isolation():
    from repro.obs import metrics as _m
    from repro.obs import tracing as _t
    _m.REGISTRY.reset()
    _t.TRACER.clear()
    yield
