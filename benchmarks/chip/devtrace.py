"""Reduce a ``jax.profiler`` trace to the device numbers the metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes under
``<dir>/plugins/profile/<time>/``, read with ``jax.profiler.ProfileData``
and nothing else.  On a TPU each chip is a plane ``/device:TPU:<n>``:

- its line ``XLA Ops`` holds one event per device operation, named by
  the operation's HLO text, so the name carries the kernel
  (``%flash_attention.3 = bf16[4,32,512,128]{...} custom-call(...)``)
  and the shapes of its result and operands;
- its line ``XLA Modules`` holds one event per program execution
  (``jit_prefill(<id>)``, ``jit_run(<id>)``, ``jit_gather_rows(<id>)``).

The host plane ``/host:CPU`` holds the benchmark's annotations
(``bench.tick``, ``bench.submit``, ``bench.deliver``) and the runtime's
events.  Device and host timestamps share a base; on the v5e traces seen
so far the device's run about 1 ms early against the host's, which only
the names of idle gaps use.

- busy: the union of a chip's operation intervals, averaged over chips;
- window: from the first to the last event of the trace;
- every operation of the first chip with its program (module);
- idle gaps: stretches of the first chip with no operation, each named
  by what the host was doing in its middle (the benchmark annotation
  open there, and the innermost host event).
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_OP_NAME = re.compile(r"^%([A-Za-z_][\w\-]*)[^ ]*\s*=")   # %name.3.remat =
_SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f(?:8\w*|16|32|64))"
                    r"\[([\d,]*)\]")
_OP_KIND = re.compile(r"[\}\)\]] ([a-z][\w\-]*)\(")   # "...} custom-call("
_ATTRS = re.compile(r"\)(?:, [a-z_]+=|$)")              # end of the operands
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


def short_name(hlo: str) -> str:
    """``%gather_rows.1 = ...`` -> ``gather_rows`` (``.N`` and ``.remat``
    suffixes dropped); modules ``jit_run(123)`` -> ``jit_run``."""
    m = _OP_NAME.match(hlo)
    if m:
        return m.group(1)
    return hlo.split("(")[0]


def shapes(hlo: str) -> tuple[list, list]:
    """(result shapes, operand shapes) of an HLO op text, each a list of
    (dtype, dims) in order."""
    rest = hlo.partition(" = ")[2]
    m = _OP_KIND.search(rest)
    if m is None:
        return [], []
    operands = _ATTRS.split(rest[m.end():], maxsplit=1)[0]

    def parse(txt):
        return [(d, tuple(int(x) for x in dims.split(",") if x))
                for d, dims in _SHAPE.findall(txt)]
    return parse(rest[:m.start() + 1]), parse(operands)


def nbytes(shape) -> int:
    dtype, dims = shape
    n = 1
    for d in dims:
        n *= d
    return n * _BYTES.get(dtype, 1)


@dataclasses.dataclass
class Op:
    name: str                      # full HLO text (ops) or module name
    start_ns: float
    dur_ns: float
    module: str = ""               # short name of the enclosing program

    @property
    def short(self) -> str:
        return short_name(self.name)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    chips: int
    ops: list                      # Ops of the first chip, in time order
    modules: list                  # its program executions, as Ops
    gaps: list                     # (label, seconds), longest first

    def kernel(self, name: str, module: str | None = None) -> list:
        """Ops of kernel ``name`` (optionally only inside programs whose
        short name is ``module``)."""
        return [o for o in self.ops if o.short == name
                and (module is None or o.module == module)]

    @functools.cached_property
    def _starts(self) -> list:
        return [o.start_ns for o in self.ops]

    def inside(self, module: Op) -> list:
        """Ops of the first chip inside one program execution."""
        i = bisect.bisect_left(self._starts, module.start_ns)
        j = bisect.bisect_left(self._starts, module.start_ns + module.dur_ns)
        return self.ops[i:j]

    def breakdown(self) -> dict:
        """The ten (program/operation) pairs with the most self time (an
        op's time less that of the ops nested in it, as a loop's body
        is in the loop), and the ten longest idle gaps."""
        by: dict = {}
        for o, self_ns in _self_times(self.ops):
            key = f"{o.module}/{o.short}"
            by[key] = by.get(key, 0.0) + self_ns * 1e-9
        top = sorted(by.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(ops: list) -> list:
    """(op, self ns) for ops that nest properly in time."""
    order = sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns))
    own = [o.dur_ns for o in order]
    stack: list = []
    for i, o in enumerate(order):
        while stack and order[stack[-1]].start_ns + order[stack[-1]].dur_ns \
                <= o.start_ns:
            stack.pop()
        if stack:
            own[stack[-1]] -= o.dur_ns
        stack.append(i)
    return list(zip(order, own))


def _attach_modules(ops: list, modules: list) -> None:
    j = 0
    for o in ops:
        while j < len(modules) and \
                modules[j].start_ns + modules[j].dur_ns <= o.start_ns:
            j += 1
        if j < len(modules) and modules[j].start_ns <= o.start_ns:
            o.module = modules[j].short


def summarize(path: str, min_gap_us: float = 50.0) -> Summary:
    """Summary of the trace at ``path``: an ``.xplane.pb`` file, or a
    directory holding the newest one under ``plugins/profile``."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = xplane_file(path)
    pd = ProfileData.from_file(path)
    devices, first_modules, host = [], [], []
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not is_dev and plane.name != HOST_PLANE:
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        for events in lines.values():
            for e in events:
                lo = min(lo, e.start_ns)
                hi = max(hi, e.start_ns + e.duration_ns)
        if is_dev:
            ops = sorted((Op(e.name, e.start_ns, e.duration_ns)
                          for e in lines.get(OPS_LINE, [])),
                         key=lambda o: o.start_ns)
            modules = sorted((Op(e.name, e.start_ns, e.duration_ns)
                              for e in lines.get(MODULES_LINE, [])),
                             key=lambda o: o.start_ns)
            _attach_modules(ops, modules)
            if not devices:
                first_modules = modules
            devices.append(ops)
        else:
            host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for events in lines.values() for e in events]
    busy = [sum(b - a for a, b in _union((o.start_ns, o.start_ns + o.dur_ns)
                                          for o in ops)) for ops in devices]
    gaps = []
    if devices:
        merged = _union((o.start_ns, o.start_ns + o.dur_ns)
                        for o in devices[0])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        host.sort()
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= min_gap_us * 1e3:
                gaps.append((_label(host, (a + b) / 2), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=max(hi - lo, 0.0) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9 if busy else 0.0,
        chips=len(devices), ops=devices[0] if devices else [],
        modules=first_modules, gaps=gaps)


def _label(host: list, t: float) -> str:
    """What the host was doing at ``t``: the benchmark's annotation open
    there (``idle`` if none) and the innermost host event around it."""
    bench, inner = "idle", None
    i = bisect.bisect_right(host, (t, float("inf"), ""))
    for a, b, name in host[max(0, i - 4000):i]:   # events that began before
        if b > t:
            if name.startswith("bench."):
                bench = name
            elif inner is None or b - a < inner[0]:
                inner = (b - a, name)
    return bench if inner is None else f"{bench}/{inner[1]}"
