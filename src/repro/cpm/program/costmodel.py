"""The launch/byte wall-clock cost model behind cost-aware fusion.

The §3–§8 cycle model (``scheduler.program_steps``) prices programs in
*concurrent steps* — the paper's currency.  It says nothing about what a
kernel **launch** costs on a physical backend, which is exactly what
decides whether fusing a run of elementwise ops into one
``fused_stream`` mega-kernel is a win:

  * compiled on TPU, a launch has real cost and the fused group's single
    launch amortizes it over the whole run (the PR-4 premise);
  * under the Pallas interpreter on CPU/GPU hosts, "launches" are free —
    eager per-op dispatch jit-fuses into one XLA program while the
    mega-kernel adds interpreter overhead and blocks XLA fusion, which is
    how the committed ``BENCH_program_fusion.json`` ended up at 0.75x
    eager.

So the model prices a fusable run both ways in seconds::

    eager(group) = launches · L_e  + passes · bytes · c_e
    fused(group) = L_f            + passes · bytes · c_f

with per-op ``passes``/``launches`` read off the op table's cost metadata
(``OpSpec.passes`` / ``OpSpec.eager_launches``) and the four coefficients
either

  * **calibrated** — a one-time microbenchmark per backend key: a small
    fixed probe stream timed fused vs eager at two sizes, solved for the
    launch intercepts and per-byte slopes, spilled to the tuning-cache
    JSON (``repro.cpm.tuning``) for reuse across runs; or
  * **roofline priors** — the device's HBM bandwidth
    (``analysis.roofline.hw``) plus a nominal launch cost, used where
    measurement is impossible or disabled (``REPRO_CPM_CALIBRATE=0``).
    The priors make fusion profitable for any multi-op run — the correct
    TPU-side default.

``schedule(prog, device=...)`` consults :func:`decide` per fusable run
and records the verdict in the emitted :class:`FusionGroup`.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.roofline import hw

from .. import tuning

#: nominal TPU-side kernel launch overhead (seconds) for the roofline
#: prior — order of a grid dispatch; only its *ratio* to the byte terms
#: matters for the fuse/eager sign
NOMINAL_LAUNCH_S = 2e-6

#: probe stream sizes (elements) for the two-point calibration fit
_PROBE_SIZES = (512, 8192)
_PROBE_REPS = 5


def calibration_enabled() -> bool:
    return os.environ.get("REPRO_CPM_CALIBRATE", "1") != "0"


@dataclass(frozen=True)
class CostParams:
    """Per-backend launch/byte coefficients (seconds / seconds-per-byte)."""
    launch_s: float            # eager per-op launch intercept  (L_e)
    eager_byte_s: float        # eager per-pass byte slope      (c_e)
    fused_launch_s: float      # fused single-launch intercept  (L_f)
    fused_byte_s: float        # fused per-pass byte slope      (c_f)
    source: str = "roofline"   # "calibrated" | "roofline" | "override"

    def as_dict(self) -> dict:
        return {"launch_s": self.launch_s,
                "eager_byte_s": self.eager_byte_s,
                "fused_launch_s": self.fused_launch_s,
                "fused_byte_s": self.fused_byte_s,
                "source": self.source}


def roofline_params() -> CostParams:
    """Priors from the roofline peaks table: byte slopes at the device's
    HBM bandwidth (identical for both paths — launches decide), nominal
    launch cost."""
    byte_s = 1.0 / hw()["hbm_bw"]
    return CostParams(NOMINAL_LAUNCH_S, byte_s, NOMINAL_LAUNCH_S, byte_s,
                      source="roofline")


# ---------------------------------------------------------------------------
# one-time microbenchmark calibration
# ---------------------------------------------------------------------------

def _probe_program(n: int):
    from .ir import CPMProgram
    return (CPMProgram()
            .append("shift", start=0, end=n // 2, shift=1, fill=0)
            .append("compare", datum=3, op="lt")
            .append("activate", start=0, end=n - 1, carry=1)
            .append("stencil", taps=(1.0, 2.0, 1.0), wrap=False))


def _time_probe(n: int, interpret: bool) -> tuple[float, float]:
    """(fused_s, eager_s) of the 4-op probe stream at size ``n``."""
    from ..array import CPMArray
    from . import executors
    from .scheduler import FusionGroup, FusionPlan, schedule

    prog = _probe_program(n)
    fused_plan = schedule(prog)                      # fuse-all baseline
    eager_plan = FusionPlan(prog, tuple(
        FusionGroup("eager", (i,), (ins,))
        for i, ins in enumerate(prog.instructions)))
    data = tuning.synth((n,), jnp.int32)

    def runner(plan):
        def go(d):
            arr = CPMArray(d, n, backend="pallas", interpret=interpret)
            cur, outs = executors.run_plan(plan, arr, backend="pallas",
                                           interpret=interpret)
            return cur.data, [o for o in outs if o is not None]
        return jax.jit(go)

    f_fused, f_eager = runner(fused_plan), runner(eager_plan)
    t_fused = tuning.time_call(lambda: f_fused(data), reps=_PROBE_REPS)
    t_eager = tuning.time_call(lambda: f_eager(data), reps=_PROBE_REPS)
    return t_fused, t_eager


def calibrate(interpret: bool) -> CostParams:
    """Fit the four coefficients from the probe at two sizes (int32, one
    row, k=4 ops): intercept = launch term, slope = per-byte term."""
    k = len(_probe_program(8).instructions)
    n1, n2 = _PROBE_SIZES
    b1, b2 = n1 * 4, n2 * 4
    tf1, te1 = _time_probe(n1, interpret)
    tf2, te2 = _time_probe(n2, interpret)
    c_e = max((te2 - te1) / (k * (b2 - b1)), 1e-15)
    c_f = max((tf2 - tf1) / (k * (b2 - b1)), 1e-15)
    l_e = max(te1 / k - c_e * b1, 1e-9)
    l_f = max(tf1 - k * c_f * b1, 1e-9)
    return CostParams(l_e, c_e, l_f, c_f, source="calibrated")


def params_for(interpret: bool) -> CostParams:
    """The coefficients for one backend key: tuning-cache hit, else a
    fresh calibration (spilled), else — calibration off, or under a
    trace — the roofline priors.  A calibration that fails to compile or
    run raises: it is a fault of the kernels, not a reason to guess."""
    key = f"calib:{tuning.backend_key(interpret)}"
    cached = tuning.lookup(key)
    if isinstance(cached, dict):
        try:
            return CostParams(**cached)
        except TypeError:
            pass
    if not calibration_enabled() or not tuning.measurable():
        # under an active trace the probe would be staged, not timed —
        # price with the roofline priors (uncached, so a later eager
        # schedule still gets to calibrate)
        return roofline_params()
    params = calibrate(interpret)
    tuning.store(key, params.as_dict())
    return params


# ---------------------------------------------------------------------------
# the per-group decision
# ---------------------------------------------------------------------------

def _cost_meta(instr, n: int) -> tuple[int, int]:
    """(row passes, eager launches) of one instruction — op-table cost
    metadata, with the concurrent-step formula as the passes fallback."""
    from ..optable import OP_TABLE
    from .ir import DERIVED_METHODS
    from .scheduler import _instr_m

    spec = OP_TABLE[DERIVED_METHODS.get(instr.op, instr.op)]
    m = _instr_m(instr)
    if spec.passes is not None:
        return int(spec.passes(n=n, m=m)), spec.eager_launches
    return int(spec.steps(n=n, m=m)), spec.eager_launches


def group_cost(instructions, rows: int, n: int, itemsize: int,
               params: CostParams) -> tuple[float, float]:
    """Predicted (fused_s, eager_s) of one fusable run on ``rows`` rows of
    ``n`` elements."""
    nbytes = rows * n * itemsize
    passes = launches = 0
    for instr in instructions:
        p, l = _cost_meta(instr, n)
        passes += p
        launches += l
    eager_s = launches * params.launch_s + passes * nbytes * params.eager_byte_s
    fused_s = params.fused_launch_s + passes * nbytes * params.fused_byte_s
    return fused_s, eager_s


#: fuse only on a predicted *clear* win.  Eager per-op dispatch is the
#: safe baseline (same instructions, bit-identical results), while the
#: coefficients behind a near-tie prediction carry microbenchmark noise —
#: hysteresis keeps borderline runs on the structure that cannot regress.
#: Launch-bound regimes (the TPU case fusion exists for) predict ratios
#: far below this margin, so it never costs a real win.
FUSE_MARGIN = 0.85

#: when a *calibrated* prediction lands in this fused/eager ratio band,
#: the fit's noise exceeds the predicted gap — settle the verdict by
#: timing the actual group both ways on synthesized inputs instead
#: (cached per (op-stream, shape, dtype, backend) in the tuning spill).
#: Roofline priors and explicit overrides are never second-guessed.
MEASURE_BAND = (0.5, 1.5)
_MEASURE_REPS = 3


def _synth(v):
    """A timing stand-in for one recorded operand: arrays (including
    tracers — decisions can happen at trace time) become concrete zeros
    of the same shape/dtype; static Python values pass through."""
    if isinstance(v, (jax.Array, np.ndarray)):
        return tuning.synth(jnp.shape(v), v.dtype)
    return v


def _measured_fuse(instructions, lead, n: int, dtype,
                   interpret: bool) -> dict | None:
    """Time the run fused vs eager on a synthesized device of the real
    geometry; returns the verdict dict or None (cache miss while tuning
    is off or a trace is active).  Measurement errors propagate."""
    from ..array import CPMArray
    from . import executors
    from .ir import CPMProgram
    from .scheduler import FusionGroup, FusionPlan, schedule

    sig = "+".join(i.op for i in instructions)
    key = (f"fuse:{sig}|{'x'.join(str(d) for d in lead) or 1}x{n}"
           f"|{jnp.dtype(dtype).name}|{tuning.backend_key(interpret)}")
    cached = tuning.lookup(key)
    if isinstance(cached, dict):
        return dict(cached, params="measured")
    if not tuning.tuning_enabled() or not tuning.measurable():
        return None

    prog = CPMProgram()
    for ins in instructions:
        prog = prog.append(ins.op,
                           **{k: _synth(v) for k, v in ins.operands.items()})
    fused_plan = schedule(prog)                  # bare: fuse-all, no device
    eager_plan = FusionPlan(prog, tuple(
        FusionGroup("eager", (i,), (ins,))
        for i, ins in enumerate(prog.instructions)))
    data = tuning.synth((*lead, n), dtype)
    used = jnp.full(lead, n, jnp.int32) if lead else n

    def runner(plan):
        def go(d):
            arr = CPMArray(d, used, backend="pallas", interpret=interpret)
            cur, outs = executors.run_plan(plan, arr, backend="pallas",
                                           interpret=interpret)
            return cur.data, [o for o in outs if o is not None]
        return jax.jit(go)

    f_fused, f_eager = runner(fused_plan), runner(eager_plan)
    t_fused = tuning.time_call(lambda: f_fused(data), reps=_MEASURE_REPS)
    t_eager = tuning.time_call(lambda: f_eager(data), reps=_MEASURE_REPS)
    verdict = {"fuse": bool(t_fused <= t_eager),
               "fused_us": t_fused * 1e6, "eager_us": t_eager * 1e6}
    tuning.store(key, verdict)
    return dict(verdict, params="measured")


def decide(instructions, rows: int, n: int, itemsize: int,
           params: CostParams, *, lead=(), dtype=None,
           interpret: bool | None = None) -> dict:
    """The scheduler's per-run verdict, recorded in the FusionGroup.

    Model-predicted from ``params``; a borderline *calibrated* prediction
    (ratio inside ``MEASURE_BAND``) is settled by direct measurement when
    the caller supplies ``dtype``/``interpret`` — see ``_measured_fuse``.
    """
    fused_s, eager_s = group_cost(instructions, rows, n, itemsize, params)
    verdict = {"fuse": bool(fused_s <= FUSE_MARGIN * eager_s),
               "fused_us": fused_s * 1e6,
               "eager_us": eager_s * 1e6,
               "params": params.source}
    ratio = fused_s / eager_s if eager_s > 0 else float("inf")
    if (params.source == "calibrated" and dtype is not None
            and interpret is not None
            and MEASURE_BAND[0] <= ratio <= MEASURE_BAND[1]):
        measured = _measured_fuse(instructions, lead, n, dtype, interpret)
        if measured is not None:
            verdict = measured
    return verdict
