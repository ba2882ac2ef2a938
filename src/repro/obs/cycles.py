"""Cycle accounting: the paper's predicted budgets vs the measured lowering.

The repo's cost model predicts every program's *concurrent-step* cycles
from the op table (``~1`` universal, ``~M`` local, ``~√N`` global,
``~log N`` super — §4–§8), and PR 3/4 proved the formulas equal the
jaxpr-measured scan trip counts per op and per program.  This module
makes that comparison a **live metric**: a process-global
:class:`CycleLedger` accumulates, per op *family*,

  * ``predicted``       — op-table concurrent-step cycles,
  * ``predicted_scan``  — the scan-lowered share of them (the part a
    jaxpr walk can measure as ``lax.scan`` trips),
  * ``measured_trips``  — scan trips measured from the reference lowering,
  * ``launches``        — ``pallas_call`` count of the op's lowering,

and exposes ``drift = measured_trips - predicted_scan`` per family — the
model-vs-measured drift metric.  A healthy build holds drift at 0; any
nonzero drift means an op's lowering no longer matches its registered
budget (exactly the regression the SIMDRAM-style measured-vs-modeled
evaluation methodology exists to catch).

Feeding the ledger:

  * ``CPMProgram.steps_report()`` is hooked — every report (i.e. every
    scheduled program whose cycles anyone asks about) records its
    predicted cycles here, per family, when telemetry is on;
  * :func:`audit` replays a program instruction-by-instruction on a
    concrete device, measuring each instruction's reference lowering
    (scan trips + pallas launches) via ``jax.make_jaxpr`` — host-side
    tracing, never inside an active jax trace (the PR-6 rule; audits
    refuse to run mid-trace).

All recording is host arithmetic; ``REPRO_OBS=0`` turns both feeds off.
"""

from __future__ import annotations

import dataclasses
import threading

from .metrics import counter, enabled


@dataclasses.dataclass
class FamilyCycles:
    """Accumulated cycle accounting for one op family."""
    family: str
    predicted: int = 0
    predicted_scan: int = 0
    measured_trips: int = 0
    launches: int = 0
    instructions: int = 0
    audited: int = 0               # instructions with a measured lowering

    @property
    def drift(self) -> int:
        return self.measured_trips - self.predicted_scan


class CycleLedger:
    def __init__(self):
        self._families: dict[str, FamilyCycles] = {}
        self._lock = threading.Lock()
        self._predicted = counter(
            "repro_cycles_predicted_total",
            "op-table predicted concurrent-step cycles", ("family",))
        self._measured = counter(
            "repro_cycles_measured_trips_total",
            "jaxpr-measured scan trips of audited lowerings", ("family",))
        self._launches = counter(
            "repro_cycles_pallas_launches_total",
            "pallas_call count of audited lowerings", ("family",))

    def _fam(self, family: str) -> FamilyCycles:
        f = self._families.get(family)
        if f is None:
            f = self._families[family] = FamilyCycles(family)
        return f

    # -- feeds ---------------------------------------------------------------
    def note_predicted(self, family: str, steps: int,
                       scan_steps: int = 0) -> None:
        """One instruction's predicted cycles (``steps_report`` hook)."""
        with self._lock:
            f = self._fam(family)
            f.predicted += steps
            f.predicted_scan += scan_steps
            f.instructions += 1
        self._predicted.inc(steps, family=family)

    def note_measured(self, family: str, trips: int, launches: int,
                      predicted: int = 0, scan_predicted: int = 0) -> None:
        """One audited instruction: measured lowering next to its budget."""
        with self._lock:
            f = self._fam(family)
            f.predicted += predicted
            f.predicted_scan += scan_predicted
            f.measured_trips += trips
            f.launches += launches
            f.instructions += 1
            f.audited += 1
        if predicted:
            self._predicted.inc(predicted, family=family)
        self._measured.inc(trips, family=family)
        self._launches.inc(launches, family=family)

    # -- views ---------------------------------------------------------------
    def drift_table(self) -> list[dict]:
        """Per-family rows, audited families first, worst drift on top."""
        with self._lock:
            fams = [dataclasses.asdict(f) | {"drift": f.drift}
                    for f in self._families.values()]
        return sorted(fams, key=lambda r: (-r["audited"], -abs(r["drift"]),
                                           r["family"]))

    def format_drift_table(self) -> str:
        rows = self.drift_table()
        head = (f"{'family':<10} {'instrs':>6} {'predicted':>9} "
                f"{'pred_scan':>9} {'meas_trips':>10} {'launches':>8} "
                f"{'drift':>5}")
        lines = [head, "-" * len(head)]
        for r in rows:
            lines.append(
                f"{r['family']:<10} {r['instructions']:>6} "
                f"{r['predicted']:>9} {r['predicted_scan']:>9} "
                f"{r['measured_trips']:>10} {r['launches']:>8} "
                f"{r['drift']:>5}")
        return "\n".join(lines)

    def clear(self) -> None:
        with self._lock:
            self._families.clear()


#: the process-global ledger
LEDGER = CycleLedger()


def _family_of(op: str) -> str:
    from repro.cpm.optable import OP_TABLE
    from repro.cpm.program.ir import DERIVED_METHODS
    spec = OP_TABLE.get(DERIVED_METHODS.get(op, op))
    return spec.family if spec is not None else "unknown"


def _scan_share(op: str, steps: int) -> int:
    """The scan-lowered share of one instruction's predicted cycles —
    ``scheduler.scan_structured_steps`` per instruction: scan-structured
    ops count fully, minus the Rule-6 drain step of derived methods
    (``find_all`` = ``substring_match`` + 1), which is not a scan trip."""
    from repro.cpm.program.ir import DERIVED_METHODS
    from repro.cpm.program.scheduler import _SCAN_STRUCTURED
    if op not in _SCAN_STRUCTURED:
        return 0
    return steps - (1 if op in DERIVED_METHODS else 0)


def note_report(prog, n: int, report: dict) -> None:
    """The ``CPMProgram.steps_report`` hook: fold one report's per-
    instruction predicted cycles into the ledger (telemetry on only)."""
    if not enabled():
        return
    for i, instr in enumerate(prog.instructions):
        steps = report.get(f"{i}:{instr.op}")
        if steps is None:
            continue
        LEDGER.note_predicted(_family_of(instr.op), int(steps),
                              _scan_share(instr.op, int(steps)))


def audit(prog, device, section: int | None = None,
          ledger: CycleLedger | None = None) -> list[dict]:
    """Measure a program's reference lowering instruction-by-instruction
    against its op-table budget, on a concrete ``device`` (a CPMArray).

    For each instruction: predicted cycles come from the op-table formula
    at the device's ``n``; measured scan trips and pallas-launch counts
    come from a ``jax.make_jaxpr`` walk of the instruction's *reference*
    replay against the evolving device state (pure host-side tracing).
    Results land in the ledger per family and are returned per
    instruction.  Refuses to run inside an active jax trace (timing and
    tracing there would be staged, not real — the PR-6 rule).
    """
    from repro.cpm import tuning
    from repro.cpm.program import executors, introspect
    from repro.cpm.program.scheduler import instruction_steps
    if not tuning.measurable():
        raise RuntimeError(
            "cycles.audit() inside an active jax trace would measure "
            "staged tracing, not execution; audit eagerly between "
            "compiled calls")
    led = ledger if ledger is not None else LEDGER
    n = device.n
    rows: list[dict] = []
    dev = device
    for instr in prog.instructions:
        predicted = instruction_steps(instr, n, section=section)
        scan_pred = _scan_share(instr.op, predicted)

        def lowered(d, instr=instr):
            out = executors.apply_instruction(d, instr, backend="reference")
            return out.data if hasattr(out, "data") else out

        trips = introspect.scan_trip_count(lowered, dev)
        launches = introspect.count_pallas_calls(lowered, dev)
        fam = _family_of(instr.op)
        if enabled():
            led.note_measured(fam, trips, launches, predicted=predicted,
                              scan_predicted=scan_pred)
        rows.append({"op": instr.op, "family": fam, "n": n,
                     "predicted": predicted, "predicted_scan": scan_pred,
                     "measured_trips": trips, "launches": launches,
                     "drift": trips - scan_pred})
        out = executors.apply_instruction(dev, instr, backend="reference")
        if type(out) is type(dev):
            dev = out                   # transforms advance the stream head
    return rows


def drift_table() -> list[dict]:
    return LEDGER.drift_table()
