"""Per-backend execution of CPM programs.

Three executors, one contract (bit-identical to eager dispatch):

  * ``reference`` — replays every instruction unfused through the ordinary
    ``CPMArray`` method (the oracle).  Batched devices with per-row operands
    replay under ``jax.vmap`` over rows; this is also the eager path the
    recorder uses, so recording and reference execution cannot diverge.
  * ``pallas``    — each *fused* group lowers to ONE
    ``cpm_kernels.fused_stream`` mega-kernel launch: the row block loads
    into VMEM once and every instruction in the group reads/writes it
    there; only group boundaries (reductions, sort, drains) pay another
    launch.
  * ``mesh``      — maps each group's instructions over shards through the
    mesh backend's shard_map collectives; ops outside the mesh op-table
    entry fall back to the reference lowering (the table's
    pin-compatibility contract is per-op).

Operand layout is described once (``_RANKS``): scalars are rank 0, needle/
template/values vectors rank 1.  An operand whose leading dims equal the
device batch shape is *per-row* — the vmap axis in the reference replay and
a per-row ``(R, k)`` block in the mega-kernel; anything else broadcasts.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp

from repro.kernels.cpm_kernels import FUSED_PRODUCERS

from ..optable import OP_TABLE
from . import ir
from .ir import DERIVED_METHODS as _DERIVED

#: ops that leave a value (mask / SAD / filtered flags) rather than a new
#: buffer state — each gets its own output ref in the mega-kernel.  Derived
#: from the kernel's table so the two views cannot drift (a mismatch would
#: silently drop producer outputs in the zip below).
PRODUCERS = frozenset(FUSED_PRODUCERS)

#: operand name -> rank (0 scalar, 1 vector) per recordable method; params
#: missing here (static ints, op strings, tap tuples) never map over rows
_RANKS: dict[str, dict[str, int]] = {
    "activate": {"start": 0, "end": 0, "carry": 0},
    "shift": {"start": 0, "end": 0, "fill": 0},
    "insert": {"pos": 0, "values": 1},
    "delete": {"pos": 0, "fill": 0},
    "truncate": {"new_len": 0},
    "compare": {"datum": 0, "mask": 0},
    "count": {"datum": 0, "mask": 0},
    "substring_match": {"needle": 1},
    "find_all": {"needle": 1},
    "template_match": {"template": 1},
    "stencil": {},
}

#: move ops read ``used_len`` inside roll/select masks — their unbatched
#: lowerings are only row-correct, so batched devices always vmap
_VMAP_ALWAYS = frozenset({"shift", "insert", "delete"})


def _is_per_row(v, rank: int, lead: tuple[int, ...]) -> bool:
    """Per-row iff the operand carries the device's batch dims verbatim —
    an extra leading dim that is not the batch shape (e.g. ``(1, k)`` on a
    ``(2, n)`` device) must NOT be silently split across rows."""
    if v is None or not lead:
        return False
    shape = jnp.shape(v)
    return (len(shape) == len(lead) + rank
            and tuple(shape[:len(lead)]) == tuple(lead))


def _per_row_operands(instr: ir.Instruction, lead) -> bool:
    ranks = _RANKS.get(instr.op, {})
    return any(_is_per_row(instr.operands.get(k), r, lead)
               for k, r in ranks.items())


# ---------------------------------------------------------------------------
# single-instruction replay (reference / any eager backend)
# ---------------------------------------------------------------------------

def apply_instruction(arr, instr: ir.Instruction, backend: str | None = None,
                      interpret: bool | None = None):
    """Execute one instruction eagerly on ``backend`` (default: the
    array's).  Falls back to reference when the forced backend has no table
    entry for the op — per-op pin compatibility, never an error mid-stream."""
    bk = backend or arr.backend
    spec = OP_TABLE.get(_DERIVED.get(instr.op, instr.op))
    if bk not in ("reference", "auto") and spec is not None \
            and bk not in spec.backends:
        bk = "reference"
    kw = {"backend": bk}
    if interpret is not None:
        kw["interpret"] = interpret
    a = dataclasses.replace(arr, **kw)
    lead = arr.batch_shape
    if lead and (instr.op in _VMAP_ALWAYS or _per_row_operands(instr, lead)):
        return _apply_rows(a, instr)
    with ir.suspended():
        return getattr(a, instr.op)(**instr.operands)


def _apply_rows(a, instr: ir.Instruction):
    """Row-wise vmap replay of one instruction on a batched device."""
    from ..array import CPMArray

    lead, n = a.batch_shape, a.n
    r = math.prod(lead)
    data = a.data.reshape(r, n)
    ul = jnp.broadcast_to(jnp.asarray(a.used_len, jnp.int32), lead).reshape(r)
    ranks = _RANKS.get(instr.op, {})
    mapped: dict[str, jax.Array] = {}
    shared = dict(instr.operands)
    for name, rank in ranks.items():
        v = instr.operands.get(name)
        if _is_per_row(v, rank, lead):
            va = jnp.asarray(v)
            mapped[name] = va.reshape(r, *va.shape[len(lead):])
            del shared[name]
    names = tuple(mapped)

    def one(d, u, *mv):
        row = CPMArray(d, u, a.backend, a.interpret)
        with ir.suspended():
            return getattr(row, instr.op)(**dict(shared, **dict(zip(names, mv))))

    out = jax.vmap(one)(data, ul, *[mapped[k] for k in names])
    if isinstance(out, CPMArray):
        return dataclasses.replace(out, data=out.data.reshape(*lead, n),
                                   used_len=out.used_len.reshape(lead))
    return jax.tree_util.tree_map(
        lambda x: x.reshape(*lead, *x.shape[1:]), out)


# ---------------------------------------------------------------------------
# plan execution
# ---------------------------------------------------------------------------

def run_plan(plan, arr, backend: str | None = None,
             interpret: bool | None = None):
    """Execute a scheduled plan; returns ``(final_array, outputs)``.

    Only ``fused`` groups on the pallas backend take the mega-kernel
    path; ``eager`` groups (fusable runs the cost model rejected) and
    ``boundary`` groups replay per-op — same instructions, bit-identical
    results, just a different launch structure."""
    from .. import backends as B

    bk = backend or arr.backend
    if bk == "auto":
        bk = B.auto_backend_name(arr.data)
    outputs: list = [None] * len(plan.program)
    cur = arr
    for group in plan.groups:
        if group.kind == "fused" and bk == "pallas":
            cur, produced = _run_fused_pallas(cur, group, interpret)
            for idx, val in produced:
                outputs[idx] = val
            continue
        for idx, instr in zip(group.indices, group.instructions):
            res = apply_instruction(cur, instr, backend=bk,
                                    interpret=interpret)
            if type(res) is type(cur):
                cur = res
            else:
                outputs[idx] = res
    return cur, outputs


# ---------------------------------------------------------------------------
# the pallas fused-group lowering
# ---------------------------------------------------------------------------

#: don't bother timing row blockings below this problem size — the launch
#: count is tiny and tuning would cost more than it can ever return
_TUNE_MIN_ROWS = 4
_TUNE_MIN_ELEMS = 1 << 15


def _blockr_candidates(r: int) -> list[int]:
    """Row blockings that compile for the chip: one row (the kernel's
    (R, 1, N) layout), multiples of 8 rows, or all rows
    (``tests/test_tpu_compile.py`` compiles each for a v5e)."""
    return sorted({br for br in (1, 8, 32, r) if 1 <= br <= r})


def _fused_block_r(descs, operands, data, ul, r, n, backend) -> int:
    """Autotuned rows-per-grid-step for one fused stream, cached per
    (op-stream-signature, shape, dtype, backend) with a JSON spill.

    The key depends only on static shape/dtype facts, so a traced caller
    still *reads* decisions made earlier — but candidates are only ever
    timed outside a trace (``tuning.measurable``), on concrete zeros of
    the recorded shapes; the winner is a static Python int baked into
    the pallas grid.
    """
    from .. import tuning

    if r < _TUNE_MIN_ROWS or r * n < _TUNE_MIN_ELEMS:
        return 1
    cands = _blockr_candidates(r)
    if len(cands) < 2:
        return 1
    sig = hashlib.md5(repr(descs).encode()).hexdigest()[:12]
    key = (f"blockr:{'+'.join(op for op, _, _ in descs)}:{sig}"
           f"|{r}x{n}|{jnp.dtype(data.dtype).name}"
           f"|{tuning.backend_key(backend.interpret)}")
    cached = tuning.lookup(key)
    if cached is not None:
        return int(cached)
    if not tuning.tuning_enabled() or not tuning.measurable():
        return 1
    datz = tuning.synth((r, n), data.dtype)
    ulz = tuning.synth((r,), jnp.int32)
    opz = tuple(tuning.synth(a.shape, a.dtype) for a in operands)

    def run(br):
        return backend.fused_stream(datz, ulz, descs, opz, block_r=br)

    return int(tuning.pick(key, cands, run, default=1))

def _norm_operand(v, rank: int, lead, r: int, dtype=None):
    """Normalize one dynamic operand to a ``(rows, k)`` kernel input
    (``rows`` is ``r`` per-row or 1 broadcast).  Returns (array, shared)."""
    a = jnp.asarray(v) if dtype is None else jnp.asarray(v, dtype)
    if _is_per_row(a, rank, lead):
        return (a.reshape(r, -1) if rank else a.reshape(r, 1)), False
    if a.ndim != rank:
        raise ValueError(
            f"operand of shape {a.shape} matches neither the shared rank-"
            f"{rank} layout nor the per-row layout {tuple(lead)} + rank-"
            f"{rank} for batch {tuple(lead)}")
    return a.reshape(1, -1), True


def _pack_scalars(values, lead, r, dtype):
    """Scalars that share one kernel ref (start/end/carry): broadcast to a
    common row count and concatenate along the operand axis."""
    parts = [_norm_operand(v, 0, lead, r, dtype) for v in values]
    shared = all(s for _, s in parts)
    rows = 1 if shared else r
    packed = jnp.concatenate(
        [jnp.broadcast_to(a, (rows, 1)) for a, _ in parts], axis=1)
    return packed, shared


def _lower(instr: ir.Instruction, dtype, n: int, lead, r: int):
    """Instruction -> (static descriptor, operand arrays, all_shared)."""
    op, ops = instr.op, instr.operands
    if op == "activate":
        packed, shared = _pack_scalars(
            [ops["start"], ops["end"], ops["carry"]], lead, r, jnp.int32)
        return (op, ()), [packed], shared
    if op == "shift":
        se, shared = _pack_scalars([ops["start"], ops["end"]], lead, r,
                                   jnp.int32)
        statics = (("shift", int(ops["shift"])),
                   ("has_fill", ops["fill"] is not None))
        opnds = [se]
        if ops["fill"] is not None:
            f, fs = _norm_operand(ops["fill"], 0, lead, r, dtype)
            opnds.append(f)
            shared = shared and fs
        return (op, statics), opnds, shared
    if op == "insert":
        values = jnp.asarray(ops["values"], dtype)
        k = values.shape[-1]
        pos, ps = _norm_operand(ops["pos"], 0, lead, r, jnp.int32)
        vals, vs = _norm_operand(values, 1, lead, r, dtype)
        return (op, (("k", int(k)),)), [pos, vals], ps and vs
    if op == "delete":
        pos, ps = _norm_operand(ops["pos"], 0, lead, r, jnp.int32)
        fill, fs = _norm_operand(ops["fill"], 0, lead, r, dtype)
        return (op, (("k", int(ops["k"])),)), [pos, fill], ps and fs
    if op == "truncate":
        nl, s = _norm_operand(ops["new_len"], 0, lead, r, jnp.int32)
        return (op, ()), [nl], s
    if op == "compare":
        has_mask = ops.get("mask") is not None
        if has_mask:
            # eager: x = data & mask (promoting), d = asarray(datum,
            # self.dtype) & mask — keep the mask in the promoted dtype so
            # the in-kernel `x & m` / `d & m` promote identically
            d, ds = _norm_operand(jnp.asarray(ops["datum"], dtype), 0,
                                  lead, r)
            # result_type honors weak python scalars exactly like `& mask`
            mct = jnp.result_type(dtype, ops["mask"])
            m, ms = _norm_operand(ops["mask"], 0, lead, r, mct)
            statics = (("op", ops["op"]), ("has_mask", True),
                       ("ct", jnp.dtype(mct).name))
            return (op, statics), [d, m], ds and ms
        ct = jnp.promote_types(dtype, jnp.asarray(ops["datum"]).dtype)
        d, ds = _norm_operand(ops["datum"], 0, lead, r, ct)
        statics = (("op", ops["op"]), ("has_mask", False),
                   ("ct", jnp.dtype(ct).name))
        return (op, statics), [d], ds
    if op == "substring_match":
        needle = jnp.asarray(ops["needle"], dtype)
        nee, s = _norm_operand(needle, 1, lead, r, dtype)
        statics = (("m", int(needle.shape[-1])), ("where", ops["where"]))
        return (op, statics), [nee], s
    if op == "template_match":
        template = jnp.asarray(ops["template"])
        t, s = _norm_operand(template, 1, lead, r)
        statics = (("m", int(template.shape[-1])),
                   ("mask_tail", bool(ops["mask_tail"])))
        return (op, statics), [t], s
    if op == "stencil":
        statics = (("taps", tuple(float(t) for t in ops["taps"])),
                   ("wrap", bool(ops["wrap"])))
        return (op, statics), [], True
    raise NotImplementedError(f"no mega-kernel lowering for op {op!r}")


def _run_fused_pallas(arr, group, interpret):
    """One fused group -> one ``fused_stream`` pallas_call."""
    from .. import backends as B

    lead, n = arr.batch_shape, arr.n
    r = math.prod(lead) if lead else 1
    data = arr.data.reshape(r, n)
    ul = jnp.broadcast_to(jnp.asarray(arr.used_len, jnp.int32),
                          lead or ()).reshape(r)
    itp = interpret if interpret is not None else arr.interpret
    backend = B.get_backend("pallas", interpret=itp)

    descs, operands, meta = [], [], []
    for idx, instr in zip(group.indices, group.instructions):
        (op, statics), opnds, all_shared = _lower(instr, arr.data.dtype, n,
                                                  lead, r)
        # the operand count rides in the static descriptor so the kernel's
        # ref routing has exactly one source of truth (this lowering)
        descs.append((op, statics, len(opnds)))
        operands.extend(opnds)
        if instr.op in PRODUCERS:
            meta.append((idx, instr.op, all_shared))
    descs, operands = tuple(descs), tuple(operands)
    block_r = _fused_block_r(descs, operands, data, ul, r, n, backend)
    out_x, out_ul, prods = backend.fused_stream(
        data, ul, descs, operands, block_r=block_r)

    mutates = any(i.op in ("shift", "insert", "delete", "truncate")
                  for i in group.instructions)
    if mutates:
        new = dataclasses.replace(
            arr, data=out_x.reshape(*lead, n) if lead else out_x.reshape(n),
            used_len=out_ul.reshape(lead) if lead else out_ul.reshape(()))
    else:                       # producers only: device state untouched —
        new = arr               # keep the caller's used_len layout

    produced = []
    for (idx, op, all_shared), raw in zip(meta, prods):
        if op in ("activate", "compare", "substring_match"):
            raw = raw.astype(bool)
        if op == "activate" and all_shared:
            out = raw[0]        # eager activate is batch-free: one (n,) mask
        elif lead:
            out = raw.reshape(*lead, n)
        else:
            out = raw.reshape(n)
        produced.append((idx, out))
    return new, produced
