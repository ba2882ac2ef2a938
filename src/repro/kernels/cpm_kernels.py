"""Pallas TPU kernels for the paper's in-memory algorithms.

Chip-scale CPM: the VMEM block is the PE array (VREG lanes = PEs), the
kernel body is the broadcast instruction stream (Rule 5), intra-block shifts
are neighbor reads (Rule 7).

Kernels:
  * ``activate``        — §3.3 Rule-4 general decoder (range + carry mask).
  * ``shift_range``     — §4.1 concurrent range move (roll + select in VMEM).
  * ``oddeven_sort``    — §7.7 local-exchange sort, N compare-exchange cycles
                          entirely in VMEM (used by MoE routing).
  * ``compare``         — §6.1 broadcast-datum compare, one VPU cycle.
  * ``histogram``       — §6.3 M-bin histogram, one compare+count per edge;
                          row-batched and HBM-tiled (rows x sections grid).
  * ``section_sum``     — §7.4 two-phase reduction: concurrent per-section
                          sums (phase 1, one grid step per section block)
                          accumulated across the grid (phase 2).  Batched:
                          ``(R, N)`` rows reduce in ONE launch over a
                          (rows, sections) grid with a per-row accumulator,
                          and N may exceed a single VMEM block (sections
                          stream from HBM).
  * ``section_limit``   — §7.5 global max/min with the same structure.
  * ``super_sum``       — §8 super-connected sum: per-section partials kept
                          in a VMEM scratch line, combined by a log-depth
                          pairwise tree (Fig. 16 skip links) instead of the
                          serial phase-2 march.
  * ``super_limit``     — §8 log-depth global max/min.
  * ``template_match``  — §7.6 sliding SAD, ~M shift-accumulate cycles.
  * ``substring_match`` — §5 streaming needle match with neighbor carry.
  * ``stencil``         — §7.3 tap algebra, ~M shift-multiply-accumulate
                          (``wrap=False`` zero-pads the row ends instead of
                          wrapping, matching the canonical `repro.cpm`
                          semantics).
  * ``compact``         — §4.2 stable pack of flagged items: a log-depth
                          Hillis-Steele cumsum over the keep flags, then
                          each kept item shifts left by its count of
                          dropped predecessors, one roll per bit —
                          ~2·log2(N) concurrent steps, bit-identical to the
                          reference argsort pack.
  * ``gather_rows`` / ``scatter_rows`` — paged-row movement for the bank
                          pool (`repro.cpm.pool`): dynamic row indices ride
                          in scalar-prefetch so each grid step DMAs exactly
                          one (1, N) page between HBM rows and VMEM.

All take ``interpret=`` with a ``None`` = auto default — compiled on TPU,
Pallas interpreter elsewhere — the same rule ``CPMArray`` applies, so a
kernel called directly on a real TPU never silently runs interpreted.
These kernels are the ``pallas`` backend of ``repro.cpm`` — prefer driving
them through ``CPMArray``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pallas_native() -> bool:
    """The one platform rule behind every kernel default: Pallas kernels
    run compiled on a TPU; anywhere else the defaults pick the jnp
    reference, and a Pallas kernel asked for explicitly runs under the
    interpreter."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret flag of a Pallas call (shared with ``CPMArray``,
    ``PallasBackend`` and ``flash_attention``).  ``None`` means auto."""
    if interpret is None:
        return not pallas_native()
    return bool(interpret)


def resolve_backend(backend: str | None) -> str:
    """CPM backend of a serving-path component (``Engine`` commit,
    ``SessionPool``/``Gateway`` token banks).  ``None`` means auto:
    ``"pallas"`` on a TPU, ``"reference"`` elsewhere."""
    if backend is None:
        return "pallas" if pallas_native() else "reference"
    return backend


# ---------------------------------------------------------------------------
# TPU block layout
# ---------------------------------------------------------------------------
#
# Mosaic takes a block only when its last two dims are multiples of
# (8, 128) or equal to the array's.  Row-batched kernels therefore see
# their (R, N) rows as an (R, 1, N) array: one (1, N) row per grid step —
# last two dims equal to the array's — with the row axis squeezed out of
# the kernel's view, so bodies still work on (1, N) rows.

LANES = 128


def _as_rows3(x: jax.Array) -> jax.Array:
    """(R, N) -> (R, 1, N), the layout of every row-batched kernel."""
    return x.reshape(x.shape[0], 1, x.shape[-1])


def _row_spec(width: int, index_map) -> pl.BlockSpec:
    """One (1, ``width``) row of an (R, 1, N) array per grid step."""
    return pl.BlockSpec((pl.squeezed, 1, width), index_map)


def _each_row(width: int) -> pl.BlockSpec:
    """Grid step ``i`` takes row ``i`` whole."""
    return _row_spec(width, lambda i: (i, 0, 0))


def _roll(x, shift: int):
    """``jnp.roll`` along lanes by a static shift.  Mosaic refuses the
    empty slice of a zero roll and rolls of booleans, so a zero shift is
    the identity here and callers roll int32 masks."""
    shift %= x.shape[-1]
    return jnp.roll(x, shift, axis=-1) if shift else x


def _lane_pick(v, j):
    """Lane ``j`` (traced) of every row of a small (rows, M) block, as a
    (rows, 1) column — a masked sum, since Mosaic has no dynamic lane
    slice.  Exact: every other term is zero."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.sum(jnp.where(lane == j, v, jnp.zeros((), v.dtype)), axis=1,
                   keepdims=True)


def lane_section(section: int, n: int) -> int:
    """The section width a sectioned kernel really uses: ``section`` rounded
    up to whole 128-lane tiles (Mosaic's block rule), or the whole row when
    that is no shorter.  Shared with the autotuner's candidate filter."""
    if section >= n:
        return n
    return min(n, -(-section // LANES) * LANES)


# ---------------------------------------------------------------------------
# §3.3 Rule 4 — the general decoder
# ---------------------------------------------------------------------------

def _activate_vals(idx, start, end, carry):
    """Rule-4 general-decoder predicate — the one value-level body shared
    by the standalone kernel and the fused instruction stream."""
    carry = jnp.maximum(carry, 1)
    return (idx >= start) & (idx <= end) & ((idx - start) % carry == 0)


def _activate_kernel(p_ref, o_ref, *, n: int):
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    mask = _activate_vals(idx, p_ref[0, 0], p_ref[0, 1], p_ref[0, 2])
    o_ref[...] = mask.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def activate(n: int, start, end, carry=1, *, interpret: bool | None = None) -> jax.Array:
    """Rule-4 activation mask of length ``n`` as one VPU predicate cycle."""
    params = jnp.stack([jnp.asarray(start, jnp.int32),
                        jnp.asarray(end, jnp.int32),
                        jnp.asarray(carry, jnp.int32)]).reshape(1, 3)
    out = pl.pallas_call(
        functools.partial(_activate_kernel, n=n),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int8),
        interpret=resolve_interpret(interpret),
    )(params)
    return out[0].astype(bool)


# ---------------------------------------------------------------------------
# §4.1 — concurrent range move
# ---------------------------------------------------------------------------

def _shift_vals(x, idx, start, end, shift: int, n: int, fill=None):
    """§4.1 range move of a resident block — the one value-level body shared
    by the standalone kernel and the fused instruction stream."""
    src_mask = (idx >= start) & (idx <= end)
    moved = _roll(x, shift)
    j = idx - shift                     # the lane each lane's content left
    dst_mask = (j >= start) & (j <= end) & (j >= 0) & (j < n)
    out = jnp.where(dst_mask, moved, x)
    if fill is not None:
        out = jnp.where(src_mask & ~dst_mask, fill, out)
    return out


def _shift_range_kernel(x_ref, p_ref, f_ref, o_ref, *, n: int, shift: int,
                        has_fill: bool):
    x = x_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    o_ref[...] = _shift_vals(x, idx, p_ref[0, 0], p_ref[0, 1], shift, n,
                             f_ref[0, 0] if has_fill else None)


@functools.partial(jax.jit, static_argnames=("shift", "interpret"))
def shift_range(x: jax.Array, start, end, shift: int = 1, fill=None, *,
                interpret: bool | None = None) -> jax.Array:
    """Move the [start, end] range of every (R, N) row by ``shift`` places.

    Same semantics as ``repro.cpm.reference.movable.shift_range`` — vacated
    slots keep old content unless ``fill`` is given; content crossing the
    physical ends is dropped.  One concurrent roll+select cycle in VMEM.
    """
    r, n = x.shape
    params = jnp.stack([jnp.asarray(start, jnp.int32),
                        jnp.asarray(end, jnp.int32)]).reshape(1, 2)
    fill_arr = jnp.asarray(0 if fill is None else fill, x.dtype).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_shift_range_kernel, n=n, shift=shift,
                          has_fill=fill is not None),
        grid=(r,),
        in_specs=[_each_row(n),
                  pl.BlockSpec((1, 2), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=_each_row(n),
        out_shape=jax.ShapeDtypeStruct((r, 1, n), x.dtype),
        interpret=resolve_interpret(interpret),
    )(_as_rows3(x), params, fill_arr).reshape(r, n)


# ---------------------------------------------------------------------------
# §7.7 odd-even transposition sort (row-wise)
# ---------------------------------------------------------------------------

def _oddeven_kernel(x_ref, o_ref, *, n: int, steps: int):
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def body(i, x):
        is_left = (idx % 2) == (i % 2)
        partner = jnp.clip(jnp.where(is_left, idx + 1, idx - 1), 0, n - 1)
        px = jnp.where(is_left, _roll(x, -1), _roll(x, 1))   # neighbor read
        out = jnp.where(is_left, jnp.minimum(x, px), jnp.maximum(x, px))
        solo = (partner == idx) | (is_left & (idx == n - 1))
        return jnp.where(solo, x, out)

    o_ref[...] = jax.lax.fori_loop(0, steps, body, x_ref[...])


@functools.partial(jax.jit, static_argnames=("steps", "interpret"))
def oddeven_sort(x: jax.Array, steps: int | None = None, *,
                 interpret: bool | None = None) -> jax.Array:
    """Row-wise ascending sort of (R, N): N odd-even cycles in VMEM."""
    r, n = x.shape
    steps = n if steps is None else steps
    return pl.pallas_call(
        functools.partial(_oddeven_kernel, n=n, steps=steps),
        grid=(r,),
        in_specs=[_each_row(n)],
        out_specs=_each_row(n),
        out_shape=jax.ShapeDtypeStruct((r, 1, n), x.dtype),
        interpret=resolve_interpret(interpret),
    )(_as_rows3(x)).reshape(r, n)


# ---------------------------------------------------------------------------
# §7.4 two-phase sectioned sum (row-batched, HBM-tiled)
# ---------------------------------------------------------------------------

def _pad_rows(x: jax.Array, section: int, fill=0):
    """(..., N) -> ((R, N_padded), nsec, unflatten-to-leading-dims)."""
    lead = x.shape[:-1]
    n = x.shape[-1]
    pad = (-n) % section
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                    constant_values=fill)
    x2 = x.reshape(-1, x.shape[-1])
    return x2, x2.shape[-1] // section, (lambda out: out.reshape(lead))


def _acc_dtype(dtype):
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def _section_sum_kernel(x_ref, o_ref, acc_ref):
    j = pl.program_id(1)                    # section index (innermost)

    @pl.when(j == 0)
    def _():                                # fresh accumulator per row
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # phase 1: concurrent in-section reduction of this VMEM block
    acc_ref[...] += jnp.sum(x_ref[...].astype(acc_ref.dtype), axis=-1,
                            keepdims=True)

    # phase 2: the running accumulator marches across sections (grid order)
    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("section", "interpret"))
def section_sum(x: jax.Array, section: int = 1024, *,
                interpret: bool | None = None) -> jax.Array:
    """Two-phase sum of every ``(..., N)`` row; section = VMEM block size.

    ONE kernel launch for any batch shape: the grid is (rows, sections)
    with a per-row VMEM accumulator, and sections stream from HBM so N may
    exceed a single VMEM block.  Integer inputs accumulate in int32 (exact,
    matching ``jnp.sum`` semantics); floats accumulate in float32.
    """
    acc_dtype = _acc_dtype(x.dtype)
    section = lane_section(section, x.shape[-1])
    xs, nsec, unflatten = _pad_rows(x, section)
    r = xs.shape[0]
    out = pl.pallas_call(
        _section_sum_kernel,
        grid=(r, nsec),
        in_specs=[_row_spec(section, lambda i, j: (i, 0, j))],
        out_specs=_row_spec(1, lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1, 1), acc_dtype),
        scratch_shapes=[pltpu.VMEM((1, 1), acc_dtype)],
        interpret=resolve_interpret(interpret),
    )(_as_rows3(xs))
    return unflatten(out).astype(jnp.promote_types(x.dtype, acc_dtype))


# ---------------------------------------------------------------------------
# §6.1 broadcast compare + §6.3 histogram
# ---------------------------------------------------------------------------

_CMP = {
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
    "lt": lambda a, b: a < b,
    "gt": lambda a, b: a > b,
    "le": lambda a, b: a <= b,
    "ge": lambda a, b: a >= b,
}


def _compare_kernel(x_ref, d_ref, o_ref, *, op: str):
    o_ref[...] = _CMP[op](x_ref[...], d_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def compare(x: jax.Array, datum, op: str = "eq", *,
            interpret: bool | None = None) -> jax.Array:
    """(R, N) rows vs a broadcast datum: one concurrent VPU compare.

    Mixed dtypes promote (never truncate toward ``x.dtype``): comparing int
    rows against 2.5 compares against 2.5, matching the reference oracle.
    """
    ct = jnp.promote_types(x.dtype, jnp.asarray(datum).dtype)
    x = x.astype(ct)
    r, n = x.shape
    d = jnp.asarray(datum, ct).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_compare_kernel, op=op),
        grid=(r,),
        in_specs=[_each_row(n),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=_each_row(n),
        out_shape=jax.ShapeDtypeStruct((r, 1, n), jnp.int8),
        interpret=resolve_interpret(interpret),
    )(_as_rows3(x), d)
    return out.reshape(r, n).astype(bool)


def _histogram_kernel(x_ref, e_ref, o_ref, acc_ref, *, m: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # (1, section)
    # one broadcast compare + Rule-6 parallel count per section edge
    below = (x < e_ref[...].reshape(m + 1, 1)).astype(jnp.int32)
    cum = jnp.sum(below, axis=-1)                    # (M+1,)
    acc_ref[...] += (cum[1:] - cum[:-1]).reshape(1, m)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("section", "interpret"))
def histogram(x: jax.Array, edges: jax.Array, section: int = 1024, *,
              interpret: bool | None = None) -> jax.Array:
    """(..., N) values x (M+1,) ascending edges -> (..., M) per-row counts
    (§6.3, ~M compare+count cycles).

    Same (rows, sections) grid as the §7.4 reductions: one launch for any
    batch shape, N streamed section-by-section from HBM into VMEM with a
    per-row (1, M) bin accumulator.  Row padding takes the top edge, which
    lands in no ``[e_i, e_{i+1})`` bin.  Mixed dtypes promote (fractional
    edges stay fractional on int data).
    """
    ct = jnp.promote_types(x.dtype, edges.dtype)
    x, edges = x.astype(ct), edges.astype(ct)
    m = edges.shape[-1] - 1
    section = lane_section(section, x.shape[-1])
    xs, nsec, _ = _pad_rows(x, section, fill=edges[-1])
    r = xs.shape[0]
    out = pl.pallas_call(
        functools.partial(_histogram_kernel, m=m),
        grid=(r, nsec),
        in_specs=[_row_spec(section, lambda i, j: (i, 0, j)),
                  pl.BlockSpec((1, m + 1), lambda i, j: (0, 0))],
        out_specs=_row_spec(m, lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1, m), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, m), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(_as_rows3(xs), edges.reshape(1, m + 1))
    return out.reshape(*x.shape[:-1], m)


# ---------------------------------------------------------------------------
# §7.5 two-phase sectioned limit (global max/min)
# ---------------------------------------------------------------------------

def _section_limit_kernel(x_ref, o_ref, acc_ref, *, mode: str, init):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.full_like(acc_ref, init)

    red = jnp.max if mode == "max" else jnp.min
    cmb = jnp.maximum if mode == "max" else jnp.minimum
    acc_ref[...] = cmb(acc_ref[...],
                       red(x_ref[...].astype(acc_ref.dtype), axis=-1,
                           keepdims=True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("section", "mode", "interpret"))
def section_limit(x: jax.Array, section: int = 1024, mode: str = "max", *,
                  interpret: bool | None = None) -> jax.Array:
    """Two-phase max/min of every ``(..., N)`` row (§7.5).

    Same batched (rows, sections) grid as :func:`section_sum`: one launch,
    per-row accumulator, sections streamed from HBM.
    """
    # function-level import: keeps the kernels module import-free of the
    # cpm package at module scope (backends.pallas imports this module)
    from repro.cpm.semantics import limit_identity

    acc_dtype = _acc_dtype(x.dtype)
    fill = limit_identity(acc_dtype, mode)
    section = lane_section(section, x.shape[-1])
    xs, nsec, unflatten = _pad_rows(x, section,
                                    fill=limit_identity(x.dtype, mode))
    r = xs.shape[0]
    out = pl.pallas_call(
        functools.partial(_section_limit_kernel, mode=mode, init=fill),
        grid=(r, nsec),
        in_specs=[_row_spec(section, lambda i, j: (i, 0, j))],
        out_specs=_row_spec(1, lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1, 1), acc_dtype),
        scratch_shapes=[pltpu.VMEM((1, 1), acc_dtype)],
        interpret=resolve_interpret(interpret),
    )(_as_rows3(xs))
    return unflatten(out).astype(x.dtype)


# ---------------------------------------------------------------------------
# §8 super-connectivity: log-depth combine of the section partials
# ---------------------------------------------------------------------------

def _tree_combine_block(x, k: int, combine, identity):
    """Log-depth pairwise combine of the first ``k`` lanes of a (1, K) block.

    Level ``j`` reads the partner 2**j lanes away — exactly Fig. 16's skip
    links; ceil(log2(k)) unrolled levels leave the full combine in lane 0.
    """
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    levels = max(1, (k - 1).bit_length()) if k > 1 else 0
    for j in range(levels):
        stride = 1 << j
        partner = _roll(x, -stride)
        partner = jnp.where(idx + stride < k, partner, identity)
        x = combine(x, partner)
    return x


def _super_kernel(x_ref, o_ref, acc_ref, *, mode: str, nsec: int, identity):
    j = pl.program_id(1)
    red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}[mode]
    cmb = {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}[mode]

    # phase 1: this section's concurrent partial, parked in its scratch lane
    part = red(x_ref[...].astype(acc_ref.dtype), axis=-1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)
    acc_ref[...] = jnp.where(lane == j, part, acc_ref[...])

    # phase 2: §8 log-depth tree over the section partials (not a march)
    @pl.when(j == nsec - 1)
    def _():
        o_ref[...] = _tree_combine_block(acc_ref[...], nsec, cmb,
                                         identity)[:, :1]


def _super_reduce(x: jax.Array, section: int, mode: str, *, interpret: bool):
    from repro.cpm.semantics import limit_identity

    acc_dtype = _acc_dtype(x.dtype)
    if mode == "sum":
        pad_fill, identity = 0, 0            # python scalars: the kernel body
    else:                                    # must not close over tracers
        pad_fill = limit_identity(x.dtype, mode)
        identity = limit_identity(acc_dtype, mode)
    section = lane_section(section, x.shape[-1])
    xs, nsec, unflatten = _pad_rows(x, section, fill=pad_fill)
    r = xs.shape[0]
    out = pl.pallas_call(
        functools.partial(_super_kernel, mode=mode, nsec=nsec,
                          identity=identity),
        grid=(r, nsec),
        in_specs=[_row_spec(section, lambda i, j: (i, 0, j))],
        out_specs=_row_spec(1, lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1, 1), acc_dtype),
        scratch_shapes=[pltpu.VMEM((1, nsec), acc_dtype)],
        interpret=resolve_interpret(interpret),
    )(_as_rows3(xs))
    return unflatten(out)


@functools.partial(jax.jit, static_argnames=("section", "interpret"))
def super_sum(x: jax.Array, section: int = 1024, *,
              interpret: bool | None = None) -> jax.Array:
    """§8 super-connected sum of every ``(..., N)`` row: sectioned phase 1,
    log-depth tree phase 2 (~2·log2(N) concurrent steps instead of ~2·√N).
    Same result as :func:`section_sum` (bit-identical for ints)."""
    out = _super_reduce(x, section, "sum", interpret=interpret)
    return out.astype(jnp.promote_types(x.dtype, out.dtype))


@functools.partial(jax.jit, static_argnames=("section", "mode", "interpret"))
def super_limit(x: jax.Array, section: int = 1024, mode: str = "max", *,
                interpret: bool | None = None) -> jax.Array:
    """§8 super-connected max/min of every ``(..., N)`` row (log-depth
    phase 2).  Same result as :func:`section_limit`."""
    return _super_reduce(x, section, mode, interpret=interpret).astype(x.dtype)


# ---------------------------------------------------------------------------
# §7.6 template match (row-wise sliding SAD)
# ---------------------------------------------------------------------------

def _sad_vals(x_f32, t_row, m: int):
    """§7.6 sliding-SAD accumulation on a resident float32 block (shared by
    the standalone kernel and the fused instruction stream); ``t_row`` is a
    (1, M) broadcast or (BR, M) per-row template ref/array."""
    t = t_row[...]

    def body(j, carry):                 # shifted = x rolled left by j
        acc, shifted = carry
        tap = _lane_pick(t, j)                               # (rows, 1)
        return (acc + jnp.abs(shifted - tap.astype(jnp.float32)),
                _roll(shifted, -1))

    return jax.lax.fori_loop(0, m, body, (jnp.zeros_like(x_f32), x_f32))[0]


def _template_kernel(x_ref, t_ref, o_ref, *, m: int):
    o_ref[...] = _sad_vals(x_ref[...].astype(jnp.float32), t_ref, m)


@functools.partial(jax.jit, static_argnames=("interpret",))
def template_match(data: jax.Array, template: jax.Array, *,
                   interpret: bool | None = None) -> jax.Array:
    """(R, N) x (M,) -> (R, N) SAD at every start position (wrapping tail)."""
    r, n = data.shape
    m = template.shape[-1]
    return pl.pallas_call(
        functools.partial(_template_kernel, m=m),
        grid=(r,),
        in_specs=[_each_row(n),
                  pl.BlockSpec((1, m), lambda i: (0, 0))],
        out_specs=_each_row(n),
        out_shape=jax.ShapeDtypeStruct((r, 1, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(_as_rows3(data), template.reshape(1, -1)).reshape(r, n)


# ---------------------------------------------------------------------------
# §5 substring match (row-wise, match-end semantics)
# ---------------------------------------------------------------------------

def _substring_ends_vals(x, nee_row, m: int, idx):
    """§5 match-END carry chain on a resident block (shared by the
    standalone kernel and the fused instruction stream); ``nee_row`` is a
    (1, M) broadcast or (BR, M) per-row needle ref/array.  Returns int32
    0/1 flags."""
    first = idx == 0
    nee = nee_row[...]

    def body(i, state):
        sym = _lane_pick(nee, i)                               # (rows, 1)
        hit = (x == sym).astype(jnp.int32)
        shifted = jnp.where(first, 0, _roll(state, 1))
        return jnp.where(i == 0, hit, hit * shifted)

    return jax.lax.fori_loop(0, m, body, jnp.zeros(x.shape, jnp.int32))


def _substring_kernel(x_ref, nee_ref, o_ref, *, m: int, n: int):
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    o_ref[...] = _substring_ends_vals(x_ref[...], nee_ref, m,
                                      idx).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def substring_match(hay: jax.Array, needle: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    """(R, N) int rows x (M,) needle -> (R, N) int8 match-end flags."""
    r, n = hay.shape
    m = needle.shape[-1]
    return pl.pallas_call(
        functools.partial(_substring_kernel, m=m, n=n),
        grid=(r,),
        in_specs=[_each_row(n),
                  pl.BlockSpec((1, m), lambda i: (0, 0))],
        out_specs=_each_row(n),
        out_shape=jax.ShapeDtypeStruct((r, 1, n), jnp.int8),
        interpret=resolve_interpret(interpret),
    )(_as_rows3(hay), needle.reshape(1, -1)).reshape(r, n)


# ---------------------------------------------------------------------------
# §7.3 stencil (row-wise tap accumulation)
# ---------------------------------------------------------------------------

def _stencil_kernel(x_ref, o_ref, *, taps: tuple[float, ...], wrap: bool):
    x = x_ref[...].astype(jnp.float32)
    n = x.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    o_ref[...] = _stencil_vals(x, idx, taps, wrap, n)


def _stencil_vals(x, idx, taps: tuple[float, ...], wrap: bool, n: int):
    """§7.3 tap accumulation on a resident float32 block (shared body)."""
    acc = jnp.zeros_like(x)
    c = len(taps) // 2
    for k, w in enumerate(taps):        # unrolled ~M shift-mul-add cycles
        if w == 0:
            continue
        shifted = _roll(x, k - c)
        if not wrap:                    # zero the lanes that wrapped around
            if k - c > 0:
                shifted = jnp.where(idx >= k - c, shifted, 0.0)
            elif k - c < 0:
                shifted = jnp.where(idx < n + (k - c), shifted, 0.0)
        acc = acc + w * shifted
    return acc


@functools.partial(jax.jit, static_argnames=("taps", "wrap", "interpret"))
def stencil(x: jax.Array, taps: tuple[float, ...], *, wrap: bool = True,
            interpret: bool | None = None) -> jax.Array:
    """(R, N) rows filtered by an odd-length tap vector.

    ``wrap=True`` keeps the historical ring semantics (row ends wrap);
    ``wrap=False`` zero-pads the row ends — the canonical `repro.cpm`
    convention (see ``repro.cpm.semantics``).
    """
    r, n = x.shape
    return pl.pallas_call(
        functools.partial(_stencil_kernel, taps=taps, wrap=wrap),
        grid=(r,),
        in_specs=[_each_row(n)],
        out_specs=_each_row(n),
        out_shape=jax.ShapeDtypeStruct((r, 1, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(_as_rows3(x)).reshape(r, n)


# ---------------------------------------------------------------------------
# §4.2 compact (stable pack, log-depth cumsum-gather)
# ---------------------------------------------------------------------------

def _compact_kernel(x_ref, k_ref, f_ref, o_ref, l_ref, *, n: int):
    x = x_ref[...]                                   # (1, n) row
    keep = k_ref[...]                                # (1, n) int32 0/1 flags
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    # phase 1: inclusive cumsum of the keep flags — a Hillis-Steele doubling
    # tree, ceil(log2(n)) concurrent shift+add cycles (the paper's per-object
    # range moves collapsed into one log-depth rank computation)
    c = keep
    levels = (n - 1).bit_length() if n > 1 else 0
    for b in range(levels):
        stride = 1 << b
        sh = _roll(c, stride)
        c = c + jnp.where(idx >= stride, sh, 0)
    new_len = jnp.sum(keep, axis=-1, keepdims=True)  # (1, 1) survivor count
    # phase 2: kept lane i moves left by d = i + 1 - c[i], the number of
    # dropped lanes before it — one roll per bit of d, low bit first,
    # ~log2(n) more concurrent cycles.  Two movers never land on one lane: two kept
    # lanes k apart differ in d by less than k, and after the bits below b
    # their remaining offsets differ by a multiple of 2**b.
    v, d, live = x, idx + 1 - c, keep
    for b in range(levels):
        stride = 1 << b
        moves = live * ((d >> b) & 1)                # int32 0/1 masks
        arrive = (_roll(moves, -stride) != 0) & (idx < n - stride)
        v = jnp.where(arrive, _roll(v, -stride), v)
        d = jnp.where(arrive, _roll(d, -stride), d)
        live = jnp.where(arrive, 1, live - moves)
    o_ref[...] = jnp.where(idx < new_len, v, f_ref[0, 0])
    l_ref[...] = new_len


@functools.partial(jax.jit, static_argnames=("interpret",))
def compact(x: jax.Array, keep: jax.Array, fill=0, *,
            interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Stable §4.2 pack of every (R, N) row: kept lanes move to the front
    (order preserved), vacated lanes take ``fill``.  Returns
    ``(compacted (R, N), new_len (R,))``.  ~2·log2(N) concurrent steps —
    bit-identical to ``reference.movable.compact``."""
    r, n = x.shape
    fill_arr = jnp.asarray(fill, x.dtype).reshape(1, 1)
    out, nl = pl.pallas_call(
        functools.partial(_compact_kernel, n=n),
        grid=(r,),
        in_specs=[_each_row(n), _each_row(n),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[_each_row(n), _each_row(1)],
        out_shape=[jax.ShapeDtypeStruct((r, 1, n), x.dtype),
                   jax.ShapeDtypeStruct((r, 1, 1), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(_as_rows3(x), _as_rows3(keep.astype(jnp.int32)), fill_arr)
    return out.reshape(r, n), nl.reshape(r)


# ---------------------------------------------------------------------------
# paged-row movement (repro.cpm.pool banks)
# ---------------------------------------------------------------------------

def _copy_row_kernel(idx_ref, x_ref, o_ref):
    del idx_ref                                      # consumed by index_map
    o_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(x: jax.Array, idx: jax.Array, *,
                interpret: bool | None = None) -> jax.Array:
    """(R, N) bank x (K,) page indices -> (K, N) gathered rows.

    The index vector rides in scalar-prefetch, so each grid step's BlockSpec
    resolves to the dynamic source row before the body runs — one (1, N)
    page DMA per output row, the paged-KV access pattern."""
    k = idx.shape[0]
    n = x.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=[_row_spec(n, lambda i, iref: (iref[i], 0, 0))],
        out_specs=_row_spec(n, lambda i, iref: (i, 0, 0)))
    return pl.pallas_call(
        _copy_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, 1, n), x.dtype),
        interpret=resolve_interpret(interpret),
    )(idx.astype(jnp.int32), _as_rows3(x)).reshape(k, n)


def _scatter_row_kernel(inv_ref, d_ref, s_ref, o_ref):
    i = pl.program_id(0)
    o_ref[...] = jnp.where(inv_ref[i] >= 0, s_ref[...], d_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_rows(dst: jax.Array, idx: jax.Array, src: jax.Array, *,
                 interpret: bool | None = None) -> jax.Array:
    """Write ``src`` (K, N) rows into ``dst`` (R, N) at row indices ``idx``
    (K unique pages); untouched rows keep their content.

    Lowered as a gather over destination rows (the inverse page map rides in
    scalar-prefetch): row r reads ``src[inv[r]]`` when some page maps there
    and its own ``dst`` block otherwise — every output block is written
    exactly once, no aliasing or read-modify-write hazard."""
    r, n = dst.shape
    k = idx.shape[0]
    inv = jnp.full((r,), -1, jnp.int32).at[idx].set(
        jnp.arange(k, dtype=jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r,),
        in_specs=[_row_spec(n, lambda i, iref: (i, 0, 0)),
                  _row_spec(n, lambda i, iref: (jnp.maximum(iref[i], 0), 0,
                                                0))],
        out_specs=_row_spec(n, lambda i, iref: (i, 0, 0)))
    return pl.pallas_call(
        _scatter_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, 1, n), dst.dtype),
        interpret=resolve_interpret(interpret),
    )(inv, _as_rows3(dst), _as_rows3(src)).reshape(r, n)


# ---------------------------------------------------------------------------
# fused instruction streams — one launch for a whole §3–§7 program group
# ---------------------------------------------------------------------------

#: producer ops and their kernel output dtypes (cast to bool by the caller)
FUSED_PRODUCERS = {
    "activate": jnp.int8,
    "compare": jnp.int8,
    "substring_match": jnp.int8,
    "template_match": jnp.float32,
    "stencil": jnp.float32,
}

_FUSED_TRANSFORMS = ("shift", "insert", "delete", "truncate")


def _fused_apply(op: str, statics, x, ul, refs, idx, n: int):
    """Execute one broadcast instruction on the resident (BR, N) block.

    ``x`` is the live buffer block, ``ul`` the §4.2 used-length register
    (a (BR, 1) column) — both stay in VMEM across the whole group.  Every
    dynamic operand ref is read as a column slice ``ref[:, j:j+1]`` whose
    row count is 1 (broadcast) or BR (per-row), so the same body serves
    any row blocking.  Returns ``(x, ul, produced)`` with ``produced``
    None for buffer transforms.  Each branch mirrors the corresponding
    eager lowering exactly (same op order, same dtypes), so the fused
    stream is bit-identical to per-op dispatch.
    """
    s = dict(statics)
    live = idx < ul
    if op == "activate":
        p = refs[0][...]
        mask = _activate_vals(idx, p[:, 0:1], p[:, 1:2], p[:, 2:3])
        return x, ul, jnp.broadcast_to(mask, x.shape).astype(jnp.int8)
    if op == "shift":
        se = refs[0][...]
        fill = refs[1][:, 0:1] if s["has_fill"] else None
        return (_shift_vals(x, idx, se[:, 0:1], se[:, 1:2], s["shift"], n,
                            fill),
                ul, None)
    if op == "insert":
        pos, v, k = refs[0][:, 0:1], refs[1][...], s["k"]
        x = _shift_vals(x, idx, pos, ul - 1, k, n)
        for j in range(k):              # §4.2 broadcast write, unrolled
            x = jnp.where(idx == pos + j, v[:, j:j + 1], x)
        return x, jnp.minimum(ul + k, n), None
    if op == "delete":
        pos, fill, k = refs[0][:, 0:1], refs[1][:, 0:1], s["k"]
        x = _shift_vals(x, idx, pos + k, ul - 1, -k, n)
        x = jnp.where((idx >= ul - k) & (idx < ul), fill, x)
        return x, jnp.maximum(ul - k, 0), None
    if op == "truncate":
        return x, jnp.minimum(ul, refs[0][:, 0:1]), None
    if op == "compare":
        d = refs[0][:, 0:1]
        if s["has_mask"]:
            m = refs[1][:, 0:1]
            a, b = x & m, d & m
        else:
            a, b = x.astype(jnp.dtype(s["ct"])), d
        return x, ul, (_CMP[s["op"]](a, b) & live).astype(jnp.int8)
    if op == "substring_match":
        m = s["m"]
        ends = _substring_ends_vals(x, refs[0], m, idx)
        flags = ((ends > 0) & live).astype(jnp.int32)
        if s["where"] == "start":
            flags = jnp.where(idx <= n - m, _roll(flags, -(m - 1)), 0)
        return x, ul, flags.astype(jnp.int8)
    if op == "template_match":
        m = s["m"]
        sad = _sad_vals(x.astype(jnp.float32), refs[0], m)
        if s["mask_tail"]:
            sad = jnp.where(idx + m <= ul, sad, jnp.inf)
        return x, ul, sad
    if op == "stencil":
        base = x if s["wrap"] else jnp.where(live, x, jnp.zeros((), x.dtype))
        return x, ul, _stencil_vals(base.astype(jnp.float32), idx,
                                    s["taps"], s["wrap"], n)
    raise NotImplementedError(f"fused instruction {op!r}")


@functools.partial(jax.jit,
                   static_argnames=("instrs", "block_r", "interpret"))
def fused_stream(x: jax.Array, used_len: jax.Array, instrs, operands, *,
                 block_r: int = 1, interpret: bool | None = None):
    """Execute a fused instruction group in ONE kernel launch.

    ``x``: (R, N) device rows; ``used_len``: (R,) §4.2 length registers.
    ``instrs``: static tuple of ``(op, statics, n_operands)`` descriptors
    in stream order (``n_operands`` is emitted by the one lowering in
    ``repro.cpm.program.executors``, so the ref routing below cannot drift
    from it); ``operands``: the matching dynamic operand arrays, each
    ``(R, k)`` per-row or ``(1, k)`` broadcast.

    ``block_r`` rows load into VMEM per grid step (the autotuned knob —
    the executor picks it from the tuning cache); rows pad up to a
    multiple and the pad rows are sliced off on return, so any ``block_r``
    is bit-identical to ``block_r=1``.  The row block and its length
    register stay resident across every instruction — the Pallas
    realization of the paper's "broadcast the stream, execute in memory"
    (§3–§4).  Returns ``(rows, used_lens, producer_outputs)``.
    """
    r, n = x.shape
    counts = [nops for _, _, nops in instrs]
    assert len(operands) == sum(counts), (len(operands), counts)
    prod_dts = [FUSED_PRODUCERS[op] for op, _, _ in instrs
                if op in FUSED_PRODUCERS]

    br = max(1, min(int(block_r), r))
    pad = (-r) % br
    ul2 = used_len.reshape(r, 1)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        ul2 = jnp.pad(ul2, ((0, pad), (0, 0)))
        operands = tuple(
            jnp.pad(a, ((0, pad), (0, 0))) if a.shape[0] == r else a
            for a in operands)
    rp = r + pad

    def kernel(*refs):
        x_ref, ul_ref = refs[0], refs[1]
        pos = 2
        op_refs = []
        for c in counts:
            op_refs.append(refs[pos:pos + c])
            pos += c
        o_x, o_ul = refs[pos], refs[pos + 1]
        prod_refs = refs[pos + 2:]

        xv = x_ref[...]
        ul = ul_ref[...]                           # (br, 1) length column
        idx = jax.lax.broadcasted_iota(jnp.int32, (br, n), 1)
        pi = 0
        for (op, statics, _), orefs in zip(instrs, op_refs):
            xv, ul, out = _fused_apply(op, statics, xv, ul, orefs, idx, n)
            if out is not None:
                prod_refs[pi][...] = out
                pi += 1
        o_x[...] = xv
        o_ul[...] = jnp.broadcast_to(jnp.asarray(ul, jnp.int32), (br, 1))

    # br == 1 takes the (R, 1, N) row layout; a taller block satisfies
    # Mosaic's (8, 128) rule itself when br is a multiple of 8 or all rows
    rows3 = br == 1

    def per_row(a):
        """A per-row (rp, k) array as the kernel takes it, with its spec."""
        k = a.shape[-1]
        if rows3:
            return _as_rows3(a), _row_spec(k, lambda i: (i, 0, 0))
        return a, pl.BlockSpec((br, k), lambda i: (i, 0))

    def per_row_out(k, dt):
        if rows3:
            return (jax.ShapeDtypeStruct((rp, 1, k), dt),
                    _row_spec(k, lambda i: (i, 0, 0)))
        return (jax.ShapeDtypeStruct((rp, k), dt),
                pl.BlockSpec((br, k), lambda i: (i, 0)))

    args, in_specs = [], []
    for a in (x, ul2) + tuple(operands):
        if a.shape[0] == 1 and rp != 1:           # broadcast operand
            arr, spec = a, pl.BlockSpec(a.shape, lambda i: (0, 0))
        else:
            arr, spec = per_row(a)
        args.append(arr)
        in_specs.append(spec)
    outs = ([per_row_out(n, x.dtype), per_row_out(1, jnp.int32)]
            + [per_row_out(n, dt) for dt in prod_dts])
    out = pl.pallas_call(
        kernel,
        grid=(rp // br,),
        in_specs=in_specs,
        out_specs=[spec for _, spec in outs],
        out_shape=[shape for shape, _ in outs],
        interpret=resolve_interpret(interpret),
    )(*args)
    out = [o.reshape(rp, o.shape[-1]) for o in out]
    return out[0][:r], out[1][:r, 0], [o[:r] for o in out[2:]]
