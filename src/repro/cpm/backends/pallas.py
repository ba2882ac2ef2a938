"""Chip-scale backend: VMEM Pallas kernels (VREG lanes = PEs).

Adapter over `repro.kernels.cpm_kernels`.  Row-wise kernels see a flattened
``(rows, n)`` layout (batch dims collapse to rows); reductions are
row-batched and HBM-tiled inside the kernels themselves — a batched
``(..., N)`` layout is ONE ``pallas_call`` over a (rows, sections) grid,
never a vmap over per-row launches, and N may exceed one VMEM block.
``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere —
the ``interpret=`` plumbing the kernels already expose.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import cpm_kernels as K

from .. import tuning
from ..optable import optimal_section
from . import _TableBacked


def _rows(x):
    """(..., n) -> ((R, n), unflatten)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]) if x.ndim != 2 else x
    if x.ndim == 1:
        x2 = x.reshape(1, -1)
    return x2, (lambda out: out.reshape(*lead, out.shape[-1]))


class PallasBackend(_TableBacked):
    name = "pallas"

    def __init__(self, interpret: bool | None = None):
        self.interpret = K.resolve_interpret(interpret)

    def _tuned_section(self, op: str, x, default: int, run) -> int:
        """Autotuned section (VMEM block width) for one reduction call,
        cached per (op, shape, dtype, backend) with a JSON spill.  The
        candidate grid spans the ~sqrt(N) paper choice through whole-row
        blocks; ``run(section)`` times candidates on synthesized zeros —
        outside any active trace only (``tuning.measurable``); traced
        callers get cache hits or the static default.  An explicit
        ``section=`` from the caller always bypasses tuning (this is
        only reached when it was None)."""
        n = x.shape[-1]
        default = K.lane_section(default, n)
        if n < 2048:                    # tuning overhead beats any return
            return default
        # shape rule before timing: each candidate as the kernel would
        # really run it (whole lane tiles), so every one compiles
        cands = sorted({K.lane_section(c, n) for c in
                        (optimal_section(n), 256, 1024, 4096, n)})
        key = (f"section:{op}|{'x'.join(map(str, x.shape))}"
               f"|{jnp.dtype(x.dtype).name}"
               f"|{tuning.backend_key(self.interpret)}")
        return int(tuning.pick(key, cands, run, default=default))

    def activate(self, n, start, end, carry=1):
        return K.activate(n, start, end, carry, interpret=self.interpret)

    def shift_range(self, x, start, end, shift, fill=None):
        x2, un = _rows(x)
        return un(K.shift_range(x2, start, end, shift, fill,
                                interpret=self.interpret))

    def substring_match(self, hay, needle):
        x2, un = _rows(hay)
        return un(K.substring_match(x2, needle,
                                    interpret=self.interpret).astype(bool))

    def compare(self, x, datum, op="eq"):
        x2, un = _rows(x)
        return un(K.compare(x2, datum, op, interpret=self.interpret))

    def histogram(self, x, edges, section=None):
        if section is None:
            xz = tuning.synth(x.shape, x.dtype)
            ez = tuning.synth(edges.shape, edges.dtype)
            section = self._tuned_section(
                f"histogram{edges.shape[-1] - 1}", x, 1024,
                lambda s: K.histogram(xz, ez, s, interpret=self.interpret))
        sec = min(section, x.shape[-1])
        return K.histogram(x, edges, sec, interpret=self.interpret)

    def section_sum(self, x, section=None):
        if section is None:
            xz = tuning.synth(x.shape, x.dtype)
            section = self._tuned_section(
                "section_sum", x, optimal_section(x.shape[-1]),
                lambda s: K.section_sum(xz, s, interpret=self.interpret))
        out = K.section_sum(x, section, interpret=self.interpret)
        # match the reference accumulation dtype (jnp.sum semantics)
        ref_dtype = jnp.zeros((), x.dtype).sum().dtype
        return out.astype(ref_dtype)

    def global_limit(self, x, mode="max", section=None):
        if section is None:
            xz = tuning.synth(x.shape, x.dtype)
            section = self._tuned_section(
                "section_limit", x, optimal_section(x.shape[-1]),
                lambda s: K.section_limit(xz, s, mode,
                                          interpret=self.interpret))
        return K.section_limit(x, section, mode, interpret=self.interpret)

    def super_sum(self, x, section=None):
        if section is None:
            xz = tuning.synth(x.shape, x.dtype)
            section = self._tuned_section(
                "super_sum", x, optimal_section(x.shape[-1]),
                lambda s: K.super_sum(xz, s, interpret=self.interpret))
        out = K.super_sum(x, section, interpret=self.interpret)
        return out.astype(jnp.zeros((), x.dtype).sum().dtype)

    def super_limit(self, x, mode="max", section=None):
        if section is None:
            xz = tuning.synth(x.shape, x.dtype)
            section = self._tuned_section(
                "super_limit", x, optimal_section(x.shape[-1]),
                lambda s: K.super_limit(xz, s, mode,
                                        interpret=self.interpret))
        return K.super_limit(x, section, mode, interpret=self.interpret)

    def sort(self, x, steps=None):
        x2, un = _rows(x)
        return un(K.oddeven_sort(x2, steps, interpret=self.interpret))

    def template_match(self, data, template):
        x2, un = _rows(data)
        return un(K.template_match(x2, template, interpret=self.interpret))

    def stencil(self, x, taps, wrap=False):
        x2, un = _rows(x)
        return un(K.stencil(x2, tuple(float(t) for t in taps), wrap=wrap,
                            interpret=self.interpret))

    def compact(self, x, keep, fill=0):
        lead = x.shape[:-1]
        x2, un = _rows(x)
        k2 = jnp.broadcast_to(keep, x.shape).reshape(x2.shape)
        out, new_len = K.compact(x2, k2, fill, interpret=self.interpret)
        return un(out), (new_len.reshape(lead) if lead
                         else new_len.reshape(()))

    def fused_stream(self, x, used_len, instrs, operands, block_r: int = 1):
        """One ``pallas_call`` for a whole fused instruction group: the row
        block and its §4.2 length register stay resident in VMEM across
        every instruction (see ``cpm_kernels.fused_stream``).  ``block_r``
        rows per grid step — the executor autotunes it per stream
        signature."""
        return K.fused_stream(x, used_len, instrs, operands,
                              block_r=block_r, interpret=self.interpret)
