"""jit'd public wrappers for every kernel, with backend dispatch.

``impl`` selects: "pallas" (TPU lowering, interpret=False), "interpret"
(Pallas body executed on CPU — the validation path off the chip), or
"ref" (pure-jnp oracle, also the dry-run lowering so the roofline reflects
the tiled dataflow rather than interpret-mode callbacks).  ``None`` picks
by platform (``cpm_kernels.pallas_native``): "pallas" on a TPU, "ref"
elsewhere.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax

from . import cpm_kernels, flash_attention as fa, ref

_IMPL: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "impl", default=None)


@contextlib.contextmanager
def use_impl(impl: str):
    """Trace the enclosed calls with ``impl`` in place of the platform
    default — e.g. ``with use_impl("ref"): jax.jit(lm.prefill)(...)`` to
    check the Pallas path against the jnp oracle on the same chip.  It
    acts at trace time, so wrap a fresh trace, not a cached jit."""
    token = _IMPL.set(impl)
    try:
        yield
    finally:
        _IMPL.reset(token)


def _mode(impl):
    impl = impl if impl is not None else _IMPL.get()
    if impl is None:
        return "pallas" if cpm_kernels.pallas_native() else "ref"
    return impl


def attention(q, k, v, *, causal=True, window=None, impl=None, **kw):
    m = _mode(impl)
    if m == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       **{k_: v_ for k_, v_ in kw.items()
                                          if k_ == "block_k"})
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=(m == "interpret"), **kw)


def decode_attention(q, k, v, cache_len=None, *, window=None, impl=None):
    # decode is a single-row gather-heavy op; the ref dataflow is already
    # the TPU-efficient form (no score materialization beyond (H, S)).
    return ref.decode_attention_ref(q, k, v, cache_len, window=window)


def sort(x, *, impl=None):
    m = _mode(impl)
    if m == "ref":
        return ref.oddeven_sort_ref(x)
    return cpm_kernels.oddeven_sort(x, interpret=(m == "interpret"))


def section_sum(x, *, section=1024, impl=None):
    m = _mode(impl)
    if m == "ref":
        return ref.section_sum_ref(x)
    return cpm_kernels.section_sum(x, section, interpret=(m == "interpret"))


def template_match(data, template, *, impl=None):
    m = _mode(impl)
    if m == "ref":
        return jax.vmap(lambda d: ref.template_match_ref(d, template))(data)
    return cpm_kernels.template_match(data, template,
                                      interpret=(m == "interpret"))


def substring_match(hay, needle, *, impl=None):
    m = _mode(impl)
    if m == "ref":
        out = jax.vmap(lambda h: ref.substring_match_ref(h, needle))(hay)
        return out
    return cpm_kernels.substring_match(hay, needle,
                                       interpret=(m == "interpret"))


def stencil(x, taps, *, impl=None):
    m = _mode(impl)
    if m == "ref":
        return jax.vmap(lambda r: ref.stencil_ref(r, list(taps)))(x)
    return cpm_kernels.stencil(x, tuple(taps), interpret=(m == "interpret"))
