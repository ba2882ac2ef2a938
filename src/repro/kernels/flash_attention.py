"""Blocked online-softmax attention (TPU Pallas).

The framework's compute hot-spot.  TPU-native tiling: q blocks of (block_q,
head_dim) stream kv blocks of (block_k, head_dim) through VMEM, carrying the
running max / denominator / accumulator in VMEM scratch across the innermost
grid dimension (the canonical TPU flash schedule — grid iteration is
sequential on TPU, so the kv axis is the in-order accumulation axis).

Supports causal masking, local (sliding-window) masking and GQA head
grouping via the kv BlockSpec index map.  Validated against ``ref.py`` in
interpret mode off the chip; TPU is the lowering target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cpm_kernels import resolve_interpret

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int | None,
                 block_q: int, block_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), dtype=bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                             # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                          # masked lanes: exp(-1e30)→0
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, KVH, Skv, D) with H % KVH == 0.

    Returns (B, H, Sq, D).  ``window`` masks cols <= rows - window (local
    attention, RecurrentGemma-style).  ``interpret=None`` runs the kernel
    compiled on a TPU and under the Pallas interpreter elsewhere.
    """
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    assert h % kvh == 0
    group = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    scale = d ** -0.5

    grid = (b, h, sq // block_q, skv // block_k)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bb, hh, iq, ik: (bb, hh, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, iq, ik, g=group: (bb, hh // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, iq, ik, g=group: (bb, hh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, iq, ik: (bb, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
