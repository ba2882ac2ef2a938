"""The decoder forward pass written from the architecture's equations.

Plain ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time, with no kernels, cache, paging or batching: token
embedding, then per layer RMSNorm -> grouped-query causal attention with
rotary positions -> residual -> RMSNorm -> feed-forward -> residual, a
final RMSNorm and the output projection.  It imports nothing of the
program under test and reads weights in the benchmark's own layout
(``weights.canonical``).

``fp8=True`` gives the control: every linear layer takes its inputs and
weights rounded to float8 e4m3 (scaled per token and per matrix to the
format's range), accumulating in float32.  It is the step below the
bfloat16 compute that the configurations state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_E4M3_MAX = 448.0


def _fp8(x, axis):
    """x rounded through float8 e4m3, scaled along ``axis`` to its range."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, fp8: bool):
    """x (..., n) @ w (n, m)."""
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, None)
    return x @ w


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (S, H, dh), positions 0..S-1; the halves of each head rotate as
    pairs (x_i, x_{i + dh/2}) at frequency theta^(-i / (dh/2))."""
    s, _, dh = x.shape
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq       # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(lw, h, cfg, fp8):
    s = h.shape[0]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // nh
    q = rope(_linear(h, lw["wq"], fp8).reshape(s, nh, dh), cfg["rope_theta"])
    k = rope(_linear(h, lw["wk"], fp8).reshape(s, kvh, dh), cfg["rope_theta"])
    v = _linear(h, lw["wv"], fp8).reshape(s, kvh, dh)
    group = nh // kvh
    k = jnp.repeat(k, group, axis=1)                 # head i reads kv i // group
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return _linear(o.reshape(s, nh * dh), lw["wo"], fp8)


def swiglu(x, w_gate, w_up, w_down, fp8):
    return _linear(jax.nn.silu(_linear(x, w_gate, fp8))
                   * _linear(x, w_up, fp8), w_down, fp8)


def decoder_logits(w, cfg, tokens, ffn, fp8: bool = False):
    """Logits (S, vocab) of every position of ``tokens`` (S,).  ``ffn(lw,
    h, fp8)`` is the feed-forward of one layer's weights ``lw``."""
    eps = cfg["rms_norm_eps"]
    layer_keys = [k for k, v in w.items()
                  if v.ndim >= 2 and v.shape[0] == cfg["num_hidden_layers"]
                  and k not in ("embed", "unembed")]
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens] * math.sqrt(cfg["hidden_size"])

        def layer(x, lw):
            x = x + attention(lw, rms_norm(x, lw["norm1"], eps), cfg, fp8)
            x = x + ffn(lw, rms_norm(x, lw["norm2"], eps), fp8)
            return x, None

        x, _ = jax.lax.scan(layer, x, {k: w[k] for k in layer_keys})
        x = rms_norm(x, w["final_norm"], eps)
        out = w["embed"] if cfg["tie_word_embeddings"] else w["unembed"]
        return _linear(x, out.T, fp8)
