"""Float32 reference of the test family ``toy_local``: the dense
decoder (SwiGLU) whose even layers (0, 2, 4, ...) attend only to the
last ``sliding_window`` positions, their own included, and whose odd
layers attend causally to all of them.

Departures from a published model, where the reference follows the
program's equations:
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import core

DEPARTURES = (
    "embeddings are scaled by sqrt(hidden_size) before the first layer",
)
__doc__ += "".join(f"\n- {d}" for d in DEPARTURES)


def _attention(lw, h, cfg, window, fp8):
    s = h.shape[0]
    nh, kvh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    theta = cfg["rope_theta"]
    q = core.rope(core._linear(h, lw["wq"], fp8).reshape(s, nh, dh), theta)
    k = core.rope(core._linear(h, lw["wk"], fp8).reshape(s, kvh, dh), theta)
    v = core._linear(h, lw["wv"], fp8).reshape(s, kvh, dh)
    k = jnp.repeat(k, nh // kvh, axis=1)
    v = jnp.repeat(v, nh // kvh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i) if window is None else (j <= i) & (j > i - window)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return core._linear(o.reshape(s, nh * dh), lw["wo"], fp8)


def logits(w, cfg, tokens, fp8: bool = False):
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens] * math.sqrt(cfg["hidden_size"])
        for i in range(cfg["num_hidden_layers"]):
            lw = {k: v[i] for k, v in w.items()
                  if k not in ("embed", "unembed", "final_norm")}
            window = cfg["sliding_window"] if i % 2 == 0 else None
            x = x + _attention(lw, core.rms_norm(x, lw["norm1"], eps), cfg,
                               window, fp8)
            x = x + core.swiglu(core.rms_norm(x, lw["norm2"], eps),
                                lw["w_gate"], lw["w_up"], lw["w_down"], fp8)
        x = core.rms_norm(x, w["final_norm"], eps)
        out = w["embed"] if cfg["tie_word_embeddings"] else w["unembed"]
        return core._linear(x, out.T, fp8)
