"""Float32 reference of a GraniteMoE decoder: the dense decoder with each
feed-forward replaced by a softmax router over ``num_local_experts``
SwiGLU experts, of which each token takes its top ``num_experts_per_tok``
with their router probabilities renormalised to sum to 1.  Every expert
runs on every token and the unchosen ones are weighted 0, so no token is
ever dropped.

Departures from the published model, where the reference follows the
program's equations:
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import core

DEPARTURES = (
    "embeddings are scaled by sqrt(hidden_size), not by embedding_multiplier",
    "attention scores are scaled by 1/sqrt(head_dim), not by "
    "attention_multiplier",
    "residual branches are added unscaled, not times residual_multiplier",
    "logits are not divided by logits_scaling",
)
__doc__ += "".join(f"\n- {d}" for d in DEPARTURES)


def _ffn_for(cfg):
    top_k = cfg["num_experts_per_tok"]

    def ffn(lw, h, fp8):
        probs = jax.nn.softmax(core._linear(h, lw["router"], fp8), -1)
        top, idx = jax.lax.top_k(probs, top_k)                 # (S, k)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(h.shape[0])[:, None], idx].set(
                top / jnp.sum(top, -1, keepdims=True))         # (S, E)
        experts = jax.vmap(
            lambda g, u, d: core.swiglu(h, g, u, d, fp8),
            out_axes=1)(lw["e_gate"], lw["e_up"], lw["e_down"])  # (S, E, d)
        return jnp.einsum("se,sed->sd", gates, experts)

    return ffn


def logits(w, cfg, tokens, fp8: bool = False):
    return core.decoder_logits(w, cfg, tokens, _ffn_for(cfg), fp8)
