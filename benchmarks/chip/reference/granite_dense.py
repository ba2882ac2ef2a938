"""Float32 reference of a dense Granite / Llama-style decoder (SwiGLU).

Departures from the published model, where the reference follows the
program's equations:
"""

from __future__ import annotations

from . import core

DEPARTURES = (
    "embeddings are scaled by sqrt(hidden_size) before the first layer",
    "no attention or MLP biases",
)
__doc__ += "".join(f"\n- {d}" for d in DEPARTURES)


def _ffn(lw, h, fp8):
    return core.swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], fp8)


def logits(w, cfg, tokens, fp8: bool = False):
    return core.decoder_logits(w, cfg, tokens, _ffn, fp8)
