"""Seeded weights, made once on the device, for the program and the reference.

:func:`canonical` draws every weight of a configuration file from the
seed in one flat layout of the benchmark's own: per-layer arrays stacked
on a leading layer axis, float32, unpadded vocabulary.  The reference
(``reference/``) reads that layout.  :func:`program_params` re-arranges
the same arrays into the tree the program's ``repro.models.lm`` takes, so
both sides compute with the same numbers while neither takes anything
that the other made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def key(seed: int):
    """A PRNG key from a seed of any size: JAX keeps 32 bits of an int, so
    the high bits are folded in."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, h=h, kvh=kvh,
                dh=cfg.get("head_dim") or d // h, f=cfg["intermediate_size"],
                V=cfg["vocab_size"], E=cfg.get("num_local_experts", 0),
                k=cfg.get("num_experts_per_tok", 0))


def canonical(cfg: dict, base) -> dict:
    """Every weight of ``cfg`` (float32) from the key ``base``
    (:func:`key` of the seed).  Call under ``jax.jit`` so the draws
    happen on the device in one program."""
    m = dims(cfg)
    L, d, h, kvh, dh, f, V, E = (m[x] for x in "L d h kvh dh f V E".split())
    shapes = {
        "embed": ((V, d), 0.02),
        "final_norm": ((d,), None),
        "norm1": ((L, d), None), "norm2": ((L, d), None),
        "wq": ((L, d, h * dh), d), "wk": ((L, d, kvh * dh), d),
        "wv": ((L, d, kvh * dh), d), "wo": ((L, h * dh, d), h * dh),
    }
    if not cfg["tie_word_embeddings"]:
        shapes["unembed"] = ((V, d), 0.02)
    if E:
        shapes.update({"router": ((L, d, E), d),
                       "e_gate": ((L, E, d, f), d), "e_up": ((L, E, d, f), d),
                       "e_down": ((L, E, f, d), f)})
    else:
        shapes.update({"w_gate": ((L, d, f), d), "w_up": ((L, d, f), d),
                       "w_down": ((L, f, d), f)})
    out = {}
    for i, (name, (shape, scale)) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(base, i)
        if scale is None:                          # norm scales
            out[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif isinstance(scale, float):             # embeddings
            out[name] = scale * jax.random.normal(k, shape, jnp.float32)
        else:                                      # dense: N(0, 1/fan_in)
            out[name] = (jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                                     jnp.float32)
                         / math.sqrt(scale))
    return out


def program_params(cfg: dict, w: dict, padded_vocab: int) -> dict:
    """The canonical weights in ``repro.models.lm``'s parameter tree: one
    stacked block unit over all layers, embeddings padded to
    ``padded_vocab`` rows with zeros (the program masks those logits)."""
    pad = lambda x: jnp.pad(x, ((0, padded_vocab - x.shape[0]), (0, 0)))
    block = {"norm1": {"scale": w["norm1"]},
             "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
             "norm2": {"scale": w["norm2"]}}
    if "router" in w:
        block["ffn"] = {"router": w["router"], "expert_gate": w["e_gate"],
                        "expert_in": w["e_up"], "expert_out": w["e_down"]}
    else:
        block["ffn"] = {"w_gate": w["w_gate"], "w_in": w["w_up"],
                        "w_out": w["w_down"]}
    p = {"emb": pad(w["embed"]), "final_norm": {"scale": w["final_norm"]},
         "blocks": [block], "tail": []}
    if "unembed" in w:
        p["unemb"] = pad(w["unembed"])
    return p
