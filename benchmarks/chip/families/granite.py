"""The Granite family: dense Granite / Llama-style decoders, and
GraniteMoE where ``num_local_experts`` is set; one attention kind.

Keys read (Hugging Face ``GraniteConfig`` / ``GraniteMoeConfig``):
``num_hidden_layers``, ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``intermediate_size`` (each
expert's width under MoE), ``vocab_size``, ``rms_norm_eps``,
``rope_theta``, ``tie_word_embeddings``; under MoE also
``num_local_experts``, ``num_experts_per_tok`` and the assumed
``capacity_factor``.  Every expert of the file is held on the chip.

:func:`stack_pattern` and :func:`program_params` (with ``pattern``) serve
other decoder families whose weights follow this layout.
"""

from __future__ import annotations

PATTERN = ("attn",)


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, h=h, kvh=kvh,
                dh=cfg.get("head_dim") or d // h, f=cfg["intermediate_size"],
                V=cfg["vocab_size"], E=cfg.get("num_local_experts", 0),
                k=cfg.get("num_experts_per_tok", 0))


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig, MoEConfig
    moe = None
    if cfg.get("num_local_experts"):
        moe = MoEConfig(n_experts=cfg["num_local_experts"],
                        top_k=cfg["num_experts_per_tok"],
                        capacity_factor=cfg["capacity_factor"])
    return ModelConfig(
        name=cfg["name"], family="moe" if moe else "dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim", 0), moe=moe,
        ffn="moe" if moe else "swiglu", norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"])


def tiny(cfg: dict) -> dict:
    """Two layers of width 64; under MoE 4 experts, top-2, drop-free."""
    cfg = dict(cfg, num_hidden_layers=2, hidden_size=64, head_dim=16,
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=96, vocab_size=256)
    if cfg.get("num_local_experts"):
        cfg.update(num_local_experts=4, num_experts_per_tok=2,
                   capacity_factor=2.0)
    return cfg


# -- weights ------------------------------------------------------------------
def weight_shapes(cfg: dict) -> dict:
    """Per-layer arrays stacked on a leading layer axis, unpadded vocab."""
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
    return shapes


def stack_pattern(layers: dict, pattern: tuple, n_layers: int):
    """A tree of per-layer arrays (leading layer axis) as ``lm``'s
    ``blocks`` and ``tail``: layer ``r * len(pattern) + u`` is repeat
    ``r`` of ``blocks[u]``; the layers past the last whole repeat are
    ``tail``, one tree each.  Fewer layers than one period make one
    repeat of all of them, as ``lm`` lays them out."""
    import jax
    p = len(pattern)
    reps = n_layers // p
    if reps == 0:
        p, reps = n_layers, 1
    end = reps * p
    blocks = [jax.tree.map(lambda x, u=u: x[u:end:p], layers)
              for u in range(p)]
    tail = [jax.tree.map(lambda x, i=i: x[i], layers)
            for i in range(end, n_layers)]
    return blocks, tail


def program_params(cfg: dict, w: dict, padded_vocab: int,
                   pattern: tuple = PATTERN) -> dict:
    """The canonical weights in ``repro.models.lm``'s parameter tree,
    stacked per kind of ``pattern``; embeddings padded to
    ``padded_vocab`` rows with zeros (the program masks those logits)."""
    import jax.numpy as jnp
    pad = lambda x: jnp.pad(x, ((0, padded_vocab - x.shape[0]), (0, 0)))
    layers = {"norm1": {"scale": w["norm1"]},
              "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
              "norm2": {"scale": w["norm2"]}}
    if "router" in w:
        layers["ffn"] = {"router": w["router"], "expert_gate": w["e_gate"],
                         "expert_in": w["e_up"], "expert_out": w["e_down"]}
    else:
        layers["ffn"] = {"w_gate": w["w_gate"], "w_in": w["w_up"],
                         "w_out": w["w_down"]}
    blocks, tail = stack_pattern(layers, pattern, dims(cfg)["L"])
    p = {"emb": pad(w["embed"]), "final_norm": {"scale": w["final_norm"]},
         "blocks": blocks, "tail": tail}
    if "unembed" in w:
        p["unemb"] = pad(w["unembed"])
    return p


# -- useful FLOPs ------------------------------------------------------------
# Live rows only, the routed top-k experts (not the capacity padding the
# program computes), causal attention at each row's real context, logits
# only where the program computes them (the last prompt position in
# prefill, every decode step).  A multiply-add is two FLOPs.
def layer_matmul_flops(cfg: dict) -> float:
    """Weight-matrix FLOPs of one token through one layer."""
    m = dims(cfg)
    d, h, kvh, dh, f = m["d"], m["h"], m["kvh"], m["dh"], m["f"]
    attn = 2 * d * (h + 2 * kvh) * dh + 2 * h * dh * d
    if m["E"]:
        ffn = 2 * d * m["E"] + m["k"] * 2 * 3 * d * f
    else:
        ffn = 2 * 3 * d * f
    return float(attn + ffn)


def attention_flops(cfg: dict, keys: float) -> float:
    """Scores and weighted values of one query against ``keys`` keys, over
    all layers and heads."""
    m = dims(cfg)
    return 4.0 * m["L"] * m["h"] * m["dh"] * keys


def logits_flops(cfg: dict) -> float:
    m = dims(cfg)
    return 2.0 * m["d"] * m["V"]


def prefill_flops(cfg: dict, s: int) -> float:
    """One prompt of ``s`` tokens: every position through every layer,
    causal attention (position i sees i + 1 keys), logits at the last."""
    m = dims(cfg)
    return (s * m["L"] * layer_matmul_flops(cfg)
            + attention_flops(cfg, s * (s + 1) / 2) + logits_flops(cfg))


def decode_flops(cfg: dict, keys: int) -> float:
    """One decode token that attends to ``keys`` keys (its own included)."""
    m = dims(cfg)
    return (m["L"] * layer_matmul_flops(cfg) + attention_flops(cfg, keys)
            + logits_flops(cfg))
