"""A family the harness has never seen, for the tests: the Granite
decoder's weights under the layer pattern (sliding-window attention,
full attention), five layers, so two whole repeats and one windowed
layer in ``tail``.  A windowed layer attends to the last
``sliding_window`` positions, its own included."""

from __future__ import annotations

import dataclasses

from families import granite

PATTERN = ("attn_local", "attn")

weight_shapes = granite.weight_shapes


def model_config(cfg: dict):
    return dataclasses.replace(granite.model_config(cfg), pattern=PATTERN,
                               window=cfg["sliding_window"])


def program_params(cfg: dict, w: dict, padded_vocab: int) -> dict:
    return granite.program_params(cfg, w, padded_vocab, pattern=PATTERN)


def tiny(cfg: dict) -> dict:
    """The file is at rehearsal size already."""
    return dict(cfg)


def _windowed_layers(cfg: dict) -> int:
    L = cfg["num_hidden_layers"]
    return sum(PATTERN[i % len(PATTERN)] == "attn_local" for i in range(L))


def _attention_flops(cfg: dict, full_keys: float, window_keys: float):
    m = granite.dims(cfg)
    n_win = _windowed_layers(cfg)
    keys = (m["L"] - n_win) * full_keys + n_win * window_keys
    return 4.0 * m["h"] * m["dh"] * keys


def prefill_flops(cfg: dict, s: int) -> float:
    """Position i sees i + 1 keys, at most the window in a windowed
    layer."""
    m, w = granite.dims(cfg), cfg["sliding_window"]
    capped = sum(min(i, w) for i in range(1, s + 1))
    return (s * m["L"] * granite.layer_matmul_flops(cfg)
            + _attention_flops(cfg, s * (s + 1) / 2, capped)
            + granite.logits_flops(cfg))


def decode_flops(cfg: dict, keys: int) -> float:
    m, w = granite.dims(cfg), cfg["sliding_window"]
    return (m["L"] * granite.layer_matmul_flops(cfg)
            + _attention_flops(cfg, keys, min(keys, w))
            + granite.logits_flops(cfg))
