"""Each reference agrees with the program's ``repro.models.lm`` at a tiny
size on the CPU, on the same seeded weights: tightly with the program
computing in float32, loosely in its own bfloat16.  That shows the
reference, not the program, is right: the two were written apart."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import weights
from reference import granite_dense, granite_moe

CONFIGS = {"granite-8b-code.L8": granite_dense,
           "granite-3.0-1b-a400m": granite_moe}


def tiny(name):
    cfg = json.loads((harness.HERE / "configs" / f"{name}.json").read_text())
    cfg, _ = harness.rehearsal_sizes(cfg, json.loads(
        (harness.HERE / "traffic" / "code-chat.json").read_text()))
    if cfg.get("num_local_experts"):
        cfg["capacity_factor"] = 2.0     # E / k: drop-free, as on the chip
    return cfg


def program_logits(cfg, w, tokens):
    from repro.models import lm
    mcfg = harness.model_config(cfg)
    p = weights.program_params(cfg, w, lm.padded_vocab(mcfg))
    x, _ = lm.forward(p, mcfg, {"tokens": tokens[None]}, remat=False)
    return lm._logits(p, mcfg, x)[0, :, :cfg["vocab_size"]].astype(
        jnp.float32)


@pytest.mark.parametrize("name", sorted(CONFIGS))
@pytest.mark.parametrize("seed", [0, 2**33 + 5])
def test_reference_matches_program_float32(name, seed, monkeypatch):
    from repro.models import layers
    monkeypatch.setattr(layers, "COMPUTE_DTYPE", jnp.float32)
    cfg = tiny(name)
    w = weights.canonical(cfg, weights.key(seed))
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], 32), jnp.int32)
    want = CONFIGS[name].logits(w, cfg, tokens)
    with jax.default_matmul_precision("highest"):
        got = program_logits(cfg, w, tokens)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-4, err


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_matches_program_bfloat16(name):
    cfg = tiny(name)
    w = weights.canonical(cfg, weights.key(7))
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, cfg["vocab_size"], 32), jnp.int32)
    want = CONFIGS[name].logits(w, cfg, tokens)
    got = program_logits(cfg, w, tokens)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 5e-2, err


def test_seeds_past_32_bits_differ():
    a, b = weights.key(5), weights.key(2**33 + 5)
    assert not bool(jnp.all(a == b))


def test_departures_listed():
    for mod in CONFIGS.values():
        assert mod.DEPARTURES
        for d in mod.DEPARTURES:
            assert d in mod.__doc__
