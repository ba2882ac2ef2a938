"""A family the harness has never seen enters as files only.

``data/`` holds a configuration (``configs/toy-local.json``), its family
module (``families/toy_local.py``: the layer pattern (sliding-window,
full) with a window shorter than most prompts) and its float32 reference
(``reference/toy_local.py``).  The test adds those directories to the
packages' search paths, as a new file under ``families/`` and
``reference/`` would be found, and serves the configuration through the
harness at rehearsal size on the CPU: weights, gateway, warm-up, an
open-loop window, the exact checks and the reference's logit gaps."""

import asyncio
import json
import pathlib

import pytest

import families
import harness
import reference
from families import granite
from traffic import load, schedule

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(DATA / "families")])
    monkeypatch.setattr(reference, "__path__",
                        [*reference.__path__, str(DATA / "reference")])
    cfg = json.loads((DATA / "configs" / "toy-local.json").read_text())
    return harness.rehearsal_sizes(cfg, load("code-chat"))


def serve(cfg, traffic, seed, seconds=3.0):
    harness.start_jax(rehearse=True, chips=1)
    clock = harness.CompileClock()
    gw = harness.build_gateway(cfg, harness.make_params(cfg, seed))
    harness.warm_up(gw, cfg, traffic, seed)
    reqs = schedule(traffic, seed, seconds, harness.vocab(cfg))

    async def go():
        await harness.warm_stream(gw, cfg, traffic, seed)
        return await harness.serve_window(gw, reqs, seconds, clock)

    win = asyncio.run(go())
    checks = harness.served_checks(win, harness.vocab(cfg))
    del gw
    harness.free()
    return win, checks


def test_toy_family_served_through_the_harness(toy, monkeypatch):
    cfg, traffic = toy
    mcfg = harness.model_config(cfg)
    assert mcfg.pattern == ("attn_local", "attn") and mcfg.n_layers == 5
    assert mcfg.window < max(traffic["prompt"]["classes"])
    seed = 2**33 + 16
    win, checks = serve(cfg, traffic, seed)
    assert win.measured and checks == {"unfinished": 0, "stream_mismatch": 0}
    # compare every finished request, not the run's sample of eight
    monkeypatch.setattr(harness, "REF_MAX_REQUESTS", 1000)
    monkeypatch.setattr(harness, "REF_MIN_TOKENS", 10**6)
    sample = harness.sample_for_reference(win, seed)
    assert max(len(r.final) for r in sample) > 2 * mcfg.window
    # the configuration's limit on the widest gap of a served token below
    # the reference's best (sd of its logits), set between the program's
    # readings (at most 0.034 on seeds 1, 2, 3, 2**33 + 16, 4000000001)
    # and the float8 control's (at least 0.31 on the same seeds)
    limit = cfg["logit_gap_limit_sd"]
    gaps = harness.logit_gaps(cfg, seed, sample, fp8_control=True)
    assert max(g for g, _ in gaps) <= limit
    assert max(c for _, c in gaps) > limit
    # the same answers against the reference without the window (3.5 to
    # 4.5 on those seeds): the window is what the two agree on
    windowless = max(g for g, _ in harness.logit_gaps(
        dict(cfg, reference="granite_dense"), seed, sample))
    assert windowless > 10 * limit


def test_toy_flops_cap_windowed_keys_at_the_window(toy):
    cfg, _ = toy
    fam = families.of(cfg)
    per_key = 4.0 * 4 * 16              # 4 x heads x head width
    w, windowed = cfg["sliding_window"], 3     # layers 0, 2 and 4
    for keys in (1, w, 100):
        assert fam.decode_flops(cfg, keys) == granite.decode_flops(
            cfg, keys) - windowed * per_key * max(0, keys - w)
    # a 32-token prompt: 528 causal keys, 36 + 24 x 8 = 228 windowed
    assert fam.prefill_flops(cfg, 32) == granite.prefill_flops(
        cfg, 32) - windowed * per_key * (528 - 228)
    assert fam.prefill_flops(cfg, w) == granite.prefill_flops(cfg, w)


def test_toy_program_tree_follows_the_pattern(toy):
    import jax
    import weights
    from repro.models import lm
    cfg, _ = toy
    mcfg = harness.model_config(cfg)
    w = jax.eval_shape(lambda k: weights.canonical(cfg, k), weights.key(0))
    p = jax.eval_shape(lambda w: families.of(cfg).program_params(
        cfg, w, lm.padded_vocab(mcfg)), w)
    want = jax.eval_shape(lambda k: lm.init_params(mcfg, k), weights.key(0))
    assert jax.tree.structure(p) == jax.tree.structure(want)
    assert [x.shape for x in jax.tree.leaves(p)] == \
        [x.shape for x in jax.tree.leaves(want)]
    assert mcfg.window == cfg["sliding_window"] == 8
