"""A whole run at rehearsal size on the CPU, past the harness's look for a
chip, with the timed path broken underneath: ``correct`` must come out
false for each fault a served cell can have, and true without one."""

import json

import run

CELL = "granite8b-L8.code-chat"


def result(capsys, seed=20260417):
    assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "3", "--trace", "0", "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    out = result(capsys)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_token_altered_where_produced(capsys, monkeypatch):
    from repro.serve import sampling
    real = sampling.sample_rows

    def off_by_one(logits, *a, **k):           # a wrong token, every step
        return (real(logits, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(sampling, "sample_rows", off_by_one)
    out = result(capsys)
    assert not out["correct"]
    assert out["checks"]["logit_gap_sd"]["value"] > \
        out["checks"]["logit_gap_sd"]["limit"]


def test_step_returns_state_unchanged(capsys, monkeypatch):
    from repro.serve import kv_cache

    def unchanged(pool_caches, slot_caches, cfg, pt):   # decode KV dropped
        return pool_caches

    monkeypatch.setattr(kv_cache, "merge_paged", unchanged)
    out = result(capsys)
    assert not out["correct"]
