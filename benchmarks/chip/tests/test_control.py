"""The control at a size a test run holds: the float32 reference computed
in float8 (the step below the configuration's bfloat16) must read far
worse than the program on the same positions, in each cell."""

import json

import pytest

import control


@pytest.mark.parametrize("cell", ["granite8b-L8.code-chat"])
def test_control_reads_far_above_the_program(cell, capsys, monkeypatch):
    # answers at this size are a few tokens long: compare every finished
    # request of the window, not the run's six
    monkeypatch.setattr(control.harness, "REF_MAX_REQUESTS", 1000)
    monkeypatch.setattr(control.harness, "REF_MIN_TOKENS", 10**6)
    assert control.main(["--workload", cell, "--seeds", "5,6,7",
                         "--control", "3", "--seconds", "4",
                         "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert all(r["unfinished"] == 0 and r["stream_mismatch"] == 0
               for r in out["rows"])
    assert out["upper"] > 3 * out["lower"], out
