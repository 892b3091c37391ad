"""A whole run, off the chip, with the timed path broken underneath.

``--rehearse`` skips the look for a chip and shrinks the cell; the rest
of the run is the benchmark's own.  A sound run reads ``correct``; the
control and every planted fault that the cell can have read not correct.
"""

import json

import pytest

from chipbench import faults, run

CASES = {
    "oneshot.kron11": [faults.control_oneshot, faults.fault_answer_oneshot],
}


def result(capsys, workload: str, seed: int) -> dict:
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "3", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", sorted(CASES))
def test_sound_run_is_correct(capsys, workload):
    out = result(capsys, workload, 2**33 + 5)
    assert out["correct"] is True
    assert out["rehearse"] is True
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w, fs in sorted(CASES.items()) for f in fs],
    ids=lambda x: getattr(x, "__name__", x))
def test_broken_path_is_not_correct(capsys, workload, fault):
    with fault():
        out = result(capsys, workload, 7)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", "oneshot.kron11", "--seed", "1",
                   "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""
