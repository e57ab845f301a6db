"""The harness end to end on the CPU at a tiny size, chip check skipped:
a sound run is correct, the float8 control is not, and a timed path
broken underneath comes out not correct, once for each fault a cell can
have.  The tiny configuration runs in float32, where the program and the
reference agree to rounding, so the committed limits separate them."""
import pytest

from conftest import tiny_cell

from bench.harness import checks as C
from bench.harness import faults as F

TRAIN = "tinyllava-train-split2b"
SERVE = ["tinyllava-serve-split2b-steady",
         "llava-next-34b-serve-split2b-offline"]


def test_train_sound_run_correct_and_control_not(run_tiny):
    res = run_tiny(tiny_cell(TRAIN), control=True)
    assert res["correct"], res["checks"]
    assert not C.passed(res["control"]), res["control"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", SERVE)
def test_serve_sound_run_correct_and_control_not(run_tiny, workload):
    res = run_tiny(tiny_cell(workload), control=True)
    assert res["correct"], res["checks"]
    assert not C.passed(res["control"]), res["control"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_faults_are_not_correct(run_tiny, monkeypatch, fault):
    F.FAULTS[fault](monkeypatch.setattr)
    assert not run_tiny(tiny_cell(TRAIN))["correct"]


def test_serve_altered_token_is_not_correct(run_tiny, monkeypatch):
    F.altered_token(monkeypatch.setattr)
    assert not run_tiny(tiny_cell(SERVE[0]))["correct"]


def test_serve_tick_that_keeps_its_state_is_not_correct(run_tiny,
                                                        monkeypatch):
    F.stale_tick(monkeypatch.setattr)
    assert not run_tiny(tiny_cell(SERVE[0]))["correct"]
