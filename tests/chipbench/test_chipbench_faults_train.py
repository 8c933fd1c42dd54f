"""A whole training run on the CPU at a small size: sound, it is correct;
with a step that returns its state unchanged, with half of each batch left
out, or with the reference at three bf16 passes in the program's place
(the control), it is not."""
from __future__ import annotations

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import chipbench_tiny as tiny  # noqa: E402


@pytest.fixture(scope="module")
def keep():
    return {}


def test_sound_train_run_is_correct(keep):
    res = tiny.run("train", keep=keep)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["checks"]) == {"loss_err", "grad_gap", "change_gap"}


@pytest.mark.parametrize("fault", ["stale_state", "half_batch"])
def test_step_fault_is_caught(keep, fault):
    res = tiny.run("train", keep=keep, fault=fault)
    assert not res["correct"]
    if fault == "stale_state":     # no change at all: every gap reads 1
        assert res["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_control_fails_a_limit():
    res = tiny.run("train", control=True)
    assert not res["correct"]
