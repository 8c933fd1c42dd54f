"""A whole serving run on the CPU at a small size: sound, it is correct;
with an answer altered where it is produced, or with the reference at
three bf16 passes in the program's place (the control), it is not."""
from __future__ import annotations

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import chipbench_tiny as tiny  # noqa: E402


@pytest.fixture(scope="module")
def keep():
    return {}


def test_sound_server_run_is_correct(keep):
    res = tiny.run("server", keep=keep)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 40 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "serve_p99_ms"}
    assert list(res)[-1] == "checks"


def test_sound_offline_run_is_correct():
    res = tiny.run("offline")
    assert res["correct"], res["checks"]
    assert res["metrics"]["serve_images_per_s"]["value"] > 0


def test_altered_answer_is_caught(keep):
    res = tiny.run("server", keep=keep, fault="altered_answer")
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > 0.1


def test_control_fails_the_limit(keep):
    res = tiny.run("server", control=True)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > \
        3 * tiny.LIMITS["serve"]["logit_err"]
