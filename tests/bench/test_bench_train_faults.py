"""A whole run of the training cell on four virtual CPU devices at a tiny
size (in a child process, which gives the CPU four devices), with the
timed path broken underneath: ``correct`` comes out false for each fault
the cell can have and for the control in the program's place, and true
for the sound step."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def runs():
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "PYTHONPATH")}
    p = subprocess.run([sys.executable, str(HERE / "train_child.py")], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_step_is_correct(runs):
    sound = runs["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0
    # traced on the CPU: no device plane, so only the host-clock metric
    assert sound["metrics"] == ["train.mfu"]


@pytest.mark.parametrize("fault", ["stale_state", "half_batch", "no_exchange"])
def test_broken_step_is_not_correct(runs, fault):
    got = runs[fault]
    assert not got["correct"], got["checks"]


def test_control_in_the_programs_place_is_not_correct(runs):
    got = runs["fp8"]
    assert not got["correct"], got["checks"]
