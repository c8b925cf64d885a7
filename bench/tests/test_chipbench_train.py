"""The training cell's ``correct`` on the CPU, at a tiny size: a sound run
passes, and the float8 control and each fault the cell can have (a step
that returns its state unchanged; half of the batch left out) fail.

These drive the whole run with the harness's look for a chip skipped."""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from bench import harness  # noqa: E402

CELL = "tiny-ssd.train"
SEED = 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_sound_run_is_correct_and_the_control_is_not(root):
    line, out = tiny.drive(root, CELL, SEED, 1.0, control=True)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    limits = harness.resolve(harness.load_spec(root), CELL, root=root).limits
    correct, shown = harness.judge(out.control, limits)
    assert not correct, shown


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(root, fault):
    line, _ = tiny.drive(root, CELL, SEED, 0.5, faults={fault})
    assert not line["correct"], line["checks"]
