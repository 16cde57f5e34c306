"""The correctness check of a llama-style model trained through sync fleet
rounds, at a size a CPU test run holds: a sound run passes; the control (the
reference one precision below the configuration's, in the program's place)
and each fault the driver plants fail it. Each run skips the harness's look
for a chip and drives the rest of a run: set-up, a short window, the
reference, the comparison. On one chip there is no exchange between chips to
leave out."""

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import run  # noqa: E402
import tiny  # noqa: E402

NAME = "tiny-llama.tiny-train"
FAULTS = run.load_module(BENCH / "drivers" / "fleet.py").FAULTS


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make_bench(tmp_path_factory.mktemp("bench"))


def test_sound_run_is_correct(bench):
    result = tiny.run_tiny(bench, NAME)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("stand_in", ["control"] + [
    ("f32", fault) for fault in FAULTS], ids=["control", *FAULTS])
def test_control_and_faults_fail(bench, stand_in):
    result = tiny.run_tiny(bench, NAME, stand_in)
    assert not result["correct"], result["checks"]
