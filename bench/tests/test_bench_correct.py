"""The comparison that decides ``correct`` rejects its control and the
faults a cell can have, at a tiny size on the CPU.

The control is the reference computed at HIGH precision (three bf16
passes, the step below the configurations' float32 at HIGHEST) in the
program's place; the faults are planted in the timed path underneath a
whole run (``bench/faults.py``). The tiny cells' limits sit between
what sound runs and the control read on these seeds on the CPU."""
import pytest

from bench import faults as FL
from bench import harness
from bench import reference as REF
from bench.tests import tiny

SEEDS = (3, 4, 5)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell,fault", [
    ("train.tiny.realign", "unchanged_step"),
    ("train.tiny.realign", "half_batch"),
    ("train.tiny.at-rest", "unchanged_step"),
    ("train.tiny.at-rest", "half_batch"),
    ("serve.tiny.open", "half_batch"),
    ("serve.tiny.open", "altered_answer")])
def test_a_planted_fault_makes_the_run_incorrect(root, cell, fault):
    with FL.planted(fault):
        res = tiny.run(root, cell, seconds=0.3)
    assert not res.correct


def _quiet(msg):
    pass


@pytest.mark.parametrize("cell", ["train.tiny.realign", "train.tiny.at-rest"])
def test_the_training_control_fails_and_sound_runs_pass(root, cell):
    c = harness.load_cell(root, cell)
    drv = harness.driver(c)
    limit = tiny.LIMITS["train"]["stats"]
    for seed in SEEDS:
        st = drv.prepare(c, seed, 1.0, _quiet)
        drv.release(st)
        ref = drv.reference_readings(st, REF.HIGHEST, _quiet)
        low = drv.reference_readings(st, REF.HIGH, _quiet)
        assert drv.numbers(st.prog, ref, _quiet)["stats"] <= limit
        assert drv.numbers(low, ref, _quiet)["stats"] > limit


def test_the_serving_control_fails_and_sound_runs_pass(root):
    c = harness.load_cell(root, "serve.tiny.open")
    drv = harness.driver(c)
    limit = tiny.LIMITS["serve"]["ivector_gap"]
    for seed in SEEDS:
        st = drv.prepare(c, seed, 0.5, _quiet)
        drv.window(st, 0.5, _quiet)
        drv.release(st)
        idx = drv.sample(st)
        ref = drv.reference_ivectors(st, idx, REF.HIGHEST)
        low = drv.reference_ivectors(st, idx, REF.HIGH)
        assert drv.numbers(st, idx, ref)["ivector_gap"] <= limit
        st.ivecs = {int(i): v for i, v in zip(idx, low)}
        assert drv.numbers(st, idx, ref)["ivector_gap"] > limit
