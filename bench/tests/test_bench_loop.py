"""The training driver's loops against ``trainer.train`` bit for bit at
a tiny size: the realigning loop (refresh_ubm when due, then the
make_iter_fn program) and the loop on statistics at rest."""
import numpy as np
import pytest

from bench import data as BD
from bench import harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["train.tiny.realign", "train.tiny.at-rest"])
def test_loop_matches_trainer_train(root, cell):
    from repro.core import trainer as TR
    from repro.core import ubm as UB
    c = harness.load_cell(root, cell)
    drv = harness.driver(c)
    seed = 11
    state = drv.prepare(c, seed, 1.0, lambda m: None)
    state.loop.step()                       # iteration 4
    inp = state.inputs
    ref = TR.train(state.cfg, UB.FullGMM(inp.weights, inp.means, inp.covs),
                   state.feats, n_iters=4, key=BD.tv_key(seed),
                   mesh=(1, 1))
    got = state.loop.model
    for name in ("T", "Sigma", "prior"):
        assert np.array_equal(np.asarray(getattr(got, name)),
                              np.asarray(getattr(ref.model, name))), name
    if state.realign:
        assert np.array_equal(np.asarray(state.loop.ubm.means),
                              np.asarray(ref.ubm.means))
