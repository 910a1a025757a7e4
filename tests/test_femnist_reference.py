"""The program's FEMNIST CNN against the benchmark's plain reference
(``bench/configs/femnist_cnn.py``, which imports nothing of the program),
at tiny widths on seeded random weights on the CPU: the forward pass and
its loss, and one ``FLClient.train`` round, whose step donates, against
the reference's own local SGD steps, leaf by leaf."""
import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402
from bench import fl_reference as flr  # noqa: E402
from repro.federated import FLClient  # noqa: E402
from repro.models.fl_models import FemnistConfig, femnist_forward, init_femnist_cnn  # noqa: E402


def _config(n_fc=3, fc_width=48, train=(37,), test=(9,)):
    cfg, module = common.config("femnist_cnn")
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(n_fc=n_fc, fc_width=fc_width)
    cfg["silos"].update(train=list(train), test=list(test))
    return cfg, module


def _silo(cfg, module, seed=2**31 + 3):
    return module.make_silos(cfg, seed, common.seed_key(seed))[0]


def test_published_widths():
    cfg, module = common.config("femnist_cnn")
    assert module.n_params(cfg) == cfg["params"] == 164_187_070
    assert 4 * module.n_params(cfg) == pytest.approx(656.7e6, rel=1e-4)
    shapes = jax.eval_shape(lambda k: module.init_params(cfg, k), jax.random.PRNGKey(0))
    program = jax.eval_shape(lambda k: init_femnist_cnn(k, FemnistConfig()), jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(lambda a: a.shape, program)


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_forward_and_loss_match_the_reference(seed):
    cfg, module = _config()
    m = cfg["model"]
    fcfg = FemnistConfig(n_classes=m["n_classes"], image_size=m["image_size"],
                         n_fc=m["n_fc"], fc_width=m["fc_width"])
    p = module.init_params(cfg, common.seed_key(seed))
    x, y = _silo(cfg, module)["train"]
    x, y = jnp.asarray(x[:10]), jnp.asarray(y[:10])
    ref = module.ref_logits(cfg, p, x)
    # float32 on the CPU, where the two agree to the bit today; the room
    # is for XLA reassociating a sum, a few ulps of logits of size ~10.
    np.testing.assert_allclose(femnist_forward(p, x, fcfg), ref, rtol=1e-5, atol=1e-5)
    loss = module.program_parts(cfg)["loss_fn"](p, (x, y))
    np.testing.assert_allclose(loss, module.ref_loss(cfg, p, (x, y)), rtol=1e-6)


class _Silo:
    def __init__(self, client_id, train):
        self.client_id = client_id
        self.train = train

    def batches(self, batch, split="train"):
        return flr.batches(self.train, batch)


def test_a_train_round_matches_the_reference_steps():
    """Two epochs of 4 batches (the last one short): 8 steps, 7 of them
    donating, against the reference's own SGD on the same batches."""
    cfg, module = _config()
    cfg["local_epochs"] = 2
    p0 = module.init_params(cfg, common.seed_key(2**31 + 5))
    train = _silo(cfg, module)["train"]
    parts = module.program_parts(cfg)
    client = FLClient("c0", _Silo("c0", train), parts["loss_fn"], parts["optimizer"],
                      batch_size=cfg["batch_size"], local_epochs=cfg["local_epochs"])
    received = jax.tree.map(jnp.asarray, p0)
    result = client.train(received)
    assert result.n_samples == len(train[1])
    expect = flr.Reference(cfg, module).local_train(p0, train)
    prog, ref = flr.host_leaves(result.params), flr.host_leaves(expect)
    base = flr.host_leaves(p0)
    for a, b, w in zip(prog, ref, base):
        # Eight float32 steps of lr 0.01 agree to the bit on the CPU today.
        # Two separately compiled programs may still round a sum apart:
        # an ulp of a weight (~1e-8) is ~1e-5 of a leaf's change (~1e-3),
        # so 1e-4 of the change is a few ulps, far below one step.
        scale = max(np.abs(b - w).max(), 1e-12)
        assert np.abs(a - b).max() <= 1e-4 * scale, np.abs(a - b).max() / scale
    # The round moved every leaf.
    assert all(np.abs(b - w).max() > 0 for b, w in zip(ref, base))
