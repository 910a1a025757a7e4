"""The benchmark's FLOP and byte functions against XLA's cost analysis,
at small shapes on the CPU."""
import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import common, costs  # noqa: E402


def _cost(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    return ca[0] if isinstance(ca, list) else ca


def test_vgg16_forward_flops():
    cfg, mod = common.config("til_vgg16")
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(image_size=32, stages=[[8, 2], [16, 1]], fc_width=64)
    p = mod.init_params(cfg, jax.random.PRNGKey(0))
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    counted = _cost(lambda p, x: mod.ref_logits(cfg, p, x), p, x)["flops"]
    # XLA leaves out the taps that fall on SAME padding (4% at 32x32, 0.3%
    # at 224x224) and adds bias adds, ReLUs and max-pools.
    assert mod.forward_flops(cfg) == pytest.approx(counted, rel=0.05)


def test_vgg16_published_flops():
    cfg, mod = common.config("til_vgg16")
    # VGG16 at 224x224: 15.47 G multiply-adds (Simonyan & Zisserman).
    assert mod.forward_flops(cfg) == pytest.approx(2 * 15.47e9, rel=0.01)


def test_lstm_forward_flops():
    cfg, mod = common.config("shakespeare_lstm")
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(hidden=32, seq_len=6)
    m = cfg["model"]
    p = mod.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((1, m["seq_len"]), jnp.int32)

    def unrolled(p, toks):
        # XLA counts a scan's body once, so count an unrolled copy.
        x = p["embed"][toks]
        for i in range(m["n_layers"]):
            layer = p[f"lstm{i}"]
            h = c = jnp.zeros((1, m["hidden"]))
            outs = []
            for t in range(m["seq_len"]):
                z = x[:, t] @ layer["wx"] + h @ layer["wh"] + layer["b"]
                i_g, f_g, g_g, o_g = jnp.split(z, 4, axis=-1)
                c = jax.nn.sigmoid(f_g) * c + jax.nn.sigmoid(i_g) * jnp.tanh(g_g)
                h = jax.nn.sigmoid(o_g) * jnp.tanh(c)
                outs.append(h)
            x = jnp.stack(outs, axis=1)
        return x @ p["head"]["w"] + p["head"]["b"]

    counted = _cost(unrolled, p, toks)["flops"]
    assert mod.forward_flops(cfg) == pytest.approx(counted, rel=0.1)
    assert np.allclose(unrolled(p, toks), mod.ref_logits(cfg, p, toks), atol=1e-5)


def test_dense_fold_bytes():
    from repro.federated.agg_engine import _accum_tree_impl, _scale_acc_impl, _scale_tree_impl

    tree = {"a": jnp.zeros((64, 32)), "b": jnp.zeros((100,))}
    n = 64 * 32 + 100
    w = jnp.float32(2.0)
    counted = (_cost(_scale_tree_impl, tree, w)["bytes accessed"]
               + 2 * _cost(_accum_tree_impl, tree, tree, w)["bytes accessed"]
               + _cost(_scale_acc_impl, tree, w)["bytes accessed"])
    _, nbytes = costs.dense_fold(n, 3)
    # XLA also counts the 4-byte weight scalar, read once per leaf.
    assert nbytes == pytest.approx(counted, rel=0.005)


def test_dequant_fold_bytes():
    from repro.federated.agg_engine import _flat_dequant_fold_jnp_impl

    padded, nb = 4 * 8192, 4
    acc = jnp.zeros(padded, jnp.float32)
    data = jnp.zeros(padded, jnp.int8)
    scales = jnp.ones(nb, jnp.float32)
    counted = _cost(_flat_dequant_fold_jnp_impl, acc, data, scales, jnp.float32(1.0))
    _, nbytes = costs.dequant_fold(padded, nb)
    assert nbytes == pytest.approx(counted["bytes accessed"], abs=8)
