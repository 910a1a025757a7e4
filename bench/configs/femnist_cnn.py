"""The paper's FEMNIST deployment: a CNN of two 5x5 convolutions and ten
4096-wide fully connected layers, five silos.

Beside ``femnist_cnn.json`` (the sizes) this module gives what the harness
needs of one configuration: weights and data made from the seed by the
benchmark, the program's loss, evaluation and optimizer that the silos'
``FLClient`` objects run, the plain float32 reference of the model
(importing nothing of the program), the model FLOPs of one sample, and
the least HBM bytes of one local step.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

F32 = 4


def _layers(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """(leaf group, weight shape) of every layer in order."""
    m = cfg["model"]
    out, c_in = [], m["channels"]
    for i, (k, c_out) in enumerate(m["convs"]):
        out.append((f"conv{i + 1}", (k, k, c_in, c_out)))
        c_in = c_out
    side = m["image_size"] // 2 ** len(m["convs"])
    n_in = side * side * c_in
    for i in range(m["n_fc"]):
        out.append((f"fc{i}", (n_in, m["fc_width"])))
        n_in = m["fc_width"]
    out.append(("head", (n_in, m["n_classes"])))
    return out


def n_params(cfg: Dict[str, Any]) -> int:
    return sum(math.prod(shape) + shape[-1] for _, shape in _layers(cfg))


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """He-normal weights, zero biases, in the program's leaf layout."""
    layers = _layers(cfg)
    keys = jax.random.split(key, len(layers))
    return {
        name: {"w": jax.random.normal(k, shape, jnp.float32)
               * math.sqrt(2.0 / math.prod(shape[:-1])),
               "b": jnp.zeros((shape[-1],), jnp.float32)}
        for k, (name, shape) in zip(keys, layers)
    }


def make_silos(cfg: Dict[str, Any], seed: int, key: jax.Array) -> List[Dict[str, Any]]:
    """The TIL configuration's class-conditional Gaussian images with
    Dirichlet label skew, at 28x28x1 over 62 classes."""
    from bench import common

    return common.config("til_vgg16")[1].make_silos(cfg, seed, key)


def program_parts(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The silos' loss, evaluation and optimizer, from the program."""
    from repro.models.fl_models import FemnistConfig, femnist_forward, softmax_cross_entropy
    from repro.optim import make_optimizer

    m, o = cfg["model"], cfg["optimizer"]
    if [list(c) for c in m["convs"]] != [[5, 32], [5, 64]]:
        raise ValueError(f"the program's FEMNIST CNN has convs 5x5x32, 5x5x64, not {m['convs']}")
    fcfg = FemnistConfig(n_classes=m["n_classes"], image_size=m["image_size"],
                         n_fc=m["n_fc"], fc_width=m["fc_width"])

    def loss_fn(p, batch):
        x, y = batch
        return softmax_cross_entropy(femnist_forward(p, x, fcfg), y)

    def eval_fn(p, batch):
        x, y = batch
        logits = femnist_forward(p, x, fcfg)
        return {
            "loss_sum": softmax_cross_entropy(logits, y) * x.shape[0],
            "n_correct": jnp.sum(jnp.argmax(logits, axis=-1) == y),
        }

    if o["name"] != "sgd":
        raise ValueError(f"unsupported optimizer {o['name']!r}")
    # After one step the momentum buffer holds the gradient itself.
    first_grad = lambda state: (state.momentum, 1.0)
    return {"loss_fn": loss_fn, "eval_fn": eval_fn, "first_grad": first_grad,
            "optimizer": make_optimizer("sgdm", o["learning_rate"], momentum=0.0)}


# -- plain reference ---------------------------------------------------------

def ref_logits(cfg: Dict[str, Any], p: Dict[str, Any], x: jax.Array) -> jax.Array:
    """LEAF's FEMNIST CNN as Multi-FedLS widens it: each convolution 5x5
    SAME with ReLU and a 2x2 max-pool, then ReLU fully connected layers
    and a linear head."""
    m = cfg["model"]
    h = x
    for i in range(len(m["convs"])):
        layer = p[f"conv{i + 1}"]
        h = jax.lax.conv_general_dilated(h, layer["w"], (1, 1), "SAME",
                                         dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jnp.maximum(h + layer["b"], 0)
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    for i in range(m["n_fc"]):
        h = jnp.maximum(h @ p[f"fc{i}"]["w"] + p[f"fc{i}"]["b"], 0)
    return h @ p["head"]["w"] + p["head"]["b"]


def ref_loss(cfg: Dict[str, Any], p: Dict[str, Any], batch: Tuple[Any, Any]) -> jax.Array:
    """Mean softmax cross-entropy over the batch."""
    x, y = batch
    logits = ref_logits(cfg, p, x.astype(p["head"]["w"].dtype))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def forward_flops(cfg: Dict[str, Any]) -> float:
    """Multiply-adds of one image's forward pass, times two."""
    m = cfg["model"]
    side, flops = m["image_size"], 0.0
    for _, (kh, kw, c_in, c_out) in _layers(cfg)[:len(m["convs"])]:
        flops += 2.0 * side * side * kh * kw * c_in * c_out
        side //= 2
    return flops + sum(2.0 * n_in * n_out for _, (n_in, n_out) in _layers(cfg)[len(m["convs"]):])


def step_bytes(cfg: Dict[str, Any]) -> float:
    """The least HBM bytes one local SGD step moves: the weights read by
    the forward and by the backward pass, the new weights and the new
    optimizer buffer written.  Gradients, which XLA may keep out of HBM,
    and activations are not counted."""
    return 4.0 * F32 * n_params(cfg)
