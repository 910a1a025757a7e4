"""The paper's TIL deployment: VGG16 at 224x224, four silos.

Beside ``til_vgg16.json`` (the sizes) this module gives what the harness
needs of one configuration:

* ``init_params`` / ``make_silos``: the weights and the silos' data, made
  from the seed by the benchmark, not by the program;
* ``program_parts``: the loss, evaluation and optimizer the silos'
  ``FLClient`` objects run: the system under test; and ``first_grad``,
  where the optimizer's state after one step holds the first gradient;
* ``ref_loss``: the plain float32 reference of the same model, written
  from the published VGG16 description and importing nothing of the
  program;
* ``forward_flops``: model FLOPs of one forward pass of one sample.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _conv_plan(cfg: Dict[str, Any]) -> List[Tuple[int, int, int]]:
    """(input size, c_in, c_out) of each 3x3 conv in order."""
    m = cfg["model"]
    size, c_in, plan = m["image_size"], m["channels"], []
    for c_out, n in m["stages"]:
        for _ in range(n):
            plan.append((size, c_in, c_out))
            c_in = c_out
        size //= 2
    return plan


def _fc_plan(cfg: Dict[str, Any]) -> List[Tuple[str, int, int]]:
    m = cfg["model"]
    final = m["image_size"] // 2 ** len(m["stages"])
    feat = final * final * m["stages"][-1][0]
    w = m["fc_width"]
    return [("fc0", feat, w), ("fc1", w, w), ("head", w, m["n_classes"])]


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """He-normal weights, zero biases, in the program's leaf layout."""
    convs, fcs = _conv_plan(cfg), _fc_plan(cfg)
    keys = jax.random.split(key, len(convs) + len(fcs))
    p: Dict[str, Any] = {}
    for i, (_, c_in, c_out) in enumerate(convs):
        std = math.sqrt(2.0 / (9 * c_in))
        p[f"conv{i}"] = {
            "w": jax.random.normal(keys[i], (3, 3, c_in, c_out), jnp.float32) * std,
            "b": jnp.zeros((c_out,), jnp.float32),
        }
    for j, (name, n_in, n_out) in enumerate(fcs):
        std = math.sqrt(2.0 / n_in)
        p[name] = {
            "w": jax.random.normal(keys[len(convs) + j], (n_in, n_out), jnp.float32) * std,
            "b": jnp.zeros((n_out,), jnp.float32),
        }
    return p


def make_silos(cfg: Dict[str, Any], seed: int, key: jax.Array) -> List[Dict[str, Any]]:
    """Class-conditional Gaussian images with Dirichlet label skew per
    silo, made on the device in one program per split size and held on
    the host: ``[{"train": (x, y), "test": (x, y)}, ...]``."""
    m, s = cfg["model"], cfg["silos"]
    shape = (m["image_size"], m["image_size"], m["channels"])
    rng = np.random.default_rng(seed)
    k_centers, k_noise = jax.random.split(key)
    centers = 0.5 * jax.random.normal(k_centers, (m["n_classes"],) + shape, jnp.float32)

    @jax.jit
    def images(k, labels, centers):      # centers as an argument: one program for every seed
        noise = jax.random.normal(k, labels.shape + shape, jnp.float32)
        return centers[labels] + 0.3 * noise

    silos = []
    noise_keys = jax.random.split(k_noise, 2 * len(s["train"]))
    for i, (n_tr, n_te) in enumerate(zip(s["train"], s["test"])):
        probs = rng.dirichlet(np.full(m["n_classes"], s["dirichlet_alpha"]))
        silo = {}
        for j, (split, n) in enumerate((("train", n_tr), ("test", n_te))):
            y = rng.choice(m["n_classes"], size=n, p=probs).astype(np.int32)
            x = np.asarray(images(noise_keys[2 * i + j], jnp.asarray(y), centers))
            silo[split] = (x, y)
        silos.append(silo)
    return silos


def program_parts(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The silos' loss, evaluation and optimizer, from the program."""
    from repro.models.fl_models import VGGConfig, softmax_cross_entropy, vgg16_forward
    from repro.optim import make_optimizer

    m, o = cfg["model"], dict(cfg["optimizer"])
    vcfg = VGGConfig(
        n_classes=m["n_classes"], image_size=m["image_size"],
        stages=tuple(tuple(st) for st in m["stages"]), fc_width=m["fc_width"],
    )

    def loss_fn(p, batch):
        x, y = batch
        return softmax_cross_entropy(vgg16_forward(p, x, vcfg), y)

    def eval_fn(p, batch):
        x, y = batch
        logits = vgg16_forward(p, x, vcfg)
        return {
            "loss_sum": softmax_cross_entropy(logits, y) * x.shape[0],
            "n_correct": jnp.sum(jnp.argmax(logits, axis=-1) == y),
        }

    name, lr = o.pop("name"), o.pop("learning_rate")
    if name != "adamw":
        raise ValueError(f"unsupported optimizer {name!r}")
    # After one AdamW step the first moment holds (1 - b1) x the gradient.
    first_grad = lambda state: (state.m, 1.0 / (1.0 - o["b1"]))
    return {"loss_fn": loss_fn, "eval_fn": eval_fn, "first_grad": first_grad,
            "optimizer": make_optimizer(name, lr, **o)}


# -- plain reference ---------------------------------------------------------

def ref_logits(cfg: Dict[str, Any], p: Dict[str, Any], x: jax.Array) -> jax.Array:
    """VGG16: 3x3 SAME convs with ReLU, 2x2 max-pool after each stage,
    two ReLU fully connected layers, a linear head."""
    h = x
    i = 0
    for _, n in cfg["model"]["stages"]:
        for _ in range(n):
            h = jax.lax.conv_general_dilated(
                h, p[f"conv{i}"]["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            h = jnp.maximum(h + p[f"conv{i}"]["b"], 0)
            i += 1
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    h = jnp.maximum(h @ p["fc0"]["w"] + p["fc0"]["b"], 0)
    h = jnp.maximum(h @ p["fc1"]["w"] + p["fc1"]["b"], 0)
    return h @ p["head"]["w"] + p["head"]["b"]


def ref_loss(cfg: Dict[str, Any], p: Dict[str, Any], batch: Tuple[Any, Any]) -> jax.Array:
    """Mean softmax cross-entropy over the batch."""
    x, y = batch
    logits = ref_logits(cfg, p, x.astype(p["head"]["w"].dtype))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def forward_flops(cfg: Dict[str, Any]) -> float:
    """Multiply-adds of one image's forward pass, times two."""
    conv = sum(2.0 * size * size * 9 * c_in * c_out
               for size, c_in, c_out in _conv_plan(cfg))
    fc = sum(2.0 * n_in * n_out for _, n_in, n_out in _fc_plan(cfg))
    return conv + fc
