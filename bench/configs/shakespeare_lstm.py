"""The paper's Shakespeare deployment: LEAF's character LSTM, eight silos.

Beside ``shakespeare_lstm.json`` (the sizes) this module gives what the
harness needs of one configuration: weights and data made from the seed
by the benchmark, the program's loss, evaluation and optimizer that the
silos' ``FLClient`` objects run, the plain float32 reference of the model
(importing nothing of the program), and the model FLOPs of one sample.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BRANCHING = 4  # successors per character in the synthetic stream


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """The program's leaf layout: embedding, LSTM layers, linear head."""
    m = cfg["model"]
    v, e, h, n = m["vocab_size"], m["embed_dim"], m["hidden"], m["n_layers"]
    keys = jax.random.split(key, 2 * n + 2)
    p: Dict[str, Any] = {
        "embed": 0.1 * jax.random.normal(keys[0], (v, e), jnp.float32),
    }
    n_in = e
    for i in range(n):
        s = 1.0 / math.sqrt(h)
        p[f"lstm{i}"] = {
            "wx": s * jax.random.normal(keys[1 + 2 * i], (n_in, 4 * h), jnp.float32),
            "wh": s * jax.random.normal(keys[2 + 2 * i], (h, 4 * h), jnp.float32),
            "b": jnp.zeros((4 * h,), jnp.float32),
        }
        n_in = h
    p["head"] = {
        "w": math.sqrt(2.0 / h) * jax.random.normal(keys[-1], (h, v), jnp.float32),
        "b": jnp.zeros((v,), jnp.float32),
    }
    return p


def make_silos(cfg: Dict[str, Any], seed: int, key: jax.Array) -> List[Dict[str, Any]]:
    """Next-character streams: one shared successor table, and for each
    silo its own start distribution and successor preferences (non-IID).
    ``[{"train": (tokens, labels), "test": (tokens, labels)}, ...]``."""
    del key  # small enough to make on the host
    m, s = cfg["model"], cfg["silos"]
    v, length = m["vocab_size"], m["seq_len"]
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, v, size=(v, BRANCHING))
    silos = []
    for n_tr, n_te in zip(s["train"], s["test"]):
        start = rng.dirichlet(np.full(v, 0.5))
        pref = rng.dirichlet(np.full(BRANCHING, 1.0))
        silo = {}
        for split, n in (("train", n_tr), ("test", n_te)):
            toks = np.empty((n, length + 1), np.int32)
            toks[:, 0] = rng.choice(v, size=n, p=start)
            picks = rng.choice(BRANCHING, size=(n, length), p=pref)
            for t in range(length):
                toks[:, t + 1] = succ[toks[:, t], picks[:, t]]
            silo[split] = (np.ascontiguousarray(toks[:, :-1]),
                           np.ascontiguousarray(toks[:, 1:]))
        silos.append(silo)
    return silos


def program_parts(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The silos' loss, evaluation and optimizer, from the program."""
    from repro.models.fl_models import LSTMConfig, shakespeare_loss
    from repro.optim import make_optimizer

    m, o = cfg["model"], cfg["optimizer"]
    lcfg = LSTMConfig(vocab_size=m["vocab_size"], embed_dim=m["embed_dim"],
                      hidden=m["hidden"], n_layers=m["n_layers"])

    def loss_fn(p, batch):
        toks, labels = batch
        return shakespeare_loss(p, toks, labels, lcfg)

    def eval_fn(p, batch):
        toks, labels = batch
        return {"loss_sum": shakespeare_loss(p, toks, labels, lcfg) * toks.shape[0]}

    if o["name"] != "sgd":
        raise ValueError(f"unsupported optimizer {o['name']!r}")
    # After one step the momentum buffer holds the gradient itself.
    first_grad = lambda state: (state.momentum, 1.0)
    return {"loss_fn": loss_fn, "eval_fn": eval_fn, "first_grad": first_grad,
            "optimizer": make_optimizer("sgdm", o["learning_rate"], momentum=0.0)}


# -- plain reference ---------------------------------------------------------

def ref_logits(cfg: Dict[str, Any], p: Dict[str, Any], toks: jax.Array) -> jax.Array:
    """Embedding, stacked LSTM layers (gates i, f, g, o), linear head."""
    h_dim = cfg["model"]["hidden"]
    x = p["embed"][toks]                                  # (B, S, E)
    for i in range(cfg["model"]["n_layers"]):
        layer = p[f"lstm{i}"]
        zero = jnp.zeros((x.shape[0], h_dim), x.dtype)

        def cell(carry, xt, layer=layer):
            h, c = carry
            z = xt @ layer["wx"] + h @ layer["wh"] + layer["b"]
            i_g, f_g, g_g, o_g = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f_g) * c + jax.nn.sigmoid(i_g) * jnp.tanh(g_g)
            h = jax.nn.sigmoid(o_g) * jnp.tanh(c)
            return (h, c), h

        _, hs = jax.lax.scan(cell, (zero, zero), jnp.swapaxes(x, 0, 1))
        x = jnp.swapaxes(hs, 0, 1)
    return x @ p["head"]["w"] + p["head"]["b"]


def ref_loss(cfg: Dict[str, Any], p: Dict[str, Any], batch: Tuple[Any, Any]) -> jax.Array:
    """Mean next-character cross-entropy over every position."""
    toks, labels = batch
    logp = jax.nn.log_softmax(ref_logits(cfg, p, toks), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def forward_flops(cfg: Dict[str, Any]) -> float:
    """Multiply-adds of one sequence's forward pass, times two."""
    m = cfg["model"]
    h, per_pos, n_in = m["hidden"], 0.0, m["embed_dim"]
    for _ in range(m["n_layers"]):
        per_pos += 2.0 * (n_in + h) * 4 * h
        n_in = h
    per_pos += 2.0 * h * m["vocab_size"]
    return per_pos * m["seq_len"]
