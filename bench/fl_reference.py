"""Plain reference of one federated round, and the comparison that
decides ``correct``.

The reference imports nothing of the program and runs at the precision
the configuration states.  From the round's global
weights it trains every silo with its own AdamW or SGD over the silo's
batches in order, optionally codes each silo's delta with its own int8
block quantizer (the wire format: symmetric per-block scales over the
leaves concatenated in pytree order), averages the updates weighted by
each silo's train count, and evaluates the new weights on every silo's
test split.

``compare_steps`` reduces the first local steps of every silo, and
``compare`` the round's fold and test loss, to the numbers held against
each cell's limits.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

QBLOCK = 8 * 128 * 8      # int8 scale block: elements per wire scale
GRAD_FLOOR = 1e-3         # a leaf whose first gradient is under this share
                          # of the median leaf's moves by rounding alone
FIRST_STEPS = 3           # local steps of each silo compared one by one


@jax.jit
def leaf_norms(tree: Any) -> jax.Array:
    """Each leaf's L2 norm, in float32, as one small array."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree.leaves(tree)])


@jax.jit
def change_norms(new: Any, base: Any) -> jax.Array:
    """Each leaf's L2 norm of ``new - base``, in float32."""
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, base))


def batches(data: Any, batch: int):
    """The silo's batches in order; the last one may be short."""
    x, y = data
    for i in range(0, len(y), batch):
        yield x[i:i + batch], y[i:i + batch]


def _optimizer(spec: Dict[str, Any], dtype: Any):
    lr = spec["learning_rate"]
    if spec["name"] == "sgd":
        def init(p):
            return None

        def update(g, state, p):
            return jax.tree.map(lambda w, d: (w - lr * d).astype(dtype), p, g), None
        return init, update
    if spec["name"] != "adamw":
        raise ValueError(f"unknown optimizer {spec['name']!r}")
    b1, b2, eps, wd = spec["b1"], spec["b2"], spec["eps"], spec["weight_decay"]

    def init(p):
        zeros = lambda: jax.tree.map(lambda w: jnp.zeros(w.shape, dtype), p)
        return (jnp.zeros((), jnp.int32), zeros(), zeros())

    def update(g, state, p):
        step, m, v = state
        step = step + 1
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)
        m = jax.tree.map(lambda a, d: (b1 * a + (1 - b1) * d).astype(dtype), m, g)
        v = jax.tree.map(lambda a, d: (b2 * a + (1 - b2) * d * d).astype(dtype), v, g)
        p = jax.tree.map(
            lambda w, a, b: (w - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * w)).astype(dtype),
            p, m, v)
        return p, (step, m, v)
    return init, update


MATMUL = {"default": None, "high": "high", "highest": "highest"}


class Reference:
    """One configuration's reference at one precision.

    By default it runs at the precision the configuration states
    (``cfg["precision"]``: a dtype and a matmul precision); the control
    is the same code one step below, at ``dtype=bfloat16``."""

    def __init__(self, cfg: Dict[str, Any], module: Any, dtype: Any = None,
                 precision: Optional[str] = "stated") -> None:
        stated = cfg["precision"]
        if precision == "stated":
            precision = MATMUL[stated["matmul"]]
        self.cfg, self.precision = cfg, precision
        self.dtype = jnp.dtype(stated["dtype"] if dtype is None else dtype)
        loss = lambda p, b: module.ref_loss(cfg, p, b)
        init, update = _optimizer(cfg["optimizer"], self.dtype)
        self.opt_init = init

        def step(p, state, b):
            g = jax.grad(loss)(p, b)
            return update(g, state, p)

        def eval_sum(p, b):
            return loss(p, b) * b[1].shape[0]

        self._step = jax.jit(step, donate_argnums=(0, 1))
        self._eval = jax.jit(eval_sum)
        self._grad = jax.jit(jax.grad(loss))
        self._loss = jax.jit(loss)

    def _ctx(self):
        if self.precision is None:
            return contextlib.nullcontext()
        return jax.default_matmul_precision(self.precision)

    def _cast(self, tree: Any) -> Any:
        return jax.tree.map(lambda a: jnp.asarray(a, self.dtype), tree)

    def _batch(self, b: Any) -> Any:
        x, y = b
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(self.dtype)
        return x, jnp.asarray(y)

    def local_train(self, params: Any, train: Any) -> Any:
        with self._ctx():
            p = self._cast(params)
            p = jax.tree.map(jnp.copy, p)
            state = self.opt_init(p)
            for _ in range(self.cfg["local_epochs"]):
                for b in batches(train, self.cfg["batch_size"]):
                    p, state = self._step(p, state, self._batch(b))
            return jax.tree.map(lambda a: a.astype(jnp.float32), p)

    def first_steps(self, params: Any, train: Any, n_steps: int = FIRST_STEPS) -> Dict[str, Any]:
        """The first ``n_steps`` local steps from ``params`` on the first
        batches of ``train`` (fewer where the silo has fewer batches):
        each step's loss, the per-leaf norm of the first gradient, and
        the per-leaf norm of the weights' change after the last step."""
        with self._ctx():
            base = self._cast(params)
            p = jax.tree.map(jnp.copy, base)
            state = self.opt_init(p)
            losses, grad = [], None
            for i, b in zip(range(n_steps), batches(train, self.cfg["batch_size"])):
                b = self._batch(b)
                losses.append(float(self._loss(p, b)))
                if i == 0:
                    grad = leaf_norms(self._grad(p, b))
                p, state = self._step(p, state, b)
            change = change_norms(p, jax.tree.map(jnp.asarray, params))
            return {"loss": losses, "grad": [float(v) for v in grad],
                    "change": [float(v) for v in change]}

    def eval_loss(self, params: Any, silos: Sequence[Dict[str, Any]]) -> float:
        """Sample-weighted mean test loss over all silos."""
        with self._ctx():
            p = self._cast(params)
            total, n = 0.0, 0
            for silo in silos:
                for b in batches(silo["test"], self.cfg["batch_size"]):
                    total += float(self._eval(p, self._batch(b)))
                    n += len(b[1])
            return total / n


@jax.jit
def _int8_roundtrip(delta_flat: jax.Array) -> jax.Array:
    """Quantize to int8 with one absmax/127 scale per QBLOCK, dequantize."""
    n = delta_flat.shape[0]
    pad = (-n) % QBLOCK
    blocks = jnp.pad(delta_flat, (0, pad)).reshape(-1, QBLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(blocks / safe[:, None]), -127, 127)
    q = jnp.where(scale[:, None] > 0, q, 0.0)
    return (q * scale[:, None]).reshape(-1)[:n]


def _flat(tree: Any) -> jax.Array:
    return jnp.concatenate([jnp.ravel(l) for l in jax.tree.leaves(tree)])


def _unflat(vec: jax.Array, like: Any) -> Any:
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for l in leaves:
        out.append(vec[off:off + l.size].reshape(l.shape))
        off += l.size
    return jax.tree.unflatten(treedef, out)


def silo_updates(ref: Reference, params: Any, silos: Sequence[Dict[str, Any]],
                 codec: Optional[str]) -> List[Any]:
    """Each silo's update as the server would fold it: trained weights,
    or with ``int8`` the base plus the dequantized delta."""
    out = []
    for silo in silos:
        local = ref.local_train(params, silo["train"])
        if codec == "int8":
            base = _flat(params)
            local = _unflat(base + _int8_roundtrip(_flat(local) - base), params)
        elif codec is not None:
            raise ValueError(f"unknown codec {codec!r}")
        out.append(local)
    return out


def fedavg(updates: Sequence[Any], weights: Sequence[float]) -> Any:
    total = float(sum(weights))
    acc = jax.tree.map(lambda l: l * (weights[0] / total), updates[0])
    for u, w in zip(updates[1:], weights[1:]):
        acc = jax.tree.map(lambda a, l: a + l * (w / total), acc, u)
    return acc


def host_leaves(tree: Any) -> List[np.ndarray]:
    return [np.asarray(l, np.float64) for l in jax.tree.leaves(tree)]


def compare(base: List[np.ndarray], program: List[np.ndarray], reference: List[np.ndarray],
            program_loss: float, reference_loss: float,
            grad_norms: Sequence[float]) -> Dict[str, Any]:
    """The numbers held against a cell's limits.

    ``change_gap``: over the leaves that the reference moves by gradient
    (first gradient at least GRAD_FLOOR of the median leaf's), the worst
    gap between the norm of the program's change of a leaf and the
    reference's, against the larger of that leaf's reference change and
    the median leaf's.  ``diff_rel`` is the same for the norm of the
    difference of the two changes, ``median_diff_rel`` its median leaf.
    ``cos_gap``: the worst leaf's 1 - cosine between the two changes (the
    direction, which a norm does not see), ``median_cos_gap`` its median
    leaf.  ``eval_loss_gap``: gap of the round's mean test loss, relative."""
    med_grad = float(np.median(grad_norms))
    keep = [i for i, g in enumerate(grad_norms) if g >= GRAD_FLOOR * med_grad]
    d_prog = [program[i] - base[i] for i in keep]
    d_ref = [reference[i] - base[i] for i in keep]
    n_ref = np.array([np.linalg.norm(d) for d in d_ref])
    n_prog = np.array([np.linalg.norm(d) for d in d_prog])
    n_diff = np.array([np.linalg.norm(a - b) for a, b in zip(d_prog, d_ref)])
    denom = np.maximum(n_ref, np.median(n_ref))
    dots = np.array([np.vdot(a, b) for a, b in zip(d_prog, d_ref)])
    cos_gap = 1.0 - dots / np.maximum(n_prog * n_ref, np.finfo(np.float64).tiny)
    return {
        "change_gap": float(np.max(np.abs(n_prog - n_ref) / denom)),
        "diff_rel": float(np.max(n_diff / denom)),
        "median_diff_rel": float(np.median(n_diff / denom)),
        "cos_gap": float(np.max(cos_gap)),
        "median_cos_gap": float(np.median(cos_gap)),
        "eval_loss_gap": abs(program_loss - reference_loss) / abs(reference_loss),
        "leaves_compared": len(keep),
        "leaves_left_out": len(grad_norms) - len(keep),
    }


def _norm_gap(program: Sequence[float], reference: Sequence[float], keep: Sequence[int]) -> float:
    """Worst leaf's gap between two norms, against the larger of the
    reference's norm of that leaf and of the median leaf."""
    prog = np.array([program[i] for i in keep], np.float64)
    ref = np.array([reference[i] for i in keep], np.float64)
    denom = np.maximum(np.maximum(ref, np.median(ref)), np.finfo(np.float64).tiny)
    return float(np.max(np.abs(prog - ref) / denom))


def compare_steps(program: Sequence[Dict[str, Any]],
                  reference: Sequence[Dict[str, Any]]) -> Dict[str, float]:
    """The first local steps of every silo, program against reference
    (both from ``first_steps``-shaped records, one per silo in order).

    ``step_loss_gap``: the worst step's loss gap, against the larger of
    that step's reference loss and the first step's; ``first_loss_gap``
    the same for the first step alone.  ``grad_gap``:
    the worst leaf's gap between the first gradient's norms.
    ``step_change_gap``: the same for the weights' change after the
    steps.  Leaves whose reference gradient is under GRAD_FLOOR of the
    median leaf's are left out of both, by that rule."""
    loss_gap = first_gap = grad_gap = change_gap = 0.0
    for prog, ref in zip(program, reference, strict=True):
        if len(prog["loss"]) != len(ref["loss"]):
            return {"step_loss_gap": np.inf, "first_loss_gap": np.inf, "grad_gap": np.inf,
                    "step_change_gap": np.inf}
        first = abs(ref["loss"][0])
        gaps = [abs(a - b) / max(abs(b), first) for a, b in zip(prog["loss"], ref["loss"])]
        loss_gap, first_gap = max([loss_gap] + gaps), max(first_gap, gaps[0])
        med = float(np.median(ref["grad"]))
        keep = [i for i, g in enumerate(ref["grad"]) if g >= GRAD_FLOOR * med]
        grad_gap = max(grad_gap, _norm_gap(prog["grad"], ref["grad"], keep))
        change_gap = max(change_gap, _norm_gap(prog["change"], ref["change"], keep))
    return {"step_loss_gap": loss_gap, "first_loss_gap": first_gap, "grad_gap": grad_gap,
            "step_change_gap": change_gap}


def reference_round(ref: Reference, params: Any, silos: Sequence[Dict[str, Any]],
                    codec: Optional[str]) -> Dict[str, Any]:
    """One reference round from ``params``: new weights and test loss."""
    weights = [float(len(s["train"][1])) for s in silos]
    new = fedavg(silo_updates(ref, params, silos, codec), weights)
    return {"params": new, "eval_loss": ref.eval_loss(new, silos)}
