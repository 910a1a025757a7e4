"""Server aggregation strategies.

FedAvg (McMahan et al. 2017) is the paper's method for all three
applications (§5.1): the aggregated weight is the sample-count-weighted
mean of client weights.

Dispatch hierarchy (hot paths never run the per-leaf Python loop):

  `agg_engine.AggregationEngine`   — what `FLServer` calls each round:
      one fused jitted reduce on CPU/GPU, flatten-once + Pallas
      `fedavg_reduce` + buffer donation on TPU.
  `fedavg_stacked` (below)         — traceable fused reduce over a
      replica stack, lowered inside `pod_fedavg.fl_round_step`; wraps
      `agg_engine.fused_stacked_tree_reduce`.
  `fedavg` (below)                 — the pure-jnp per-leaf oracle, kept
      ONLY as the correctness ground truth for tests.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def fedavg(client_params: Sequence[Any], weights: Sequence[float]) -> Any:
    """Weighted average of client parameter pytrees (per-leaf oracle).

    This is the slow op-by-op reference; round paths go through
    `agg_engine.AggregationEngine.aggregate` instead.
    """
    w = np.asarray(weights, np.float64)
    if w.sum() <= 0:
        raise ValueError("aggregation weights must sum to a positive value")
    w = (w / w.sum()).astype(np.float32)

    def avg(*leaves):
        acc = leaves[0].astype(jnp.float32) * w[0]
        for wi, leaf in zip(w[1:], leaves[1:]):
            acc = acc + leaf.astype(jnp.float32) * wi
        return acc.astype(leaves[0].dtype)

    return jax.tree.map(avg, *client_params)


def fedavg_stacked(stacked: Any, weights: jnp.ndarray) -> Any:
    """FedAvg over a leading client axis (used by the pod-parallel step).

    stacked: pytree whose leaves have leading dim n_clients;
    weights: (n_clients,) float32, need not be normalized.

    The whole flattened replica stack is reduced in one fused call
    ((N, L) contraction; Pallas kernel on TPU) rather than a per-leaf
    `tree.map` — see `agg_engine.fused_stacked_tree_reduce`.
    """
    from .agg_engine import fused_stacked_tree_reduce

    return fused_stacked_tree_reduce(stacked, weights)


def aggregate_metrics(
    client_metrics: Sequence[Dict[str, float]], weights: Sequence[float]
) -> Dict[str, float]:
    """Sample-weighted mean of scalar evaluation metrics."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    out: Dict[str, float] = {}
    for key in client_metrics[0]:
        out[key] = float(sum(wi * m[key] for wi, m in zip(w, client_metrics)))
    return out
