"""Pallas TPU kernels for the framework's compute hot spots:

  fedavg_reduce   — the server aggregation reduce (the paper's per-round
                    hot spot at cross-silo model sizes);
  flash_attention — causal GQA attention w/ sliding window (client-side
                    training/prefill compute for the attention archs);
  ssd_scan        — Mamba-2 SSD intra-chunk scan (SSM / hybrid archs).

Dispatch hierarchy: ops.py is the entry point — it routes each call to
the Pallas implementation or the pure-jnp oracle in ref.py, and resolves
interpret mode by backend detection (`jax.default_backend() != "tpu"`),
overridable only by an explicit ``interpret=`` argument.
The federated aggregation engine (`repro.federated.agg_engine`) sits one
layer above: it feeds `fedavg_reduce` a flatten-once (N, L) client
buffer on TPU (donated, so HBM is reused) and a fused jnp contraction
elsewhere.
"""
from .ops import fedavg_reduce, flash_attention, ssd_scan

__all__ = ["fedavg_reduce", "flash_attention", "ssd_scan"]
