"""Jitted dispatch wrappers: one entry point per kernel that routes to the
Pallas implementation or the pure-jnp oracle.

Interpret mode is backend-detected: on a TPU runtime the same
`pl.pallas_call` lowers to Mosaic (`interpret=False`); everywhere else
(CPU/GPU containers) the kernels execute via the Pallas interpreter.
Only an explicit ``interpret=`` argument overrides the detection (tests
use it); nothing in the environment can switch a TPU run to the
interpreter.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .fedavg_reduce import fedavg_reduce as _fedavg_pallas
from .flash_attention import flash_attention as _flash_pallas
from .ssd_scan import ssd_chunk_scan as _ssd_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def fedavg_reduce(
    stacked: jnp.ndarray,
    weights: jnp.ndarray,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    if not use_pallas:
        return ref.fedavg_reduce_ref(stacked, weights)
    it = _interpret_default() if interpret is None else interpret
    return _fedavg_pallas(stacked, weights, interpret=it)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    it = _interpret_default() if interpret is None else interpret
    return _flash_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=it,
    )


def ssd_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    B_mat: jnp.ndarray,
    C_mat: jnp.ndarray,
    chunk: int = 256,
    block_h: int = 8,
    initial_state: Optional[jnp.ndarray] = None,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if not use_pallas:
        return ref.ssd_scan_ref(x, dt, A, B_mat, C_mat, chunk, initial_state)
    it = _interpret_default() if interpret is None else interpret
    return _ssd_pallas(
        x, dt, A, B_mat, C_mat, chunk=chunk, block_h=block_h,
        interpret=it, initial_state=initial_state,
    )
