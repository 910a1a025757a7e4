"""Pallas TPU kernel: weighted FedAvg reduction over stacked client
parameters.

The server's aggregation step reduces N client parameter vectors (the
flattened model, possibly GBs) into one weighted average. On TPU this is a
pure memory-bound streaming reduce: HBM -> VMEM tiles of every client's
shard, fp32 multiply-accumulate in VREGs, one output tile written back.

Tiling: the flattened parameter vector is viewed as (n_clients, L) and cut
into (n_clients, BLOCK) VMEM tiles — BLOCK = 8*128*8 floats keeps the tile
MXU/VPU-aligned (last dim a multiple of 128) and the working set
(n_clients+1) * BLOCK * 4 B comfortably inside VMEM for cross-silo client
counts (N <= ~64).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 8 * 128 * 8  # 8192 elements per tile


def _fedavg_kernel(w_ref, x_ref, o_ref):
    """w: (N, 1) fp32; x: (N, BLOCK); o: (1, BLOCK)."""
    x = x_ref[...].astype(jnp.float32)          # (N, BLOCK)
    w = w_ref[...]                               # (N, 1) fp32
    acc = jnp.sum(x * w, axis=0, keepdims=True)  # (1, BLOCK) fp32
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fedavg_reduce(
    stacked: jnp.ndarray,   # (N, L) — flattened client params
    weights: jnp.ndarray,   # (N,) — unnormalized sample counts
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Weighted average over axis 0. Returns (L,) in stacked.dtype.

    ``interpret=None`` auto-detects: compiled Mosaic on TPU, Pallas
    interpreter elsewhere. Pass an explicit bool to override (tests).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, L = stacked.shape
    w = (weights / jnp.sum(weights)).astype(jnp.float32).reshape(n, 1)

    pad = (-L) % BLOCK
    x = jnp.pad(stacked, ((0, 0), (0, pad))) if pad else stacked
    Lp = L + pad
    grid = (Lp // BLOCK,)

    out = pl.pallas_call(
        _fedavg_kernel,
        out_shape=jax.ShapeDtypeStruct((1, Lp), stacked.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),       # weights: replicated
            pl.BlockSpec((n, BLOCK), lambda i: (0, i)),   # client tile
        ],
        out_specs=pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
        interpret=interpret,
    )(w, x)
    return out[0, :L]


# Quantization blocks folded per grid step.  Each block is one row of the
# (nb, BLOCK) view, so a step moves an (ROWS, BLOCK) tile: 32 rows is the
# int8 sublane tile (32, 128) and a multiple of fp16's (16, 128), and the
# double-buffered working set (int8 data + fp32 acc in + fp32 out, about
# 2.3 MiB per buffer) stays well inside scoped VMEM.
ROWS = 32


def _half_bits_to_f32(h: jnp.ndarray) -> jnp.ndarray:
    """Exact IEEE fp16 -> fp32 decode of uint16 bit patterns.

    Mosaic cannot load float16 vectors, so fp16 updates enter the kernel
    as their uint16 bits and are widened here with integer ops: normals,
    infinities and NaNs by re-biasing the exponent, subnormals (which
    would flush to zero as fp32 denormals on the VPU) as
    ``mantissa * 2**-24``.  Bit-for-bit equal to ``astype(float32)``."""
    h = h.astype(jnp.int32)
    sign = (h >> 15) << 31
    exp = (h >> 10) & 0x1F
    man = h & 0x3FF
    exp32 = jnp.where(exp == 0x1F, 0xFF, exp + (127 - 15))
    normal = jax.lax.bitcast_convert_type(
        sign | (exp32 << 23) | (man << 13), jnp.float32
    )
    sub = man.astype(jnp.float32) * (2.0 ** -24)
    sub = jnp.where(sign != 0, -sub, sub)
    return jnp.where(exp == 0, sub, normal)


def _dequant_fold_kernel(s_ref, a_ref, x_ref, o_ref):
    """s: (R, 1) weighted per-block scales; a/x/o: (R, BLOCK).

    One fused pass: dequantize each row (``x * scale``), weight it, and
    add it onto the fp32 accumulator rows — the quantized bytes are read
    once and no dense fp32 copy of the update is ever materialized."""
    raw = x_ref[...]
    if raw.dtype == jnp.uint16:  # fp16 payload, passed as its bits
        x = _half_bits_to_f32(raw)
    else:
        x = raw.astype(jnp.float32)
    o_ref[...] = a_ref[...] + s_ref[...] * x


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def dequant_fold(
    acc: jnp.ndarray,       # (Lp,) fp32 accumulator, Lp % BLOCK == 0
    data: jnp.ndarray,      # (Lp,) quantized update (int8 or fp16)
    scales: jnp.ndarray,    # (Lp // BLOCK,) per-block dequant scales
    weight: jnp.ndarray,    # scalar fold weight
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused dequantize-and-fold: ``acc + weight * (data * scales)``.

    Quantization blocks are exactly the rows of the ``(Lp // BLOCK,
    BLOCK)`` view (one wire scale per row); the grid walks it ``ROWS``
    rows at a time, with the weighted scales as an ``(ROWS, 1)`` column
    block, so each int8/fp16 tile is dequantized in VREGs and
    accumulated in a single HBM pass.  A row count that is not a
    multiple of ``ROWS`` leaves a partial last block, whose
    out-of-range rows are never written back.  The accumulator is
    donated and aliased to the output (updated in place, O(L) memory
    for the whole round).  fp16 updates reuse the same kernel with unit
    scales.  Like ``fedavg_reduce``: compiled Mosaic on TPU, interpreter
    elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    Lp = acc.shape[0]
    if Lp % BLOCK:
        raise ValueError(f"accumulator length {Lp} not a multiple of BLOCK={BLOCK}")
    nb = Lp // BLOCK
    # Fewer rows than one tile: the block spans the whole (nb, BLOCK)
    # array, which Mosaic accepts at any row count.
    rows = ROWS if nb > ROWS else nb
    if data.dtype == jnp.float16:
        data = jax.lax.bitcast_convert_type(data, jnp.uint16)
    a2 = acc.reshape(nb, BLOCK)
    x2 = data.reshape(nb, BLOCK)
    w = jnp.asarray(weight, jnp.float32)
    ws = (w * scales.astype(jnp.float32)).reshape(nb, 1)

    out = pl.pallas_call(
        _dequant_fold_kernel,
        out_shape=jax.ShapeDtypeStruct((nb, BLOCK), jnp.float32),
        grid=(pl.cdiv(nb, rows),),
        in_specs=[
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),       # weighted scales
            pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),   # accumulator rows
            pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),   # quantized rows
        ],
        out_specs=pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
        input_output_aliases={1: 0},  # accumulator updated in place
        interpret=interpret,
    )(ws, a2, x2)
    return out.reshape(Lp)
