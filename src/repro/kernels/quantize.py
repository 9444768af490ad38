"""Pallas TPU per-block symmetric int8 quantize / dequantize.

Used by (a) WAN-aware checkpoint compression — the paper's §VIII feasible-
envelope expansion — and (b) cross-pod int8 gradient all-reduce. The op is
bandwidth-bound, so the kernel is a straight VMEM-tiled elementwise pass:
each grid step loads a (ROWS, BLOCK) tile, computes the per-row absmax scale
on the VPU, and writes int8 + scales without re-reading HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 256  # quantization group (lane-aligned: 2x128)
ROWS = 64  # rows per grid step


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # (ROWS, BLOCK)
    amax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale[:, None]


def _dequant_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    x_ref[...] = q * s_ref[...]  # s is (ROWS, 1), broadcasts over lanes


def _row_tiles(rows: int):
    """``(grid_rows, padded_rows)``: a tile of ROWS rows (a multiple of
    8, legal for the int8/f32 TPU tilings) over rows padded up to whole
    tiles, or one whole-array tile when there are at most ROWS rows."""
    grid_rows = min(ROWS, rows)
    return grid_rows, -(-rows // grid_rows) * grid_rows


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantize_int8_pallas(x: jax.Array, *, block: int = BLOCK, interpret: bool = False):
    """x: flat (n,) with n % block == 0 -> (q int8 (n,), scales (n/block,)).
    Rows beyond the last whole tile are zero-padded (scale 1, q 0) and
    sliced off."""
    n = x.shape[0]
    rows = n // block
    grid_rows, rows_pad = _row_tiles(rows)
    x2 = jnp.pad(x.reshape(rows, block), ((0, rows_pad - rows), (0, 0)))
    q2, s = pl.pallas_call(
        _quant_kernel,
        grid=(rows_pad // grid_rows,),
        in_specs=[pl.BlockSpec((grid_rows, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((grid_rows, block), lambda i: (i, 0)),
            pl.BlockSpec((grid_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, block), jnp.int8),
            jax.ShapeDtypeStruct((rows_pad, 1), jnp.float32),
        ],
        interpret=interpret,
        name="_quant_kernel",
    )(x2)
    return q2[:rows].reshape(n), s[:rows].reshape(rows)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dequantize_int8_pallas(q: jax.Array, scale: jax.Array, *, block: int = BLOCK, interpret: bool = False):
    n = q.shape[0]
    rows = n // block
    grid_rows, rows_pad = _row_tiles(rows)
    pad = ((0, rows_pad - rows), (0, 0))
    x2 = pl.pallas_call(
        _dequant_kernel,
        grid=(rows_pad // grid_rows,),
        in_specs=[
            pl.BlockSpec((grid_rows, block), lambda i: (i, 0)),
            pl.BlockSpec((grid_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((grid_rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, block), jnp.float32),
        interpret=interpret,
        name="_dequant_kernel",
    )(jnp.pad(q.reshape(rows, block), pad),
      jnp.pad(scale.reshape(rows, 1), pad, constant_values=1.0))
    return x2[:rows].reshape(n)
