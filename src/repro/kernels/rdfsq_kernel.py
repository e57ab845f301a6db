"""Pallas TPU kernel: fused RD-FSQ quantize+pack / unpack+dequantize.

The compressor sits serially on the split-learning wire (it runs on every
microbatch before the cross-pod transfer), so its latency adds directly to
the communication-critical path.  The fused kernel makes it a single
streaming VMEM pass: read a (ROWS x COLS) tile of boundary activations,
clip -> linear-scale -> round -> shift-or-pack 2/4-bit codes into uint8
words, write the packed tile.  HBM traffic is 1 read of x + 1 write of
x * bits/16 — the naive jnp path materializes the intermediate codes at
8 bits plus separate pack ops.

TPU notes: COLS=1024 keeps the lane dim a multiple of 128 both before
(1024) and after packing (1024 * bits / 8 >= 128 for bits >= 1); the
(ROWS x COLS) fp32 tile + packed output + the (COLS x ROWS) int32 packing
scratch stay far under the ~16 MiB VMEM budget, leaving room for double
buffering.  Codes are int32 until the final uint8 store (Mosaic has no
float <-> uint8 cast) and are packed along sublanes (``lane_pack``).  The
MXU is not involved — this is a VPU kernel; the per-(row)-scalar
(lo, hi) side inputs ride along as a (ROWS, 2) VMEM tile.

Validated on CPU with interpret=True against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import storage_bits
from repro.kernels.lane_pack import pack_rows, unpack_rows

ROWS = 8
COLS = 1024
_EPS = 1e-6


def _quantize_kernel(x_ref, stats_ref, out_ref, scr, *, bits: int):
    x = x_ref[...].astype(jnp.float32)  # (ROWS, COLS)
    lo = stats_ref[:, 0:1]
    hi = stats_ref[:, 1:2]
    d = 2 ** bits
    half = (d - 1) / 2.0
    xc = jnp.clip(x, lo, hi)
    e = 2.0 * (xc - lo) / (hi - lo + _EPS) - 1.0
    if d % 2 == 1:
        z = jnp.round(half * e)
    else:
        z = jnp.round(half * e - 0.5) + 0.5
    z = jnp.clip(z, -half, half)
    idx = (z + half).astype(jnp.int32)
    out_ref[...] = pack_rows(idx, bits, scr).astype(jnp.uint8)


def _dequantize_kernel(w_ref, stats_ref, out_ref, scr, *, bits: int):
    words = w_ref[...].astype(jnp.int32)  # (ROWS, COLS//per)
    lo = stats_ref[:, 0:1]
    hi = stats_ref[:, 1:2]
    d = 2 ** bits
    half = (d - 1) / 2.0
    codes = unpack_rows(words, bits, scr)  # (ROWS, COLS)
    c = (codes.astype(jnp.float32) - half) / half
    out_ref[...] = ((c + 1.0) / 2.0 * (hi - lo) + lo).astype(out_ref.dtype)


def quantize_pallas(x2d: jnp.ndarray, stats: jnp.ndarray, bits: int, *,
                    interpret: bool) -> jnp.ndarray:
    """x2d: (R, C) with R % ROWS == 0, C % COLS == 0; stats: (R, 2)."""
    r, c = x2d.shape
    per = 8 // storage_bits(bits)
    grid = (r // ROWS, c // COLS)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, COLS), lambda i, j: (i, j)),
            pl.BlockSpec((ROWS, 2), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, COLS // per), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c // per), jnp.uint8),
        scratch_shapes=[pltpu.VMEM((COLS, ROWS), jnp.int32)],
        interpret=interpret,
    )(x2d, stats)


def dequantize_pallas(words: jnp.ndarray, stats: jnp.ndarray, bits: int, *,
                      out_dtype=jnp.float32, interpret: bool) -> jnp.ndarray:
    r, cw = words.shape
    per = 8 // storage_bits(bits)
    c = cw * per
    grid = (r // ROWS, c // COLS)
    return pl.pallas_call(
        functools.partial(_dequantize_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, COLS // per), lambda i, j: (i, j)),
            pl.BlockSpec((ROWS, 2), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, COLS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype),
        scratch_shapes=[pltpu.VMEM((COLS, ROWS), jnp.int32)],
        interpret=interpret,
    )(words, stats)
