"""Pallas TPU kernel: blockwise NF-b (QLoRA) quantize / dequantize.

One grid step processes a (BLOCKS_PER_TILE x G) tile of activation blocks:
per-block (min, range) reduction, normalize onto [-1, 1], nearest-neighbor
lookup against the <=16-entry NF codebook held in SMEM (an unrolled
compare over the levels — VPU-friendly, no gather, first level wins ties
exactly like ``argmin``), then int32 shift-or pack along sublanes
(``lane_pack``) and one uint8 store.  Outputs per tile: packed codes +
per-block (min, range) side-info (the "auxiliary information" whose wire
cost the paper discusses for QLoRA), emitted as fp32 because Mosaic has
no (rows, 1) fp16 tile; the caller narrows them to the fp16 wire form.

VMEM: 128 x 64 fp32 tile (32 KiB) + packing scratch + outputs — tiny; the
kernel is bandwidth-bound by design (quantization is a streaming op).
Double quantization of the ranges happens outside the kernel (it touches
only NB/G scalars, 1/64th of the data).

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

BLOCKS_PER_TILE = 128
_EPS = 1e-8


def _quant_kernel(x_ref, book_ref, codes_ref, m_ref, r_ref, scr, *,
                  bits: int):
    x = x_ref[...].astype(jnp.float32)  # (BT, G)
    m = x.min(axis=1, keepdims=True)
    mx = x.max(axis=1, keepdims=True)
    rng = mx - m
    norm = 2.0 * (x - m) / (rng + _EPS) - 1.0
    best = jnp.abs(norm - book_ref[0])
    codes = jnp.zeros(norm.shape, jnp.int32)
    for i in range(1, book_ref.shape[0]):
        dist = jnp.abs(norm - book_ref[i])
        closer = dist < best
        codes = jnp.where(closer, i, codes)
        best = jnp.where(closer, dist, best)
    codes_ref[...] = pack_rows(codes, bits, scr).astype(jnp.uint8)
    m_ref[...] = m
    r_ref[...] = rng


def _dequant_kernel(w_ref, m_ref, r_ref, book_ref, out_ref, scr, *,
                    bits: int):
    m = m_ref[...]
    rng = r_ref[...]
    codes = unpack_rows(w_ref[...].astype(jnp.int32), bits, scr)  # (BT, G)
    # gather-free lookup: one select per codebook level
    norm = jnp.zeros(codes.shape, jnp.float32)
    for i in range(book_ref.shape[0]):
        norm = jnp.where(codes == i, book_ref[i], norm)
    out_ref[...] = ((norm + 1.0) / 2.0 * rng + m).astype(out_ref.dtype)


def quantize_pallas(blocks: jnp.ndarray, book: jnp.ndarray, bits: int, *,
                    interpret: bool):
    """blocks: (NB, G) with NB % BLOCKS_PER_TILE == 0."""
    nb, g = blocks.shape
    per = 8 // storage_bits(bits)
    grid = (nb // BLOCKS_PER_TILE,)
    return pl.pallas_call(
        functools.partial(_quant_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCKS_PER_TILE, g), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((BLOCKS_PER_TILE, g // per), lambda i: (i, 0)),
            pl.BlockSpec((BLOCKS_PER_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLOCKS_PER_TILE, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, g // per), jnp.uint8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((g, BLOCKS_PER_TILE), jnp.int32)],
        interpret=interpret,
    )(blocks, book.astype(jnp.float32))


def dequantize_pallas(words: jnp.ndarray, m: jnp.ndarray, rng: jnp.ndarray,
                      book: jnp.ndarray, bits: int, g: int, *,
                      out_dtype=jnp.float32, interpret: bool):
    nb = words.shape[0]
    per = 8 // storage_bits(bits)
    grid = (nb // BLOCKS_PER_TILE,)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCKS_PER_TILE, g // per), lambda i: (i, 0)),
            pl.BlockSpec((BLOCKS_PER_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLOCKS_PER_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((BLOCKS_PER_TILE, g), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, g), out_dtype),
        scratch_shapes=[pltpu.VMEM((g, BLOCKS_PER_TILE), jnp.int32)],
        interpret=interpret,
    )(words, m.astype(jnp.float32), rng.astype(jnp.float32),
      book.astype(jnp.float32))
