"""jit'd public wrappers for the compressor kernels.

Handles layout (flatten to 2-D, pad to tile multiples, slice back),
backend dispatch (interpret=True on CPU — the kernels target TPU), and
the cheap outside-the-kernel pieces (RD-FSQ statistics pass, NF double
quantization of block ranges).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.packing import storage_bits
from repro.core.quantizers.nf import nf_codebook
from repro.kernels import nf_kernel, rdfsq_kernel
from repro.kernels.ref import rdfsq_stats

_EPS = 1e-8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# RD-FSQ
# ---------------------------------------------------------------------------

def _pad_to(x, mult, axis, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.jit, static_argnames=("bits", "clip_sigma"))
def rdfsq_quantize(x: jnp.ndarray, bits: int, clip_sigma: float = 3.0
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused quantize+pack.  x: (B, ...) -> (packed (B, C*b/8), stats (B,2)).

    Statistics (one reduction pass) run in jnp; the streaming
    clip/scale/round/pack runs in the Pallas kernel.
    """
    b = x.shape[0]
    x2d = x.reshape(b, -1)
    c = x2d.shape[1]
    lo, hi = rdfsq_stats(x2d, clip_sigma)
    stats = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32)
    xp = _pad_to(x2d.astype(jnp.float32), rdfsq_kernel.COLS, 1)
    # pad rows so the row grid divides; padded rows reuse row-0 stats
    xp = _pad_to(xp, rdfsq_kernel.ROWS, 0)
    statsp = _pad_to(stats, rdfsq_kernel.ROWS, 0, value=1.0)
    words = rdfsq_kernel.quantize_pallas(xp, statsp, bits,
                                         interpret=_interpret())
    per = 8 // storage_bits(bits)
    cw = -(-c // per)  # ceil after packing of the unpadded columns
    return words[:b, :cw], stats.astype(jnp.float16)


@partial(jax.jit, static_argnames=("bits", "n_cols", "out_dtype"))
def rdfsq_dequantize(words: jnp.ndarray, stats: jnp.ndarray, bits: int,
                     n_cols: int, out_dtype=jnp.float32) -> jnp.ndarray:
    b = words.shape[0]
    per = 8 // storage_bits(bits)
    wp = _pad_to(words, rdfsq_kernel.COLS // per, 1)
    wp = _pad_to(wp, rdfsq_kernel.ROWS, 0)
    statsp = _pad_to(stats.astype(jnp.float32), rdfsq_kernel.ROWS, 0,
                     value=1.0)
    x = rdfsq_kernel.dequantize_pallas(wp, statsp, bits,
                                       out_dtype=out_dtype,
                                       interpret=_interpret())
    return x[:b, :n_cols]


# ---------------------------------------------------------------------------
# NF-b (QLoRA)
# ---------------------------------------------------------------------------

def _double_quant(rng: jnp.ndarray, dq_group: int):
    nb = rng.shape[0]
    pad = (-nb) % dq_group
    groups = jnp.pad(rng, ((0, pad), (0, 0))).reshape(-1, dq_group)
    gscale = jnp.max(jnp.abs(groups), axis=-1, keepdims=True)
    codes = jnp.round(groups / (gscale + _EPS) * 255.0).astype(jnp.uint8)
    return codes.reshape(-1, 1)[:nb + pad], gscale[:, 0].astype(jnp.float16)


@partial(jax.jit, static_argnames=("bits", "block", "double_quant",
                                   "dq_group"))
def nf_quantize(x: jnp.ndarray, bits: int, block: int = 64,
                double_quant: bool = True, dq_group: int = 256):
    """Blockwise NF-b quantize+pack.

    Returns (packed codes (NB, G*b/8), scales, aux dict); the caller keeps
    ``x.size`` for dequantization.
    """
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block
    blocks = jnp.pad(flat, (0, pad)).reshape(-1, block)
    nb = blocks.shape[0]
    bpad = (-nb) % nf_kernel.BLOCKS_PER_TILE
    blocks = jnp.pad(blocks, ((0, bpad), (0, 0)))
    book = jnp.asarray(nf_codebook(bits), jnp.float32)
    words, m, rng = nf_kernel.quantize_pallas(blocks, book, bits,
                                              interpret=_interpret())
    words = words[:nb]
    m, rng = m[:nb].astype(jnp.float16), rng[:nb].astype(jnp.float16)
    aux = dict(block_min=m)
    if double_quant:
        codes, gscale = _double_quant(rng.astype(jnp.float32), dq_group)
        scales = codes[:nb]
        aux["dq_scale"] = gscale
    else:
        scales = rng
    return words, scales, aux


@partial(jax.jit, static_argnames=("bits", "block", "double_quant",
                                   "dq_group", "n", "out_dtype"))
def nf_dequantize(words: jnp.ndarray, scales: jnp.ndarray, aux: dict,
                  bits: int, n: int, block: int = 64,
                  double_quant: bool = True, dq_group: int = 256,
                  out_dtype=jnp.float32):
    nb = words.shape[0]
    m = aux["block_min"]
    if double_quant:
        gscale = aux["dq_scale"].astype(jnp.float32)
        pad = (-nb) % dq_group
        codes = jnp.pad(scales, ((0, pad), (0, 0))).reshape(-1, dq_group)
        rng = (codes.astype(jnp.float32) / 255.0 * gscale[:, None]
               ).reshape(-1, 1)[:nb].astype(jnp.float16)
    else:
        rng = scales
    bpad = (-nb) % nf_kernel.BLOCKS_PER_TILE
    wp = jnp.pad(words, ((0, bpad), (0, 0)))
    mp = jnp.pad(m, ((0, bpad), (0, 0)))
    rp = jnp.pad(rng, ((0, bpad), (0, 0)))
    book = jnp.asarray(nf_codebook(bits), jnp.float32)
    x = nf_kernel.dequantize_pallas(wp, mp, rp, book, bits, block,
                                    out_dtype=out_dtype,
                                    interpret=_interpret())
    return x[:nb].reshape(-1)[:n]
