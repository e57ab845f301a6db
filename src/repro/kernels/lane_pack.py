"""In-kernel sub-byte slot packing of codes that run along the lane axis.

The wire codecs pack ``per = 8 // storage_bits(bits)`` ADJACENT codes of
a row into one byte, lowest slot first (the ``core.packing`` slot
layout).  Mosaic does not lower a lane-strided gather, nor a reshape that
splits the lane axis, so these helpers move the packed axis onto
sublanes: a transpose into a VMEM scratch, strided sublane loads (pack)
or stores (unpack) that regroup every ``per``-th code, and a transpose
back.  All arithmetic is int32; callers cast to and from uint8 only at
the ref boundary.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import storage_bits


def pack_rows(codes: jnp.ndarray, bits: int, scr) -> jnp.ndarray:
    """(R, C) int32 codes -> (R, C // per) int32 words.

    ``scr``: VMEM scratch ref of shape (C, R) int32."""
    sb = storage_bits(bits)
    per = 8 // sb
    n_words = codes.shape[1] // per
    scr[...] = codes.T
    words = scr[pl.ds(0, n_words, stride=per), :]
    for k in range(1, per):
        words = words | (scr[pl.ds(k, n_words, stride=per), :] << (k * sb))
    return words.T


def unpack_rows(words: jnp.ndarray, bits: int, scr) -> jnp.ndarray:
    """(R, W) int32 words -> (R, W * per) int32 codes.

    ``scr``: VMEM scratch ref of shape (W * per, R) int32."""
    sb = storage_bits(bits)
    per = 8 // sb
    mask = (1 << sb) - 1
    wt = words.T
    n_words = wt.shape[0]
    for k in range(per):
        scr[pl.ds(k, n_words, stride=per), :] = (wt >> (k * sb)) & mask
    return scr[...].T
