"""Pallas TPU kernel: fused packed-int4/int3 dequant + matmul (repro.wq).

The serve-time decode path is HBM-bandwidth bound on *weights*: every
tick streams the whole server stack out of HBM at full width.  With the
weights stored as ``core.packing`` bitstreams (0.5 B/element at int4
instead of 2 B bf16), the matmul must unpack + dequantize on the fly —
done here inside the MXU pipeline so the codes never exist at 8 bits in
HBM: each grid step reads a ``(bk * bits / 8, bn)`` uint8 tile and the
``(bk / group, bn)`` scale/min tiles into VMEM, rebuilds the codes with
int32 word arithmetic (8 consecutive codes of a column span exactly
``bits`` whole bytes, so a ``(nb, bits, bn)`` reshape + byte shifts
yields one 32-bit word per code octet — ``bits <= 4`` fits; int32 because
Mosaic reduces no unsigned type), maps ``code * scale + min``, and
contracts the dequantized ``(bk, bn)`` tile against the activation tile
in the activation dtype with an fp32 VMEM accumulator.  The scales and
mins are stored fp16 and widened to fp32 by the wrapper: the v5e vector
unit loads no fp16.

HBM traffic per output tile: ``bits/16`` of the bf16 weight bytes plus
the fp16 side info (``2 * 16 / (group * bits)`` relative) — the ~3.76x
serve-time weight-bandwidth cut measured by ``benchmarks/wq_bench.py``.

Grid: ``(M / bm, N / bn, K / bk)`` with K innermost; the wrapper pads M
to ``bm``, N to ``bn = 128`` (lane width) and K to ``bk`` — the whole K
axis up to ``MAX_BK``, else 8 groups, so the scale tiles' sublane count
is legal — and padded K rows decode against zero-padded activations, so
they contribute exactly 0.  Validated on CPU with
``interpret=True`` against ``kernels/ref.py::wq_matmul_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import packed_size

BM = 16   # sublane multiple for both fp32 (8) and bf16 (16) tiles
BN = 128  # lane width
MAX_BK = 4096  # longest K axis taken as one block


def _matmul_kernel(x_ref, w_ref, s_ref, m_ref, o_ref, acc_ref, *,
                   bits: int, group: int, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    words = w_ref[...].astype(jnp.int32)     # (bk * bits // 8, bn) bytes
    nb = words.shape[0] // bits              # 8-code octets in this K tile
    bn = words.shape[1]
    w32 = words.reshape(nb, bits, bn)
    byte_shifts = (jnp.arange(bits, dtype=jnp.int32) * 8)[None, :, None]
    # the shifted bytes are disjoint, so the (wrapping) int32 sum is their
    # bitwise OR: one 32-bit word per code octet
    word32 = (w32 << byte_shifts).sum(axis=1)          # (nb, bn)
    code_shifts = (jnp.arange(8, dtype=jnp.int32) * bits)[None, :, None]
    codes = jax.lax.shift_right_logical(word32[:, None, :], code_shifts) \
        & (2 ** bits - 1)                               # (nb, 8, bn)
    codes = codes.reshape(nb * 8, bn).astype(jnp.float32)

    gpb = (nb * 8) // group                  # groups per K tile (>= 1)
    scale = s_ref[...][:, None, :]           # (gpb, 1, bn) fp32
    mn = m_ref[...][:, None, :]
    w = (codes.reshape(gpb, group, bn) * scale + mn).reshape(nb * 8, bn)

    x = x_ref[...]                           # (bm, bk) activation dtype
    acc_ref[...] += jax.lax.dot(x, w.astype(x.dtype),
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(a: jnp.ndarray, axis: int, size: int) -> jnp.ndarray:
    pad = size - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(jax.jit, static_argnames=("bits", "group", "d_in",
                                             "interpret"))
def matmul_pallas(x2d: jnp.ndarray, words: jnp.ndarray,
                  scales: jnp.ndarray, mins: jnp.ndarray, *, bits: int,
                  group: int, d_in: int, interpret: bool) -> jnp.ndarray:
    """(M, d_in) @ packed (d_in, d_out) -> (M, d_out) fp32.

    ``words``: (packed_size(d_in, bits), d_out) per-column bitstreams in
    STORAGE channel order (any act-order gather happened on ``x``
    upstream); ``scales``/``mins``: (ceil(d_in / group), d_out) fp16.
    """
    if bits not in (2, 3, 4):
        raise ValueError(f"fused wq kernel supports bits in (2, 3, 4); "
                         f"got {bits}")
    m, k_in = x2d.shape
    assert k_in == d_in, (k_in, d_in)
    d_out = words.shape[1]
    assert words.shape[0] == packed_size(d_in, bits), words.shape
    n_groups = -(-d_in // group)
    assert scales.shape == (n_groups, d_out), scales.shape

    # the (gpb, BN) scale/min tiles put gpb on sublanes: it must be a
    # multiple of 8 or every group, so short K axes take one block
    gpb = n_groups if n_groups * group <= MAX_BK else 8
    bk = group * gpb
    m_pad = -(-m // BM) * BM
    n_pad = -(-d_out // BN) * BN
    k_pad = -(-d_in // bk) * bk
    n_k = k_pad // bk

    x_p = _pad_to(_pad_to(x2d, 1, k_pad), 0, m_pad)
    w_p = _pad_to(_pad_to(words, 0, k_pad * bits // 8), 1, n_pad)
    # fp32 side info: the v5e vector unit has no fp16 loads
    s_p = _pad_to(_pad_to(scales.astype(jnp.float32), 0, k_pad // group),
                  1, n_pad)
    mn_p = _pad_to(_pad_to(mins.astype(jnp.float32), 0, k_pad // group),
                   1, n_pad)

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, bits=bits, group=group, n_k=n_k),
        grid=(m_pad // BM, n_pad // BN, n_k),
        in_specs=[
            pl.BlockSpec((BM, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk * bits // 8, BN), lambda i, j, k: (k, j)),
            pl.BlockSpec((gpb, BN), lambda i, j, k: (k, j)),
            pl.BlockSpec((gpb, BN), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=interpret,
    )(x_p, w_p, s_p, mn_p)
    return out[:m, :d_out]
