"""Pallas TPU fused single-token decode attention kernels.

The decode roofline is dominated by streaming the KV cache once per
token; the jnp path additionally round-trips the (B, H, L) fp32 score and
probability tensors through HBM — for a 32k cache those are the same
order of magnitude as the cache itself — and the int8 path materializes
a dequantized copy of every block.  These kernels stream the cache
through VMEM once, keep the online-softmax state (m, l, acc) in scratch
across the L sweep, and for the int8 cache fold the per-(token, head)
absmax scales directly into the two dots, so no dequantized K/V tile
ever exists outside VMEM.

Grid: (B, nL) with the cache-length axis innermost.  Caches keep the
repo's native (B, L, KH, D) ring-buffer layout; each grid step DMAs one
(bL, KH, D) block holding every KV head (the TPU tiling rule forbids a
block of 1 on the KH axis, which is the second-minor one) and loops over
the heads in VMEM.  The query position rides in as a scalar-prefetch
operand (SMEM); the per-slot key positions arrive as (1, bL) lane
vectors, so ``bL`` must be a multiple of 128 or the whole cache
(:func:`compiles`).  Masking (empty slots, causality, sliding window)
uses those runtime positions, and fully-masked blocks (outside the
window / not yet written) are skipped with ``pl.when`` — the ring-buffer
sweep degrades to O(window) work for long-context serving.

Validated on CPU with interpret=True against attention_ref.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_TRANS_B = (((1,), (1,)), ((), ()))
_PLAIN = (((1,), (0,)), ((), ()))


# Largest cache-length block the kernels will accept.  Lengths with no
# divisor <= MAX_BLOCK (e.g. large primes) are rejected by pick_block.
MAX_BLOCK = 2048


def pick_block(length: int, target: int = 512) -> Optional[int]:
    """VMEM-safe cache-length block: the largest divisor of ``length``
    <= min(target, MAX_BLOCK), preferring lane-aligned (multiple-of-128)
    blocks, then sublane-aligned (multiple-of-8) ones.  Returns ``None``
    when no reasonable block divides (e.g. prime lengths beyond
    MAX_BLOCK)."""
    cap = min(target, MAX_BLOCK, length)
    for step in (128, 8):  # aligned, largest first
        for cand in range(cap - cap % step, step - 1, -step):
            if length % cand == 0:
                return cand
    if length <= cap:
        return length  # odd-but-small ring buffers: one block
    for cand in range(cap, 7, -1):  # unaligned beats no block at all
        if length % cand == 0:
            return cand
    return None


def compiles(block: int, length: int) -> bool:
    """Whether the compiled TPU kernel takes a ``block`` of a ``length``
    cache: the (1, bL) key-position and scale blocks put bL on the lane
    axis, which must be a multiple of 128 or the whole axis."""
    return length % block == 0 and (block % 128 == 0 or block == length)


def paged_compiles(page_size: int) -> bool:
    """Whether the compiled paged kernels take ``page_size``: a page block
    spans the pool's trailing axes, so the tiling rule allows any size,
    but a one-slot page leaves a (1, 1) mask Mosaic cannot broadcast."""
    return page_size >= 2


def _valid(kp, qp, window):
    """(1, bL) mask: slot written, causal, in-window."""
    v = jnp.logical_and(kp >= 0, kp <= qp)
    if window is not None:
        v = jnp.logical_and(v, qp - kp < window)
    return v


def _init_state(m_s, l_s, acc_s):
    m_s[...] = jnp.full_like(m_s, _NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)


def _finalize(o_ref, l_s, acc_s):
    o_ref[0] = acc_s[...] / jnp.maximum(l_s[...], 1e-30)


def _attend_heads(q_ref, k_ref, v_ref, valid, m_s, l_s, acc_s,
                  ks_ref=None, vs_ref=None):
    """One online-softmax step for every KV head of the block.

    q_ref (1, KH, G, D) pre-scaled queries; k_ref/v_ref (1, bL, KH, D/Dv)
    cache blocks (int8 codes when ``ks_ref``/``vs_ref`` hold the (1, KH,
    bL) absmax scales); valid (1, bL).  The V scales fold into p before
    the dot, while the l normalizer keeps the unscaled p, matching the
    reference softmax-then-scale order."""
    for h in range(k_ref.shape[2]):
        q = q_ref[0, h]                           # (G, D)
        k = k_ref[0, :, h, :].astype(q.dtype)     # (bL, D)
        s = jax.lax.dot_general(q, k, _TRANS_B,
                                preferred_element_type=jnp.float32)
        if ks_ref is not None:
            s = s * ks_ref[0, h:h + 1, :]
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_s[h]                           # (G, 1)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_next)
        corr = jnp.exp(m_prev - m_next)
        l_s[h] = l_s[h] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_s[h] = m_next
        if vs_ref is not None:
            p = p * vs_ref[0, h:h + 1, :]
        v = v_ref[0, :, h, :].astype(q.dtype)     # (bL, Dv)
        pv = jax.lax.dot_general(p.astype(q.dtype), v, _PLAIN,
                                 preferred_element_type=jnp.float32)
        acc_s[h] = acc_s[h] * corr + pv


def _any(mask):
    return jnp.max(mask.astype(jnp.int32)) > 0


def _scratch(kh, g, dv):
    return [pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, dv), jnp.float32)]


# ---------------------------------------------------------------------------
# contiguous ring-buffer caches (static generate path)
# ---------------------------------------------------------------------------

def _decode_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, *rest, window,
                   nl, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_s, l_s, acc_s = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_state(m_s, l_s, acc_s)

    valid = _valid(kpos_ref[0], qpos_ref[b], window)  # (1, bL)

    @pl.when(_any(valid))
    def _compute():
        _attend_heads(q_ref, k_ref, v_ref, valid, m_s, l_s, acc_s,
                      ks_ref, vs_ref)

    @pl.when(j == nl - 1)
    def _fin():
        _finalize(o_ref, l_s, acc_s)


def _decode_call(qf, k_cache, v_cache, scales, kpos, qpos, *, window,
                 block, interpret):
    b, kh, g, d = qf.shape
    length = k_cache.shape[1]
    dv = v_cache.shape[-1]
    nl = length // block
    cache_map = lambda b_, j, qp: (b_, j, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, block), lambda b_, j, qp: (b_, 0, j)),
        pl.BlockSpec((1, kh, g, d), lambda b_, j, qp: (b_, 0, 0, 0)),
        pl.BlockSpec((1, block, kh, d), cache_map),
        pl.BlockSpec((1, block, kh, dv), cache_map),
    ]
    in_specs += [pl.BlockSpec((1, kh, block), lambda b_, j, qp: (b_, 0, j))
                 ] * len(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kh, g, dv),
                               lambda b_, j, qp: (b_, 0, 0, 0)),
        scratch_shapes=_scratch(kh, g, dv),
    )
    kernel = functools.partial(_decode_kernel, window=window, nl=nl,
                               quantized=bool(scales))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dv), jnp.float32),
        interpret=interpret,
    )(qpos, kpos.reshape(b, 1, length), qf, k_cache, v_cache, *scales)


def decode(qf, k_cache, v_cache, kpos, qpos, *, window, block, interpret):
    """qf: (B, KH, G, D) pre-scaled; caches (B, L, KH, D/Dv); kpos (B, L);
    qpos (B,) int32.  Returns (B, KH, G, Dv) fp32."""
    return _decode_call(qf, k_cache, v_cache, (), kpos, qpos, window=window,
                        block=block, interpret=interpret)


def decode_q8(qf, k_codes, v_codes, k_scale, v_scale, kpos, qpos, *,
              window, block, interpret):
    """Int8-cache decode.  qf (B, KH, G, D) pre-scaled; codes
    (B, L, KH, D) int8; scales (B, KH, L) fp32 (pre-transposed by the
    caller — they are D-times smaller than the codes).  Returns
    (B, KH, G, D) fp32."""
    return _decode_call(qf, k_codes, v_codes, (k_scale, v_scale), kpos,
                        qpos, window=window, block=block,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# paged lookup path (serving engine: KV pool + per-request page tables)
# ---------------------------------------------------------------------------
#
# The continuous-batching engine stores the KV cache as fixed-size pages
# in a shared pool; each slot owns a page table mapping logical page j to
# a physical pool page.  The page table and the slots' query positions
# ride in as *scalar-prefetch* operands (PrefetchScalarGridSpec), so the
# BlockSpec index maps read the table to DMA each slot's pages straight
# out of the pool — no gathered contiguous copy of the cache ever exists.
# Unallocated entries (-1) are clamped to physical page 0 (the engine's
# reserved null page) for the DMA and masked out in-kernel via the
# prefetched table, so whatever page 0 holds never contributes.  Grid:
# (S, npp), page axis innermost; each step takes one (pg, KH, D) page
# with every KV head, the same online-softmax sweep as the ring kernels.
# A page block spans whole trailing axes, so every page size of two or
# more compiles (:func:`paged_compiles`).

def _pt_phys(pt_ref, s, j):
    """Clamped physical page for (slot s, logical page j)."""
    return jnp.maximum(pt_ref[s, j], 0)


def _paged_kernel(pt_ref, qpos_ref, q_ref, k_ref, v_ref, pos_ref, *rest,
                  window, npp, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_s, l_s, acc_s = rest
    s_idx = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_state(m_s, l_s, acc_s)

    valid = _valid(pos_ref[0], qpos_ref[s_idx], window) \
        & (pt_ref[s_idx, j] >= 0)                  # (1, pg)

    @pl.when(_any(valid))
    def _compute():
        _attend_heads(q_ref, k_ref, v_ref, valid, m_s, l_s, acc_s,
                      ks_ref, vs_ref)

    @pl.when(j == npp - 1)
    def _fin():
        _finalize(o_ref, l_s, acc_s)


def _paged_call(qf, k_pool, v_pool, scales, pos_pool, page_table, qpos, *,
                window, interpret):
    s, kh, g, d = qf.shape
    n_pages, pg = k_pool.shape[:2]
    dv = v_pool.shape[-1]
    npp = page_table.shape[1]
    page_map = lambda s_, j, pt, qp: (_pt_phys(pt, s_, j), 0, 0, 0)
    row_map = lambda s_, j, pt, qp: (_pt_phys(pt, s_, j), 0, 0)
    in_specs = [
        pl.BlockSpec((1, kh, g, d), lambda s_, j, pt, qp: (s_, 0, 0, 0)),
        pl.BlockSpec((1, pg, kh, d), page_map),
        pl.BlockSpec((1, pg, kh, dv), page_map),
        pl.BlockSpec((1, 1, pg), row_map),
    ]
    in_specs += [pl.BlockSpec((1, kh, pg), row_map)] * len(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, npp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kh, g, dv),
                               lambda s_, j, pt, qp: (s_, 0, 0, 0)),
        scratch_shapes=_scratch(kh, g, dv),
    )
    kernel = functools.partial(_paged_kernel, window=window, npp=npp,
                               quantized=bool(scales))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, kh, g, dv), jnp.float32),
        interpret=interpret,
    )(page_table, qpos, qf, k_pool, v_pool,
      pos_pool.reshape(n_pages, 1, pg), *scales)


def decode_paged(qf, k_pool, v_pool, pos_pool, page_table, qpos, *,
                 window, interpret):
    """Paged-pool decode.  qf: (S, KH, G, D) pre-scaled; pools
    (P, pg, KH, D/Dv); pos_pool (P, pg) int32; page_table (S, npp) int32
    (-1 = unallocated); qpos (S,) int32.  Returns (S, KH, G, Dv) fp32."""
    return _paged_call(qf, k_pool, v_pool, (), pos_pool, page_table, qpos,
                       window=window, interpret=interpret)


def decode_paged_q8(qf, k_pool, v_pool, k_scale, v_scale, pos_pool,
                    page_table, qpos, *, window, interpret):
    """Paged int8-pool decode.  Codes (P, pg, KH, D) int8; scales
    (P, KH, pg) fp32 (pre-transposed by the caller); otherwise as
    :func:`decode_paged`.  Returns (S, KH, G, D) fp32."""
    return _paged_call(qf, k_pool, v_pool, (k_scale, v_scale), pos_pool,
                       page_table, qpos, window=window, interpret=interpret)
