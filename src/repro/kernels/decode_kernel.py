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


def _attend_heads(q_ref, k_of, v_of, valid, m_s, l_s, acc_s,
                  ks_of=None, vs_of=None):
    """One online-softmax step for every KV head of the block.

    q_ref (1, KH, G, D) pre-scaled queries; ``k_of(h)``/``v_of(h)`` give
    head h's (bL, D/Dv) keys and values (int8 codes when ``ks_of``/``vs_of``
    give its (1, bL) absmax scales); valid (1, bL).  The V scales fold
    into p before the dot, while the l normalizer keeps the unscaled p,
    matching the reference softmax-then-scale order."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h]                           # (G, D)
        k = k_of(h).astype(q.dtype)               # (bL, D)
        s = jax.lax.dot_general(q, k, _TRANS_B,
                                preferred_element_type=jnp.float32)
        if ks_of is not None:
            s = s * ks_of(h)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_s[h]                           # (G, 1)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_next)
        corr = jnp.exp(m_prev - m_next)
        l_s[h] = l_s[h] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_s[h] = m_next
        if vs_of is not None:
            p = p * vs_of(h)
        v = v_of(h).astype(q.dtype)               # (bL, Dv)
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
        scales = () if ks_ref is None else (
            lambda h: ks_ref[0, h:h + 1, :], lambda h: vs_ref[0, h:h + 1, :])
        _attend_heads(q_ref, lambda h: k_ref[0, :, h, :],
                      lambda h: v_ref[0, :, h, :], valid, m_s, l_s, acc_s,
                      *scales)

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
# a physical pool page.  The kernel reads each slot's pages straight out
# of the pool — no gathered contiguous copy of the cache ever exists.
#
# Grid: (S, nb), block axis innermost; a step reads a block of ``bp``
# logical pages (:func:`paged_block`: the largest power of two with
# bp * pg <= 512 positions, at most 32 pages and within a VMEM budget;
# the table is padded with -1 to a multiple of bp).  Each page of the
# block is a pipelined operand of its own (K, V, positions and, for the
# int8 pool, the scales), so the grid pipeline copies the next step's
# pages while this step computes, across slot boundaries too; in VMEM
# the block's pages are concatenated into one (bp * pg)-long sweep per
# KV head.
#
# A slot reads only its live pages: logical pages 0 .. qpos // pg (with
# a window, from the page holding qpos - window + 1) that the table
# allocates.  The wrapper turns table and qpos into a *page plan*
# (:func:`_page_plan`), the scalar-prefetch operand the index maps read:
# per (slot, logical page), a live page's physical page, or ``~p`` for a
# skipped page, with p the page its operand already holds — the block
# index does not change, so the pipeline copies nothing for it.  A
# skipped page's VMEM therefore holds another page's data; the kernel
# masks it by the plan (its positions read as -1), never by that data.
# Inside live pages the position pool masks as before (written, causal,
# in window).  An inactive slot (qpos -1) reads no page and returns
# exact zeros.
#
# The pages are grid operands rather than manual copies of pools
# passed in HBM (``memory_space=pl.ANY``): Mosaic copies by hand only
# whole (sublane, lane) tiles of the two minor axes, which a (pg, KH, D)
# page with KH = 5 or D = 64, or a (1, pg) position row, is not; the grid
# pipeline's copies take any block that spans whole axes, so every page
# size of two or more compiles (:func:`paged_compiles`).

PAGED_BLOCK_POSITIONS = 512
PAGED_BLOCK_PAGES = 32
PAGED_VMEM_BUDGET = 8 << 20  # one block's K and V pages, double-buffered


def _tile_bytes(rows, cols, dtype):
    """VMEM bytes of a (rows, cols) array in (sublane, 128-lane) tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * (4 // itemsize)  # 8 rows a tile at 32 bits, 16 at 16, 32 at 8
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * itemsize


def paged_block(npp: int, page_size: int, position_bytes: int) -> int:
    """Pages a paged grid step reads: the largest power of two ``bp`` with
    bp <= npp, bp * page_size <= PAGED_BLOCK_POSITIONS, bp <=
    PAGED_BLOCK_PAGES (each page is an operand of its own) and the
    block's pages, double-buffered, within PAGED_VMEM_BUDGET
    (``position_bytes`` is one position's K and V in VMEM tiles)."""
    def fits(n):
        return (n <= min(npp, PAGED_BLOCK_PAGES)
                and n * page_size <= PAGED_BLOCK_POSITIONS
                and 2 * n * page_size * position_bytes <= PAGED_VMEM_BUDGET)

    bp = 1
    while fits(2 * bp):
        bp *= 2
    return bp


def _page_plan(page_table, qpos, *, page_size, bp, window):
    """(S * npp,) int32 page plan in grid order: entry s * npp + j is slot
    s's logical page j.  A live page (allocated, at or before qpos's page,
    reached by the window) gives its physical page; a skipped one gives
    ``~p``, p the page its operand read last (before its first live page,
    the one it reads first; the last step's page if it reads none)."""
    s, npp = page_table.shape
    qp = qpos[:, None]
    lp = jnp.arange(npp)[None, :]
    first = 0 if window is None else \
        jnp.maximum(qp - window + 1, 0) // page_size
    live = ((qp >= 0) & (lp >= first) & (lp <= qp // page_size)
            & (page_table >= 0))
    steps = s * npp // bp                 # grid steps; operand i = j % bp
    live = live.reshape(steps, bp)
    phys = jnp.maximum(page_table, 0).reshape(steps, bp)
    t = jnp.arange(steps)[:, None]
    last_read = jax.lax.cummax(jnp.where(live, t, -1), axis=0)
    next_read = jax.lax.cummin(jnp.where(live, t, steps - 1), axis=0,
                               reverse=True)
    held = jnp.take_along_axis(
        phys, jnp.where(last_read >= 0, last_read, next_read), axis=0)
    return jnp.where(live, phys, ~held).reshape(-1)


def _held(entry):
    """Physical page a plan entry keeps in its operand's VMEM."""
    return jnp.where(entry >= 0, entry, ~entry)


def _paged_kernel(plan_ref, qpos_ref, q_ref, *refs, window, bp, nb,
                  quantized):
    n_in = (5 if quantized else 3) * bp
    k_refs, v_refs, pos_refs = refs[:bp], refs[bp:2 * bp], refs[2 * bp:3 * bp]
    ks_refs, vs_refs = refs[3 * bp:4 * bp], refs[4 * bp:n_in]
    o_ref, m_s, l_s, acc_s = refs[n_in:]
    s_idx = pl.program_id(0)
    b = pl.program_id(1)
    base = (s_idx * nb + b) * bp
    live = [plan_ref[base + i] >= 0 for i in range(bp)]

    @pl.when(b == 0)
    def _init():
        _init_state(m_s, l_s, acc_s)

    @pl.when(functools.reduce(jnp.logical_or, live))
    def _block():
        kp = jnp.concatenate([jnp.where(live[i], pos_refs[i][0], -1)
                              for i in range(bp)], axis=1)  # (1, bp * pg)
        valid = _valid(kp, qpos_ref[s_idx], window)

        def rows(pages):        # head h's (bp * pg, D) sweep of the block
            return lambda h: jnp.concatenate(
                [r[0, :, h, :].astype(q_ref.dtype) for r in pages], axis=0)

        def lanes(pages):       # head h's (1, bp * pg) scales
            return lambda h: jnp.concatenate(
                [r[0, h:h + 1, :] for r in pages], axis=1)

        scales = (lanes(ks_refs), lanes(vs_refs)) if quantized else ()

        @pl.when(_any(valid))
        def _compute():
            _attend_heads(q_ref, rows(k_refs), rows(v_refs), valid,
                          m_s, l_s, acc_s, *scales)

    @pl.when(b == nb - 1)
    def _fin():
        _finalize(o_ref, l_s, acc_s)


def _paged_call(qf, k_pool, v_pool, scales, pos_pool, page_table, qpos, *,
                window, interpret):
    s, kh, g, d = qf.shape
    n_pages, pg = k_pool.shape[:2]
    dv = v_pool.shape[-1]
    bp = paged_block(page_table.shape[1], pg,
                     _tile_bytes(kh, d, k_pool.dtype)
                     + _tile_bytes(kh, dv, v_pool.dtype))
    pad = -page_table.shape[1] % bp
    page_table = jnp.pad(page_table, ((0, 0), (0, pad)), constant_values=-1)
    nb = page_table.shape[1] // bp
    plan = _page_plan(page_table, qpos, page_size=pg, bp=bp, window=window)

    def page(i, rank):
        return lambda s_, b, plan_, qp: (
            (_held(plan_[(s_ * nb + b) * bp + i]),) + (0,) * (rank - 1))

    pools = [(k_pool, (1, pg, kh, d)), (v_pool, (1, pg, kh, dv)),
             (pos_pool.reshape(n_pages, 1, pg), (1, 1, pg))]
    pools += [(sc, (1, kh, pg)) for sc in scales]
    in_specs = [pl.BlockSpec((1, kh, g, d),
                             lambda s_, b, plan_, qp: (s_, 0, 0, 0))]
    operands = [qf]
    for pool, block in pools:
        in_specs += [pl.BlockSpec(block, page(i, len(block)))
                     for i in range(bp)]
        operands += [pool] * bp
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kh, g, dv),
                               lambda s_, b, plan_, qp: (s_, 0, 0, 0)),
        scratch_shapes=_scratch(kh, g, dv),
    )
    kernel = functools.partial(_paged_kernel, window=window, bp=bp, nb=nb,
                               quantized=bool(scales))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, kh, g, dv), jnp.float32),
        interpret=interpret,
    )(plan, qpos, *operands)


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
