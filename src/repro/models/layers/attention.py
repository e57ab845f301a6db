"""GQA attention public API: projections, RoPE, KV caches, and dispatch.

The actual attention math lives behind a two-backend dispatch
(``repro.kernels.attention_ops``):

* **pallas** — fused TPU flash-attention kernels
  (``kernels/flash_kernel.py`` forward + backward,
  ``kernels/decode_kernel.py`` single-token bf16/int8 decode); default on
  TPU backends, interpret-mode elsewhere.
* **jnp** — the chunked online-softmax reference with a custom VJP
  (``kernels/attention_ref.py``); default off-TPU and the oracle for the
  kernel parity tests.

Select with the ``impl=`` keyword, the ``REPRO_ATTN_IMPL`` env var
(``pallas`` | ``jnp``), or leave unset for the backend default.  Both
backends share the operand contract: operands stay in model dtype (bf16),
every dot accumulates in fp32, the backward recomputes per-block
probabilities from the saved (row-max, row-sum) so no (Sq x Skv) tensor
is ever materialized, and masking uses RUNTIME position vectors (see
``attention_ref._block_mask`` for why trace-time iota is forbidden).

Supports: causal masking, sliding windows (the sub-quadratic variant used
for long_500k on full-attention architectures), GQA head grouping,
Dv != Dk (MLA), decode against ring-buffer KV caches (bf16 and
int8-quantized with fused scales).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import attention_ops
from repro.kernels.attention_ref import (_FAR, _NEG_INF,
                                         decode_attention_paged_q8_ref,
                                         decode_attention_paged_ref,
                                         decode_attention_q8_ref,
                                         decode_attention_ref,
                                         flash_reference)
from repro.models.layers.rope import apply_rope, rope_angles
from repro.sharding import ctx as shard_ctx

__all__ = [
    "init_attention_params", "flash_attention", "decode_attention",
    "decode_attention_q8", "decode_attention_paged",
    "decode_attention_paged_q8", "gqa_forward", "gqa_decode",
    "gqa_decode_paged", "init_kv_cache", "init_paged_kv_pool",
    "quantize_kv_token", "_NEG_INF",
]


def init_attention_params(key, d_model: int, n_heads: int, n_kv_heads: int,
                          head_dim: int, dtype=jnp.float32) -> Dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = d_model ** -0.5
    s_out = (n_heads * head_dim) ** -0.5
    return dict(
        wq=(jax.random.normal(k1, (d_model, n_heads * head_dim)) * s_in
            ).astype(dtype),
        wk=(jax.random.normal(k2, (d_model, n_kv_heads * head_dim)) * s_in
            ).astype(dtype),
        wv=(jax.random.normal(k3, (d_model, n_kv_heads * head_dim)) * s_in
            ).astype(dtype),
        wo=(jax.random.normal(k4, (n_heads * head_dim, d_model)) * s_out
            ).astype(dtype),
    )


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    positions: Optional[jnp.ndarray] = None,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, kv_valid_len: Optional[int] = None,
                    q_chunk: int = 512, kv_chunk: int = 512,
                    impl: Optional[str] = None) -> jnp.ndarray:
    """Online-softmax causal attention.

    q: (B, Sq, H, D); k: (B, Skv, KH, D); v: (B, Skv, KH, Dv) with
    H % KH == 0 (Dv may differ from D, as in MLA).
    ``positions``: (Sq,) runtime token positions (defaults to arange —
    pass the model's position-id input so XLA cannot constant-fold masks).
    ``impl``: attention backend override (``pallas`` | ``jnp``).
    Returns (B, Sq, H, Dv) in q.dtype.
    """
    assert causal and q_offset == 0, "flash path is causal/offset-0 only"
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = d ** -0.5
    if positions is None:
        positions = jnp.arange(sq, dtype=jnp.int32)
    positions = positions.astype(jnp.int32)
    if kv_valid_len is None:
        kv_valid_len = skv
    chunk = min(q_chunk, kv_chunk, sq, skv)
    pad_q = (-sq) % chunk
    pad_kv = (-skv) % chunk
    qs = jnp.pad(q * jnp.asarray(scale, q.dtype),
                 ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp_arr = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    qpos = jnp.pad(positions, (0, pad_q), constant_values=-_FAR)
    # key positions: match q positions where they exist; anything beyond
    # (longer KV, padding, kv_valid_len cutoff) is marked unreachable.
    kpos = jnp.full((skv + pad_kv,), _FAR, jnp.int32)
    kpos = kpos.at[:min(sq, skv)].set(positions[:min(sq, skv)])
    kpos = jnp.where(jnp.arange(kpos.shape[0]) < kv_valid_len, kpos, _FAR)
    if attention_ops.resolve_impl(impl) == "pallas":
        out = attention_ops.flash_pallas(qs, kp_arr, vp, qpos, kpos, window,
                                         chunk)
    else:
        out = flash_reference(qs, kp_arr, vp, qpos, kpos, window, chunk)
    # the q * scale pre-multiplication is in-graph, so its chain rule is
    # handled by the surrounding autodiff.
    return out[:, :sq]


def _grouped_query(q: jnp.ndarray, kh: int) -> jnp.ndarray:
    """(B, 1, H, D) -> pre-scaled, shard-constrained (B, KH, G, D)."""
    b, _, h, d = q.shape
    qf = q.reshape(b, kh, h // kh, d) * jnp.asarray(d ** -0.5, q.dtype)
    return shard_ctx.constrain(qf, "decode_q")  # SSPerf B2


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, kpos: jnp.ndarray,
                     qpos: jnp.ndarray, *,
                     window: Optional[int] = None,
                     impl: Optional[str] = None) -> jnp.ndarray:
    """Single-token attention against a (ring-buffer) KV cache.

    q: (B, 1, H, D); caches: (B, L, KH, D/Dv); kpos: (B, L) absolute
    position of each cache slot (-1 for empty); qpos: (B,).
    """
    b, _, h, _ = q.shape
    qf = _grouped_query(q, k_cache.shape[2])
    if attention_ops.resolve_impl(impl) == "pallas":
        out = attention_ops.decode_pallas(qf, k_cache, v_cache, kpos, qpos,
                                          window=window)
    else:
        out = decode_attention_ref(qf, k_cache, v_cache, kpos, qpos,
                                   window=window)
    return out.reshape(b, 1, h, v_cache.shape[-1]).astype(q.dtype)


def decode_attention_q8(q, k_codes, v_codes, k_scale, v_scale, kpos, qpos, *,
                        window=None, impl: Optional[str] = None):
    """Single-token attention against an int8 cache; scales fold into the
    dots: s = (q . codes) * k_scale;  out = (p * v_scale) . codes."""
    b, _, h, d = q.shape
    qf = _grouped_query(q, k_codes.shape[2])
    if attention_ops.resolve_impl(impl) == "pallas":
        out = attention_ops.decode_q8_pallas(qf, k_codes, v_codes, k_scale,
                                             v_scale, kpos, qpos,
                                             window=window)
    else:
        out = decode_attention_q8_ref(qf, k_codes, v_codes, k_scale, v_scale,
                                      kpos, qpos, window=window)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def decode_attention_paged(q, k_pool, v_pool, pos_pool, page_table, qpos, *,
                           window: Optional[int] = None,
                           impl: Optional[str] = None) -> jnp.ndarray:
    """Single-token attention against a paged KV pool (serving engine).

    q: (S, 1, H, D) one row per scheduler slot; pools: (P, pg, KH, D/Dv)
    with pos_pool (P, pg) absolute positions (-1 empty); page_table:
    (S, npp) physical page per logical page (-1 unallocated); qpos: (S,)
    with -1 marking inactive slots (their output is 0).
    """
    s, _, h, _ = q.shape
    qf = _grouped_query(q, k_pool.shape[2])
    if attention_ops.resolve_impl(impl) == "pallas":
        out = attention_ops.decode_paged_pallas(
            qf, k_pool, v_pool, pos_pool, page_table, qpos, window=window)
    else:
        out = decode_attention_paged_ref(
            qf, k_pool, v_pool, pos_pool, page_table, qpos, window=window)
    return out.reshape(s, 1, h, v_pool.shape[-1]).astype(q.dtype)


def decode_attention_paged_q8(q, k_pool, v_pool, k_scale_pool, v_scale_pool,
                              pos_pool, page_table, qpos, *,
                              window: Optional[int] = None,
                              impl: Optional[str] = None) -> jnp.ndarray:
    """Paged int8-pool decode; scale pools (P, pg, KH) fp16 fold into the
    dots exactly as in ``decode_attention_q8``."""
    s, _, h, d = q.shape
    qf = _grouped_query(q, k_pool.shape[2])
    if attention_ops.resolve_impl(impl) == "pallas":
        out = attention_ops.decode_paged_q8_pallas(
            qf, k_pool, v_pool, k_scale_pool, v_scale_pool, pos_pool,
            page_table, qpos, window=window)
    else:
        out = decode_attention_paged_q8_ref(
            qf, k_pool, v_pool, k_scale_pool, v_scale_pool, pos_pool,
            page_table, qpos, window=window)
    return out.reshape(s, 1, h, d).astype(q.dtype)


def gqa_forward(params: Dict, x: jnp.ndarray, *, n_heads: int,
                n_kv_heads: int, head_dim: int, rope_theta: float,
                positions: jnp.ndarray, causal: bool = True,
                window: Optional[int] = None,
                return_kv: bool = False):
    """Full-sequence attention (train / prefill)."""
    b, s, _ = x.shape
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, s, n_heads, head_dim)
    k = (x @ params["wk"].astype(x.dtype)).reshape(b, s, n_kv_heads, head_dim)
    v = (x @ params["wv"].astype(x.dtype)).reshape(b, s, n_kv_heads, head_dim)
    cos, sin = rope_angles(positions, head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    out = flash_attention(q, k, v, positions=positions, causal=causal,
                          window=window)
    y = out.reshape(b, s, n_heads * head_dim) @ params["wo"].astype(x.dtype)
    if return_kv:
        return y, (k, v)
    return y


def gqa_decode(params: Dict, x: jnp.ndarray, cache: Dict, *, n_heads: int,
               n_kv_heads: int, head_dim: int, rope_theta: float,
               qpos: jnp.ndarray, window: Optional[int] = None):
    """One-token decode. ``cache`` = {k, v, pos} ring buffer; returns
    (y, new_cache)."""
    b, s1, _ = x.shape
    assert s1 == 1
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, 1, n_heads, head_dim)
    k = (x @ params["wk"].astype(x.dtype)).reshape(b, 1, n_kv_heads, head_dim)
    v = (x @ params["wv"].astype(x.dtype)).reshape(b, 1, n_kv_heads, head_dim)
    cos, sin = rope_angles(qpos[:, None], head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    slot = jnp.mod(qpos, cache["k"].shape[1])  # ring buffer
    bidx = jnp.arange(b)
    kpos = cache["pos"].at[bidx, slot].set(qpos)
    if "k_scale" in cache:  # int8-quantized cache (SSPerf D5)
        kc, ks = quantize_kv_token(k[:, 0])
        vc, vs = quantize_kv_token(v[:, 0])
        kc = shard_ctx.constrain_kv(kc)
        vc = shard_ctx.constrain_kv(vc)
        k_cache = cache["k"].at[bidx, slot].set(kc)
        v_cache = cache["v"].at[bidx, slot].set(vc)
        k_scale = cache["k_scale"].at[bidx, slot].set(ks)
        v_scale = cache["v_scale"].at[bidx, slot].set(vs)
        out = decode_attention_q8(q, k_cache, v_cache, k_scale, v_scale,
                                  kpos, qpos, window=window)
        y = out.reshape(b, 1, n_heads * head_dim) @ \
            params["wo"].astype(x.dtype)
        return y, dict(k=k_cache, v=v_cache, k_scale=k_scale,
                       v_scale=v_scale, pos=kpos)
    # align the new token with the cache layout BEFORE the scatter — else
    # GSPMD reshards via a full cache rematerialization (SSPerf B1)
    k_new = shard_ctx.constrain_kv(k[:, 0].astype(cache["k"].dtype))
    v_new = shard_ctx.constrain_kv(v[:, 0].astype(cache["v"].dtype))
    k_cache = cache["k"].at[bidx, slot].set(k_new)
    v_cache = cache["v"].at[bidx, slot].set(v_new)
    out = decode_attention(q, k_cache, v_cache, kpos, qpos, window=window)
    y = out.reshape(b, 1, n_heads * head_dim) @ params["wo"].astype(x.dtype)
    return y, dict(k=k_cache, v=v_cache, pos=kpos)


def gqa_decode_paged(params: Dict, x: jnp.ndarray, cache: Dict, *,
                     n_heads: int, n_kv_heads: int, head_dim: int,
                     rope_theta: float, qpos: jnp.ndarray,
                     page_table: jnp.ndarray,
                     window: Optional[int] = None):
    """One decode tick against a paged KV pool.

    ``cache`` = {k, v, pos[, k_scale, v_scale]} pools of shape
    (P, pg, ...); ``page_table`` (S, npp) maps each slot's logical pages
    to physical ones; ``qpos`` (S,) is the position of the token being
    decoded, -1 for inactive slots.  Inactive (or unallocated) writes are
    routed to the reserved trash page 0 with pos = -1, so they are never
    attended to.  Returns (y, new_cache); the page table is host-owned
    and never mutated here.
    """
    s, s1, _ = x.shape
    assert s1 == 1
    pg = cache["k"].shape[1]
    q = (x @ params["wq"].astype(x.dtype)).reshape(s, 1, n_heads, head_dim)
    k = (x @ params["wk"].astype(x.dtype)).reshape(s, 1, n_kv_heads, head_dim)
    v = (x @ params["wv"].astype(x.dtype)).reshape(s, 1, n_kv_heads, head_dim)
    cos, sin = rope_angles(qpos[:, None], head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    active = qpos >= 0
    qp = jnp.maximum(qpos, 0)
    phys = page_table[jnp.arange(s), qp // pg]
    phys = jnp.where(active & (phys >= 0), phys, 0)
    off = qp % pg
    pos_pool = cache["pos"].at[phys, off].set(jnp.where(active, qpos, -1))
    if "k_scale" in cache:  # int8-quantized pool
        kc, ks = quantize_kv_token(k[:, 0])
        vc, vs = quantize_kv_token(v[:, 0])
        k_pool = cache["k"].at[phys, off].set(kc)
        v_pool = cache["v"].at[phys, off].set(vc)
        k_scale = cache["k_scale"].at[phys, off].set(ks)
        v_scale = cache["v_scale"].at[phys, off].set(vs)
        out = decode_attention_paged_q8(q, k_pool, v_pool, k_scale, v_scale,
                                        pos_pool, page_table, qpos,
                                        window=window)
        y = out.reshape(s, 1, n_heads * head_dim) @ \
            params["wo"].astype(x.dtype)
        return y, dict(k=k_pool, v=v_pool, k_scale=k_scale,
                       v_scale=v_scale, pos=pos_pool)
    k_pool = cache["k"].at[phys, off].set(k[:, 0].astype(cache["k"].dtype))
    v_pool = cache["v"].at[phys, off].set(v[:, 0].astype(cache["v"].dtype))
    out = decode_attention_paged(q, k_pool, v_pool, pos_pool, page_table,
                                 qpos, window=window)
    y = out.reshape(s, 1, n_heads * head_dim) @ params["wo"].astype(x.dtype)
    return y, dict(k=k_pool, v=v_pool, pos=pos_pool)


def init_kv_cache(batch: int, length: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16, bits: int = 16) -> Dict:
    """bits=8: int8-quantized cache (BEYOND-PAPER: the paper's activation
    quantization applied to the KV cache — the decode-roofline's dominant
    memory; EXPERIMENTS.md SSPerf D5).  Codes + per-(token, head) fp16
    absmax scales; the scales fold into the attention dots, so no
    dequantized copy is ever stored."""
    if bits == 8:
        return dict(
            k=jnp.zeros((batch, length, n_kv_heads, head_dim), jnp.int8),
            v=jnp.zeros((batch, length, n_kv_heads, head_dim), jnp.int8),
            k_scale=jnp.zeros((batch, length, n_kv_heads), jnp.float16),
            v_scale=jnp.zeros((batch, length, n_kv_heads), jnp.float16),
            pos=jnp.full((batch, length), -1, jnp.int32),
        )
    return dict(
        k=jnp.zeros((batch, length, n_kv_heads, head_dim), dtype),
        v=jnp.zeros((batch, length, n_kv_heads, head_dim), dtype),
        pos=jnp.full((batch, length), -1, jnp.int32),
    )


def init_paged_kv_pool(n_pages: int, page_size: int, n_kv_heads: int,
                       head_dim: int, dtype=jnp.bfloat16,
                       bits: int = 16) -> Dict:
    """Paged twin of ``init_kv_cache``: (P, pg, ...) pools shared by every
    request, indexed through per-request page tables.  Physical page 0 is
    reserved as the trash page (inactive-slot writes land there and its
    pos stays -1), so allocators must hand out pages 1..P-1 only."""
    if bits == 8:
        return dict(
            k=jnp.zeros((n_pages, page_size, n_kv_heads, head_dim),
                        jnp.int8),
            v=jnp.zeros((n_pages, page_size, n_kv_heads, head_dim),
                        jnp.int8),
            k_scale=jnp.zeros((n_pages, page_size, n_kv_heads),
                              jnp.float16),
            v_scale=jnp.zeros((n_pages, page_size, n_kv_heads),
                              jnp.float16),
            pos=jnp.full((n_pages, page_size), -1, jnp.int32),
        )
    return dict(
        k=jnp.zeros((n_pages, page_size, n_kv_heads, head_dim), dtype),
        v=jnp.zeros((n_pages, page_size, n_kv_heads, head_dim), dtype),
        pos=jnp.full((n_pages, page_size), -1, jnp.int32),
    )


def quantize_kv_token(x: jnp.ndarray):
    """(..., KH, hd) -> (int8 codes, fp16 absmax scale over hd)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-8
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale.astype(jnp.float16)
