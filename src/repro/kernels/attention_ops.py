"""Backend dispatch for the attention kernels.

Mirrors the ``kernels/ops.py`` idiom for the compressor: layout handling
(transposes to the kernels' (B, H, S, D) form), ``interpret=True`` off
TPU, and implementation selection.

Selection order (``resolve_impl``):
  1. explicit ``impl=`` keyword threaded through the public APIs in
     ``models/layers/attention.py`` (used by parity tests / benchmarks);
  2. the ``REPRO_ATTN_IMPL`` environment variable (``pallas`` | ``jnp``)
     for zero-code A/B flips;
  3. default: Pallas on TPU backends, the jnp reference elsewhere (the
     interpreter is correct but slow, so CPU CI stays on jnp unless a
     test opts in).

No silent fallback: once the Pallas backend is chosen, a shape the
compiled TPU kernel cannot take raises (:func:`require_compiled`) rather
than quietly running the jnp reference — a run on the chip either uses
the kernels or says why not.  Interpret mode takes any shape.

``flash_pallas`` is the Pallas twin of ``attention_ref.flash_reference``
— same operand contract (pre-scaled q, sentinel positions, chunk-aligned
padding), same custom-VJP residuals (out, m, l), so ``flash_attention``
can swap them 1:1 with zero call-site churn.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import attention_ref, decode_kernel, flash_kernel
from repro.utils.dispatch import resolve_backend_impl

_VALID_IMPLS = ("pallas", "jnp")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def require_compiled(ok: bool, what: str) -> None:
    """Refuse, on TPU, a shape the compiled kernel cannot take."""
    if not ok and not _interpret():
        raise ValueError(
            f"{what}: the compiled TPU kernel cannot take this shape; pass "
            f"impl='jnp' to run the jnp reference instead")


def resolve_impl(impl: Optional[str] = None) -> str:
    """Resolve the attention backend (see module docstring for order)."""
    return resolve_backend_impl(impl, "REPRO_ATTN_IMPL", "attention",
                                _VALID_IMPLS)


# ---------------------------------------------------------------------------
# train / prefill flash attention
# ---------------------------------------------------------------------------

def _to_bhsd(x):
    return x.transpose(0, 2, 1, 3)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def flash_pallas(q, k, v, qpos, kpos, window, chunk):
    """Pallas flash attention on pre-scaled, chunk-padded operands.

    Same contract as ``attention_ref.flash_reference``: q (B, Sq, H, D)
    pre-multiplied by 1/sqrt(D), k/v (B, Skv, KH, D/Dv), qpos/kpos with
    +/-2^30 sentinels.  Returns (B, Sq, H, Dv) in q.dtype.
    """
    out, _, _ = _flash_pallas_fwd_impl(q, k, v, qpos, kpos, window, chunk)
    return out


def _flash_pallas_fwd_impl(q, k, v, qpos, kpos, window, chunk):
    require_compiled(flash_kernel.compiles(chunk, q.shape[1], k.shape[1]),
                     f"flash attention with chunk {chunk} over "
                     f"{q.shape[1]} queries / {k.shape[1]} keys")
    outs, m, l = flash_kernel.forward(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
        qpos.reshape(-1, 1), kpos.reshape(1, -1),
        window=window, block=chunk, interpret=_interpret())
    return _to_bhsd(outs).astype(q.dtype), m, l


def _flash_pallas_vjp_fwd(q, k, v, qpos, kpos, window, chunk):
    out, m, l = _flash_pallas_fwd_impl(q, k, v, qpos, kpos, window, chunk)
    return out, (q, k, v, qpos, kpos, out, m, l)


def _flash_pallas_vjp_bwd(window, chunk, res, gout):
    q, k, v, qpos, kpos, out, m, l = res
    # delta = rowsum(dO * O) — the only O(S) recomputation input the
    # backward kernels need beyond (m, l).
    di = jnp.einsum("bshd,bshd->bsh", gout.astype(jnp.float32),
                    out.astype(jnp.float32)).transpose(0, 2, 1)[..., None]
    qt, kt, vt = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    got = _to_bhsd(gout)
    qp2, kp2 = qpos.reshape(-1, 1), kpos.reshape(1, -1)
    common = dict(window=window, block=chunk, interpret=_interpret())
    dq = flash_kernel.backward_dq(qt, kt, vt, got, m, l, di, qp2, kp2,
                                  **common)
    dk, dvv = flash_kernel.backward_dkv(qt, kt, vt, got, m, l, di, qp2, kp2,
                                        **common)
    return (_to_bhsd(dq).astype(q.dtype), _to_bhsd(dk).astype(k.dtype),
            _to_bhsd(dvv).astype(v.dtype), jnp.zeros_like(qpos),
            jnp.zeros_like(kpos))


flash_pallas.defvjp(_flash_pallas_vjp_fwd, _flash_pallas_vjp_bwd)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode_block(length: int) -> int:
    """Cache-length block for the ring kernels (one block in interpret
    mode when no VMEM-safe divisor exists)."""
    block = decode_kernel.pick_block(length)
    if _interpret():
        return block or length
    require_compiled(block is not None
                     and decode_kernel.compiles(block, length),
                     f"decode over a {length}-slot cache (block {block})")
    return block


def decode_pallas(qf, k_cache, v_cache, kpos, qpos, *, window=None):
    """Fused single-token decode.  qf (B, KH, G, D) pre-scaled; caches in
    the native (B, L, KH, D/Dv) ring-buffer layout.  Returns
    (B, KH, G, Dv) fp32."""
    return decode_kernel.decode(
        qf, k_cache, v_cache, kpos, qpos.astype(jnp.int32), window=window,
        block=_decode_block(k_cache.shape[1]), interpret=_interpret())


def decode_q8_pallas(qf, k_codes, v_codes, k_scale, v_scale, kpos, qpos, *,
                     window=None):
    """Fused int8-cache decode; folds the absmax scales into the dots
    inside the kernel.  The (B, L, KH) fp16 scales are cast/transposed to
    (B, KH, L) fp32 here — they are D-times smaller than the codes."""
    ks = k_scale.astype(jnp.float32).transpose(0, 2, 1)
    vs = v_scale.astype(jnp.float32).transpose(0, 2, 1)
    return decode_kernel.decode_q8(
        qf, k_codes, v_codes, ks, vs, kpos, qpos.astype(jnp.int32),
        window=window, block=_decode_block(k_codes.shape[1]),
        interpret=_interpret())


def decode_paged_pallas(qf, k_pool, v_pool, pos_pool, page_table, qpos, *,
                        window=None):
    """Paged-pool decode (serving engine).  The grid is (S, nb): each step
    reads a block of up to 32 pages (at most 512 positions, within a VMEM
    budget; ``decode_kernel.paged_block``), straight from their physical
    location — no gathered contiguous cache copy.  A slot reads only its
    live pages (0 .. qpos // pg, those a window reaches); the others are
    neither copied nor attended.  qf (S, KH, G, D) pre-scaled; pools
    (P, pg, KH, D/Dv); page_table (S, npp) with -1 for unallocated; qpos
    (S,).  Returns (S, KH, G, Dv) fp32."""
    return decode_kernel.decode_paged(
        qf, k_pool, v_pool, pos_pool, page_table.astype(jnp.int32),
        qpos.astype(jnp.int32), window=window, interpret=_interpret())


def decode_paged_q8_pallas(qf, k_pool, v_pool, k_scale_pool, v_scale_pool,
                           pos_pool, page_table, qpos, *, window=None):
    """Paged int8-pool decode; absmax scales fold into the dots in-kernel.
    Scale pools arrive in the engine's native (P, pg, KH) fp16 layout and
    are cast/transposed to (P, KH, pg) fp32 here (D-times smaller than the
    codes)."""
    ks = k_scale_pool.astype(jnp.float32).transpose(0, 2, 1)
    vs = v_scale_pool.astype(jnp.float32).transpose(0, 2, 1)
    return decode_kernel.decode_paged_q8(
        qf, k_pool, v_pool, ks, vs, pos_pool, page_table.astype(jnp.int32),
        qpos.astype(jnp.int32), window=window, interpret=_interpret())
