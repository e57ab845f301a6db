"""Pallas TPU flash-attention kernels (forward + backward).

Attention is the dominant FLOP and HBM-traffic path in train, prefill,
the split pipeline and decode; the jnp reference
(``kernels/attention_ref.py``) pays scan-carry materialization, per-chunk
``lax.cond`` dispatch and fp32 accumulator round-trips through HBM that a
fused kernel keeps in VMEM.  Three kernels:

* ``forward``  — online softmax over (q-block, kv-block) grid cells with
  the kv axis innermost; the fp32 (m, l, acc) state lives in VMEM scratch
  across the kv sweep and only the normalized output + per-row (m, l)
  ever reach HBM.  Returns ``(out fp32, m, l)``.
* ``backward_dq`` — same sweep; recomputes per-block probabilities from
  the saved (m, l) exactly like the jnp VJP, so no (Sq x Skv) tensor is
  ever materialized.
* ``backward_dkv`` — kv-major sweep with the (GQA group, q-block) axes
  innermost, accumulating dK/dV for each KV head in VMEM scratch.

Masking uses RUNTIME position vectors (qpos along sublanes, kpos along
lanes) rather than trace-time iota — the same contract as the reference:
the sentinels (+/-2^30) encode padding and ``kv_valid_len``, and arbitrary
position ids keep working.  Fully-masked grid cells are skipped with
``pl.when`` on block min/max positions (splash-attention style), which
preserves the causal ~2x and sliding-window O(S*W) savings.

Row state (m, l, delta) is carried at lane-width 1 — (bq, 1) fp32 tiles —
instead of the 128-wide replicated idiom: the HBM-level residuals stay
(B, H, Sq, 1) so the train-memory story of the custom VJP is unchanged.

Validated on CPU with interpret=True against attention_ref (see
tests/test_attention_pallas.py); layout is (B, H, S, D) inside the
kernels, transposed at the ``attention_ops`` boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_TRANS_B = (((1,), (1,)), ((), ()))   # (a, b) -> a @ b.T
_TRANS_A = (((0,), (0,)), ((), ()))   # (a, b) -> a.T @ b
_PLAIN = (((1,), (0,)), ((), ()))     # (a, b) -> a @ b


def compiles(block: int, sq: int, skv: int) -> bool:
    """Whether the compiled TPU kernels take ``block`` over padded query
    / key lengths ``sq`` / ``skv``: the (1, block) key-position tiles put
    the block on the lane axis, so it must be a multiple of 128 unless
    one block spans both sequences."""
    return block % 128 == 0 or sq == block == skv


def _visible(qp, kp, window):
    """Block-level skip predicate from runtime position extrema."""
    vis = jnp.min(kp) <= jnp.max(qp)
    if window is not None:
        vis = jnp.logical_and(vis, jnp.max(kp) > jnp.min(qp) - window)
    return vis


def _mask(qp, kp, window):
    """(bq, bkv) mask from qp (bq, 1) / kp (1, bkv) runtime positions."""
    m = kp <= qp
    if window is not None:
        m = jnp.logical_and(m, qp - kp < window)
    return m


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref,
                o_ref, m_ref, l_ref, m_s, l_s, acc_s, *, window, nkv):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    qp = qpos_ref[...]  # (bq, 1) int32
    kp = kpos_ref[...]  # (1, bkv) int32

    @pl.when(_visible(qp, kp, window))
    def _compute():
        q = q_ref[0, 0]  # (bq, D), pre-scaled
        k = k_ref[0, 0]  # (bkv, D)
        s = jax.lax.dot_general(q, k, _TRANS_B,
                                preferred_element_type=jnp.float32)
        s = jnp.where(_mask(qp, kp, window), s, _NEG_INF)
        m_prev = m_s[...]  # (bq, 1)
        l_prev = l_s[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_next)
        corr = jnp.exp(m_prev - m_next)
        l_s[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_next
        v = v_ref[0, 0]  # (bkv, Dv)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, _PLAIN,
                                 preferred_element_type=jnp.float32)
        acc_s[...] = acc_s[...] * corr + pv

    @pl.when(j == nkv - 1)
    def _finalize():
        l_fin = l_s[...]
        o_ref[0, 0] = acc_s[...] / jnp.maximum(l_fin, 1e-30)
        m_ref[0, 0] = m_s[...]
        l_ref[0, 0] = l_fin


def forward(q, k, v, qpos, kpos, *, window, block, interpret):
    """q: (B, H, Sq, D) pre-scaled; k/v: (B, KH, Skv, D/Dv); qpos (Sq, 1),
    kpos (1, Skv) int32 with sentinel padding; Sq/Skv multiples of
    ``block``.  Returns (out fp32 (B, H, Sq, Dv), m, l (B, H, Sq, 1))."""
    b, h, sq, d = q.shape
    kh, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kh
    nq, nkv = sq // block, skv // block
    grid = (b, h, nq, nkv)
    kernel = functools.partial(_fwd_kernel, window=window, nkv=nkv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, 1), lambda b_, h_, i, j: (i, 0)),
            pl.BlockSpec((1, block), lambda b_, h_, i, j: (0, j)),
            pl.BlockSpec((1, 1, block, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block, d),
                         lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block, dv),
                         lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block, dv),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qpos, kpos, q, k, v)


# ---------------------------------------------------------------------------
# backward: dQ (q-major sweep, kv innermost)
# ---------------------------------------------------------------------------

def _dq_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, go_ref, m_ref, l_ref,
               di_ref, dq_ref, dq_s, *, window, nkv):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    qp = qpos_ref[...]
    kp = kpos_ref[...]

    @pl.when(_visible(qp, kp, window))
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(q, k, _TRANS_B,
                                preferred_element_type=jnp.float32)
        s = jnp.where(_mask(qp, kp, window), s, _NEG_INF)
        linv = 1.0 / jnp.maximum(l_ref[0, 0], 1e-30)  # (bq, 1)
        p = jnp.exp(s - m_ref[0, 0]) * linv
        go = go_ref[0, 0]  # (bq, Dv)
        v = v_ref[0, 0]    # (bkv, Dv)
        dp = jax.lax.dot_general(go, v, _TRANS_B,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, 0])
        dq_s[...] += jax.lax.dot_general(ds.astype(k.dtype), k, _PLAIN,
                                         preferred_element_type=jnp.float32)

    @pl.when(j == nkv - 1)
    def _finalize():
        dq_ref[0, 0] = dq_s[...]


def backward_dq(q, k, v, go, m, l, di, qpos, kpos, *, window, block,
                interpret):
    """Inputs in (B, H/KH, S, ...) layout (see ``forward``); go
    (B, H, Sq, Dv); m/l/di (B, H, Sq, 1) fp32.  Returns dq fp32
    (B, H, Sq, D) w.r.t. the pre-scaled query."""
    b, h, sq, d = q.shape
    kh, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kh
    nq, nkv = sq // block, skv // block
    kernel = functools.partial(_dq_kernel, window=window, nkv=nkv)
    qo_map = lambda b_, h_, i, j: (b_, h_, i, 0)
    kv_map = lambda b_, h_, i, j: (b_, h_ // g, j, 0)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((block, 1), lambda b_, h_, i, j: (i, 0)),
            pl.BlockSpec((1, block), lambda b_, h_, i, j: (0, j)),
            pl.BlockSpec((1, 1, block, d), qo_map),
            pl.BlockSpec((1, 1, block, d), kv_map),
            pl.BlockSpec((1, 1, block, dv), kv_map),
            pl.BlockSpec((1, 1, block, dv), qo_map),
            pl.BlockSpec((1, 1, block, 1), qo_map),
            pl.BlockSpec((1, 1, block, 1), qo_map),
            pl.BlockSpec((1, 1, block, 1), qo_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block, d), qo_map),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        interpret=interpret,
    )(qpos, kpos, q, k, v, go, m, l, di)


# ---------------------------------------------------------------------------
# backward: dK/dV (kv-major sweep, (group, q) innermost)
# ---------------------------------------------------------------------------

def _dkv_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, go_ref, m_ref,
                l_ref, di_ref, dk_ref, dv_ref, dk_s, dv_s, *, window,
                ng, nq):
    g_idx = pl.program_id(3)
    i = pl.program_id(4)

    @pl.when(jnp.logical_and(g_idx == 0, i == 0))
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    qp = qpos_ref[...]
    kp = kpos_ref[...]

    @pl.when(_visible(qp, kp, window))
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(q, k, _TRANS_B,
                                preferred_element_type=jnp.float32)
        s = jnp.where(_mask(qp, kp, window), s, _NEG_INF)
        linv = 1.0 / jnp.maximum(l_ref[0, 0], 1e-30)
        p = jnp.exp(s - m_ref[0, 0]) * linv  # (bq, bkv)
        go = go_ref[0, 0]
        v = v_ref[0, 0]
        dv_s[...] += jax.lax.dot_general(p.astype(go.dtype), go, _TRANS_A,
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(go, v, _TRANS_B,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, 0])
        dk_s[...] += jax.lax.dot_general(ds.astype(q.dtype), q, _TRANS_A,
                                         preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(g_idx == ng - 1, i == nq - 1))
    def _finalize():
        dk_ref[0, 0] = dk_s[...]
        dv_ref[0, 0] = dv_s[...]


def backward_dkv(q, k, v, go, m, l, di, qpos, kpos, *, window, block,
                 interpret):
    """Returns (dk, dv) fp32 in (B, KH, Skv, D/Dv) layout; the GQA group
    sum happens in VMEM scratch across the (group, q-block) grid axes."""
    b, h, sq, d = q.shape
    kh, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kh
    nq, nkv = sq // block, skv // block
    kernel = functools.partial(_dkv_kernel, window=window, ng=g, nq=nq)
    qo_map = lambda b_, kh_, j, g_, i: (b_, kh_ * g + g_, i, 0)
    kv_map = lambda b_, kh_, j, g_, i: (b_, kh_, j, 0)
    return pl.pallas_call(
        kernel,
        grid=(b, kh, nkv, g, nq),
        in_specs=[
            pl.BlockSpec((block, 1), lambda b_, kh_, j, g_, i: (i, 0)),
            pl.BlockSpec((1, block), lambda b_, kh_, j, g_, i: (0, j)),
            pl.BlockSpec((1, 1, block, d), qo_map),
            pl.BlockSpec((1, 1, block, d), kv_map),
            pl.BlockSpec((1, 1, block, dv), kv_map),
            pl.BlockSpec((1, 1, block, dv), qo_map),
            pl.BlockSpec((1, 1, block, 1), qo_map),
            pl.BlockSpec((1, 1, block, 1), qo_map),
            pl.BlockSpec((1, 1, block, 1), qo_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block, d), kv_map),
            pl.BlockSpec((1, 1, block, dv), kv_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, skv, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, skv, dv), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, d), jnp.float32),
            pltpu.VMEM((block, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qpos, kpos, q, k, v, go, m, l, di)
