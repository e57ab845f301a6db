"""Operations and bytes, counted from shapes, for the roofline and MFU
metrics.

The parameter arithmetic follows the usual dense-transformer count (the
program's ``launch/roofline.param_counts`` for a dense GQA stack, copied
so the yardstick cannot move): a layer holds ``d hd (2 H + 2 KH)``
attention and ``3 d f`` SwiGLU weights; the head ``d V``; the connector
``dv dc + dc d``; the codec ``2 d^2``.  A matrix product of ``n`` weights
costs ``2 n`` per token forward and ``6 n`` forward and backward.
Attention adds ``4 T hd H`` per query at context ``T`` forward (scores
and values), half of ``T^2`` under a causal mask for a whole sequence.
Recomputation (remat) is never counted.
"""
from __future__ import annotations

from typing import Dict


def layer_weights(c) -> float:
    return (c.d_model * c.head_dim * (2 * c.n_heads + 2 * c.n_kv_heads)
            + 3 * c.d_model * c.d_ff)


def token_weights(c) -> float:
    """Weights every sequence position passes through: the layers, the
    codec and the head."""
    codec = 2 * c.d_model ** 2 if c.learnable_codec else 0
    return c.n_layers * layer_weights(c) + codec + c.d_model * c.vocab_size


def connector_weights(c) -> float:
    dc = c.d_connector or c.d_model
    return c.d_vision * dc + dc * c.d_model


def causal_attention_fwd(c, seq: int) -> float:
    """Forward attention operations of one causal sequence, all layers."""
    return 2.0 * seq * seq * c.head_dim * c.n_heads * c.n_layers


def train_step_flops(c, batch: int, seq: int) -> float:
    """Model operations of one training step (forward and backward)."""
    per_row = (6.0 * seq * token_weights(c)
               + 6.0 * c.n_image_tokens * connector_weights(c)
               + 3.0 * causal_attention_fwd(c, seq))
    return batch * per_row


def prefill_flops(c, prompt_tokens: int) -> float:
    """One request's prefill over its image and prompt (no padding)."""
    t = c.n_image_tokens + prompt_tokens
    return (2.0 * t * token_weights(c)
            + 2.0 * c.n_image_tokens * connector_weights(c)
            + causal_attention_fwd(c, t))


def decode_flops(c, context: int) -> float:
    """One decoded token attending over ``context`` positions."""
    return (2.0 * token_weights(c)
            + 4.0 * context * c.head_dim * c.n_heads * c.n_layers)


# -- kernels -----------------------------------------------------------------

def flash_fwd(b: int, h: int, kh: int, s: int, d: int) -> Dict[str, float]:
    """Causal flash forward: QK^T and PV over the lower triangle; reads
    q, k, v once and writes o (bf16) and the row statistics (fp32)."""
    flops = 2.0 * b * h * s * s * d
    byts = 2.0 * b * s * d * (2 * h + 2 * kh) + 8.0 * b * h * s
    return dict(flops=flops, bytes=byts)


def flash_dq(b: int, h: int, kh: int, s: int, d: int) -> Dict[str, float]:
    """Backward for dq: scores again, dP = dO V^T, dQ = dS K (causal)."""
    flops = 3.0 * b * h * s * s * d
    byts = 2.0 * b * s * d * (3 * h + 2 * kh) + 12.0 * b * h * s
    return dict(flops=flops, bytes=byts)


def flash_dkv(b: int, h: int, kh: int, s: int, d: int) -> Dict[str, float]:
    """Backward for dk, dv: scores, dP, dV = P^T dO, dK = dS^T Q."""
    flops = 4.0 * b * h * s * s * d
    byts = 2.0 * b * s * d * (2 * h + 4 * kh) + 12.0 * b * h * s
    return dict(flops=flops, bytes=byts)


def paged_decode(c, context: int) -> Dict[str, float]:
    """One query token of one layer over ``context`` cached positions:
    the K and V it reads, q in, out back, and the products."""
    kv = 2.0 * context * c.n_kv_heads * c.head_dim * 2
    qo = 2.0 * 2 * c.n_heads * c.head_dim
    return dict(flops=4.0 * context * c.head_dim * c.n_heads,
                bytes=kv + qo + 4.0 * context)


def roofline_share(flops: float, byts: float, seconds: float,
                   peaks: Dict) -> Dict:
    """Least time at the chip's peaks over the time taken, in %, and
    which bound binds."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    return dict(share=100.0 * max(t_c, t_m) / seconds,
                bound="compute" if t_c >= t_m else "memory")
