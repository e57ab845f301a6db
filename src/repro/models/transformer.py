"""Composable decoder stack interpreting ``ArchConfig.block_pattern()``.

Block types: ``dense`` (GQA/MLA attention + SwiGLU), ``moe`` (attention +
mixture-of-experts), ``mamba2`` (SSD), ``rwkv6`` (time-mix + channel-mix),
``shared_attn`` (Zamba2's parameter-shared attention block over
concat(hidden, initial embedding)).

Consecutive identical layers are *stacked* (leading layer axis) and executed
through ``repro.models.stack`` — the unified stack executor that owns the
scan / remat / sqrt-L-remat / cache-collection policies (one trace per
segment instead of one per layer, which keeps 62-layer dry-run compiles
tractable).  The split-learning cut never falls inside a segment (see
``ArchConfig.segments``); the compressor (quantize -> wire -> dequantize,
STE) runs between the client and server segment lists.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import utils
from repro.configs.base import ArchConfig
from repro.core import split as split_mod
from repro.models import stack as stack_mod
from repro.models.layers import attention as attn_mod
from repro.models.layers import embedding as emb_mod
from repro.models.layers import mamba2 as mamba_mod
from repro.models.layers import mla as mla_mod
from repro.models.layers import rwkv6 as rwkv_mod
from repro.models.layers.mlp import (init_mlp_params, init_swiglu_params,
                                     mlp_forward, swiglu_forward)
from repro.models.layers.moe import init_moe_params, moe_forward
from repro.models.layers.norms import rms_norm
from repro.sharding import ctx as shard_ctx

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


def pdtype(cfg: ArchConfig):
    return DTYPES[cfg.param_dtype]


def cdtype(cfg: ArchConfig):
    return DTYPES[cfg.compute_dtype]


# ---------------------------------------------------------------------------
# RWKV channel mix (the FFN half of an RWKV block)
# ---------------------------------------------------------------------------

def init_cmix_params(key, d_model: int, d_ff: int, dtype=jnp.float32) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s = d_model ** -0.5
    return dict(
        mu_k=jnp.full((d_model,), 0.5, dtype),
        mu_r=jnp.full((d_model,), 0.5, dtype),
        wk=(jax.random.normal(k1, (d_model, d_ff)) * s).astype(dtype),
        wv=(jax.random.normal(k2, (d_ff, d_model)) * d_ff ** -0.5
            ).astype(dtype),
        wr=(jax.random.normal(k3, (d_model, d_model)) * s).astype(dtype),
    )


def cmix_forward(p: Dict, x: jnp.ndarray, x_prev: jnp.ndarray) -> jnp.ndarray:
    dt = x.dtype
    xk = x + (x_prev - x) * p["mu_k"].astype(dt)
    xr = x + (x_prev - x) * p["mu_r"].astype(dt)
    k = jnp.square(jax.nn.relu(xk @ p["wk"].astype(dt)))
    return jax.nn.sigmoid(xr @ p["wr"].astype(dt)) * (k @ p["wv"].astype(dt))


# ---------------------------------------------------------------------------
# per-block init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ArchConfig, d_model: int, dtype):
    if cfg.attn_type == "mla":
        return mla_mod.init_mla_params(
            key, d_model, cfg.n_heads, q_lora_rank=cfg.q_lora_rank,
            kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
            qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
            dtype=dtype)
    return attn_mod.init_attention_params(
        key, d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, dtype=dtype)


def init_block_params(key, cfg: ArchConfig, block_type: str) -> Dict:
    dtype = pdtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if block_type in ("dense", "moe"):
        p = dict(ln1=jnp.ones((d,), dtype), ln2=jnp.ones((d,), dtype),
                 attn=_init_attn(ks[0], cfg, d, dtype))
        if block_type == "moe":
            p["ffn"] = init_moe_params(
                ks[1], d, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff,
                n_shared_experts=cfg.n_shared_experts,
                dense_residual_d_ff=cfg.d_ff if cfg.dense_residual else 0,
                dtype=dtype)
        else:
            p["ffn"] = init_swiglu_params(ks[1], d, cfg.d_ff, dtype)
        return p
    if block_type == "mamba2":
        return dict(ln=jnp.ones((d,), dtype),
                    mixer=mamba_mod.init_mamba2_params(
                        ks[0], d, expand=cfg.ssm_expand,
                        headdim=cfg.ssm_headdim, d_state=cfg.ssm_state,
                        dtype=dtype))
    if block_type == "rwkv6":
        return dict(ln1=jnp.ones((d,), dtype), ln2=jnp.ones((d,), dtype),
                    tmix=rwkv_mod.init_rwkv6_params(
                        ks[0], d, cfg.rwkv_head_dim, dtype=dtype),
                    cmix=init_cmix_params(ks[1], d, cfg.d_ff, dtype))
    if block_type == "shared_attn":
        return dict(
            w_in=(jax.random.normal(ks[0], (2 * d, d)) * (2 * d) ** -0.5
                  ).astype(dtype),
            ln1=jnp.ones((d,), dtype), ln2=jnp.ones((d,), dtype),
            attn=_init_attn(ks[1], cfg, d, dtype),
            ffn=init_swiglu_params(ks[2], d, cfg.d_ff, dtype))
    raise ValueError(block_type)


# ---------------------------------------------------------------------------
# per-block forward (full sequence) and decode (one token)
# ---------------------------------------------------------------------------

_EMPTY_AUX = dict(load_balance=jnp.zeros((), jnp.float32),
                  router_z=jnp.zeros((), jnp.float32),
                  drop_fraction=jnp.zeros((), jnp.float32))


def _attn_forward(cfg: ArchConfig, p, x, positions, window, return_kv=False):
    if cfg.attn_type == "mla":
        return mla_mod.mla_forward(
            p, x, n_heads=cfg.n_heads, qk_nope_dim=cfg.qk_nope_dim,
            qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
            kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
            positions=positions, window=window, return_kv=return_kv)
    return attn_mod.gqa_forward(
        p, x, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        positions=positions, window=window, return_kv=return_kv)


def block_forward(cfg: ArchConfig, block_type: str, p: Dict, x: jnp.ndarray,
                  *, positions, window, emb0=None,
                  collect_cache: Optional[int] = None):
    """Full-sequence block. Returns (x, aux, cache_or_None)."""
    aux = dict(_EMPTY_AUX)
    cache = None
    # Tie positions to the layer input: without this barrier XLA hoists the
    # (layer-invariant) attention-mask computation out of the layer scan as
    # a precomputed (nq x nkv x ...) table — gigabytes per device
    # (EXPERIMENTS.md SSPerf).  grad_safe_barrier keeps the pin on BOTH
    # the forward and backward scans (raw optimization_barrier has no
    # differentiation rule and would kill jax.grad through the stack).
    x, positions = utils.grad_safe_barrier((x, positions))
    if block_type in ("dense", "moe", "shared_attn"):
        if block_type == "shared_attn":
            xin = jnp.concatenate([x, emb0], axis=-1) @ \
                p["w_in"].astype(x.dtype)
        else:
            xin = x
        h = rms_norm(xin, p["ln1"], cfg.norm_eps)
        if collect_cache is not None:
            a, kv = _attn_forward(cfg, p["attn"], h, positions, window,
                                  return_kv=True)
            cache = _fill_kv_cache(cfg, kv, collect_cache, positions)
        else:
            a = _attn_forward(cfg, p["attn"], h, positions, window)
        xin = xin + a
        h2 = rms_norm(xin, p["ln2"], cfg.norm_eps)
        if block_type == "moe":
            f, moe_aux = moe_forward(p["ffn"], h2, top_k=cfg.moe_top_k,
                                     capacity_factor=cfg.capacity_factor)
            aux.update({k: jnp.asarray(v, jnp.float32)
                        for k, v in moe_aux.items()})
        else:
            f = swiglu_forward(p["ffn"], h2)
        out = xin + f
        if block_type == "shared_attn":
            out = x + out  # residual around the whole shared block
        return shard_ctx.constrain(out, "hidden"), aux, cache
    if block_type == "mamba2":
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        if collect_cache is not None:
            y, cache = mamba_mod.mamba2_forward(
                p["mixer"], h, expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
                d_state=cfg.ssm_state, return_state=True)
        else:
            y = mamba_mod.mamba2_forward(
                p["mixer"], h, expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
                d_state=cfg.ssm_state)
        return shard_ctx.constrain(x + y, "hidden"), aux, cache
    if block_type == "rwkv6":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if collect_cache is not None:
            y, tcache = rwkv_mod.rwkv6_forward(
                p["tmix"], h, head_dim=cfg.rwkv_head_dim, return_state=True)
        else:
            y = rwkv_mod.rwkv6_forward(p["tmix"], h,
                                       head_dim=cfg.rwkv_head_dim)
            tcache = None
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        h2_prev = jnp.pad(h2, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        x = x + cmix_forward(p["cmix"], h2, h2_prev)
        if collect_cache is not None:
            cache = dict(tmix=tcache, cmix_last=h2[:, -1:])
        return shard_ctx.constrain(x, "hidden"), aux, cache
    raise ValueError(block_type)


def _fill_kv_cache(cfg: ArchConfig, kv, cache_len: int, positions):
    """Place prefill K/V into a ring buffer of ``cache_len`` slots."""
    if cfg.attn_type == "mla":
        ckv, krope = kv  # (B, S, kv_lora), (B, S, dr)
        b, s = ckv.shape[:2]
        cache = mla_mod.init_mla_cache(b, cache_len, cfg.kv_lora_rank,
                                       cfg.qk_rope_dim,
                                       dtype=ckv.dtype)
        keep = min(s, cache_len)
        pos = positions[-keep:]
        slots = jnp.mod(pos, cache_len)
        cache["ckv"] = cache["ckv"].at[:, slots].set(ckv[:, -keep:])
        cache["krope"] = cache["krope"].at[:, slots].set(krope[:, -keep:])
        cache["pos"] = cache["pos"].at[:, slots].set(
            jnp.broadcast_to(pos, (b, keep)))
        return cache
    k, v = kv  # (B, S, KH, hd)
    b, s = k.shape[:2]
    cache = attn_mod.init_kv_cache(b, cache_len, cfg.n_kv_heads,
                                   cfg.head_dim, dtype=k.dtype,
                                   bits=cfg.kv_cache_bits)
    keep = min(s, cache_len)
    pos = positions[-keep:]
    slots = jnp.mod(pos, cache_len)
    if cfg.kv_cache_bits == 8:
        kc, ks = attn_mod.quantize_kv_token(k[:, -keep:])
        vc, vs = attn_mod.quantize_kv_token(v[:, -keep:])
        cache["k"] = cache["k"].at[:, slots].set(kc)
        cache["v"] = cache["v"].at[:, slots].set(vc)
        cache["k_scale"] = cache["k_scale"].at[:, slots].set(ks)
        cache["v_scale"] = cache["v_scale"].at[:, slots].set(vs)
    else:
        cache["k"] = cache["k"].at[:, slots].set(k[:, -keep:])
        cache["v"] = cache["v"].at[:, slots].set(v[:, -keep:])
    cache["pos"] = cache["pos"].at[:, slots].set(
        jnp.broadcast_to(pos, (b, keep)))
    return cache


def block_decode(cfg: ArchConfig, block_type: str, p: Dict, x: jnp.ndarray,
                 cache, *, qpos, window, emb0=None, page_table=None):
    """One-token block step. Returns (x, new_cache).

    ``page_table`` (S, npp) switches the attention blocks onto the paged
    KV pool path (serving engine): ``cache`` is then the (P, pg, ...) pool
    tree from ``attn_mod.init_paged_kv_pool`` and the batch axis of ``x``
    is the scheduler slot axis."""
    if block_type in ("dense", "moe", "shared_attn"):
        if block_type == "shared_attn":
            xin = jnp.concatenate([x, emb0], axis=-1) @ \
                p["w_in"].astype(x.dtype)
        else:
            xin = x
        h = rms_norm(xin, p["ln1"], cfg.norm_eps)
        if page_table is not None:
            if cfg.attn_type == "mla":
                raise NotImplementedError("paged decode requires GQA KV "
                                          "caches (attn_type != mla)")
            a, new_cache = attn_mod.gqa_decode_paged(
                p["attn"], h, cache, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, qpos=qpos,
                page_table=page_table, window=window)
        elif cfg.attn_type == "mla":
            a, new_cache = mla_mod.mla_decode(
                p["attn"], h, cache, n_heads=cfg.n_heads,
                qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
                v_head_dim=cfg.v_head_dim, kv_lora_rank=cfg.kv_lora_rank,
                rope_theta=cfg.rope_theta, qpos=qpos, window=window)
        else:
            a, new_cache = attn_mod.gqa_decode(
                p["attn"], h, cache, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, qpos=qpos, window=window)
        xin = xin + a
        h2 = rms_norm(xin, p["ln2"], cfg.norm_eps)
        if block_type == "moe":
            f, _ = moe_forward(p["ffn"], h2, top_k=cfg.moe_top_k,
                               capacity_factor=8.0)  # no drops at decode
        else:
            f = swiglu_forward(p["ffn"], h2)
        out = xin + f
        if block_type == "shared_attn":
            out = x + out
        return out, new_cache
    if block_type == "mamba2":
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        y, new_cache = mamba_mod.mamba2_decode(
            p["mixer"], h, cache, expand=cfg.ssm_expand,
            headdim=cfg.ssm_headdim, d_state=cfg.ssm_state)
        return x + y, new_cache
    if block_type == "rwkv6":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, tcache = rwkv_mod.rwkv6_decode(p["tmix"], h, cache["tmix"],
                                          head_dim=cfg.rwkv_head_dim)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + cmix_forward(p["cmix"], h2,
                             cache["cmix_last"].astype(h2.dtype))
        return x, dict(tmix=tcache, cmix_last=h2)
    raise ValueError(block_type)


# ---------------------------------------------------------------------------
# cache init (for serve_step input specs and tests)
# ---------------------------------------------------------------------------

def init_block_cache(cfg: ArchConfig, block_type: str, batch: int,
                     cache_len: int, dtype):
    if block_type in ("dense", "moe", "shared_attn"):
        if cfg.attn_type == "mla":
            return mla_mod.init_mla_cache(batch, cache_len, cfg.kv_lora_rank,
                                          cfg.qk_rope_dim, dtype)
        return attn_mod.init_kv_cache(batch, cache_len, cfg.n_kv_heads,
                                      cfg.head_dim, dtype,
                                      bits=cfg.kv_cache_bits)
    if block_type == "mamba2":
        return mamba_mod.init_mamba2_cache(
            batch, cfg.d_model, expand=cfg.ssm_expand,
            headdim=cfg.ssm_headdim, d_state=cfg.ssm_state, dtype=dtype)
    if block_type == "rwkv6":
        return dict(
            tmix=rwkv_mod.init_rwkv6_cache(batch, cfg.d_model,
                                           cfg.rwkv_head_dim, dtype),
            cmix_last=jnp.zeros((batch, 1, cfg.d_model), dtype))
    raise ValueError(block_type)


def init_caches(cfg: ArchConfig, batch: int, cache_len: int,
                dtype=jnp.bfloat16):
    """Stacked caches per segment, keyed like the params tree."""
    client_segs, server_segs = cfg.client_server_segments()
    out = {}
    for side, segs in (("client", client_segs), ("server", server_segs)):
        side_caches = {}
        for i, (t, n) in enumerate(segs):
            one = init_block_cache(cfg, t, batch, cache_len, dtype)
            side_caches[f"seg{i}"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy()
                if n > 1 else a[None], one)
        out[side] = side_caches
    return out


def init_paged_caches(cfg: ArchConfig, n_pages: int, page_size: int,
                      dtype=jnp.bfloat16):
    """Stacked paged KV pools per segment, keyed like ``init_caches``.

    One (P, pg, ...) pool per layer (leading layer axis per segment); the
    per-request page table is shared across layers, so page p always means
    the same logical span in every layer's pool.  Serving-engine only:
    requires every block to be an attention block with GQA caches."""
    if cfg.attn_type == "mla":
        raise NotImplementedError("paged serving requires GQA KV caches")
    client_segs, server_segs = cfg.client_server_segments()
    out = {}
    for side, segs in (("client", client_segs), ("server", server_segs)):
        side_caches = {}
        for i, (t, n) in enumerate(segs):
            if t not in ("dense", "moe", "shared_attn"):
                raise NotImplementedError(
                    f"paged serving does not support {t} blocks")
            one = attn_mod.init_paged_kv_pool(
                n_pages, page_size, cfg.n_kv_heads, cfg.head_dim, dtype,
                bits=cfg.kv_cache_bits)
            side_caches[f"seg{i}"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy()
                if n > 1 else a[None], one)
        out[side] = side_caches
    return out


# ---------------------------------------------------------------------------
# whole-model init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ArchConfig) -> Dict:
    dtype = pdtype(cfg)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    if cfg.modality == "audio":
        params["embed"] = emb_mod.init_codebook_embedding(
            keys[0], cfg.n_codebooks, cfg.vocab_size, cfg.d_model, dtype)
    else:
        params["embed"] = emb_mod.init_embedding(
            keys[0], cfg.vocab_size, cfg.d_model, dtype)
    if cfg.modality == "vlm":
        params["connector"] = init_mlp_params(
            keys[1], cfg.d_vision, cfg.d_connector or cfg.d_model,
            cfg.d_model, dtype)
    params["head"] = emb_mod.init_head(
        keys[2], cfg.d_model, cfg.vocab_size,
        n_codebooks=cfg.n_codebooks if cfg.modality == "audio" else 0,
        dtype=dtype)
    params["final_norm"] = jnp.ones((cfg.d_model,), dtype)

    pattern = cfg.block_pattern()
    if "shared_attn" in pattern:
        params["shared_attn"] = init_block_params(keys[3], cfg, "shared_attn")

    client_segs, server_segs = cfg.client_server_segments()
    seg_key = keys[4]
    for side, segs in (("client", client_segs), ("server", server_segs)):
        side_params = {}
        for i, (t, n) in enumerate(segs):
            seg_key, sub = jax.random.split(seg_key)
            if t == "shared_attn":
                side_params[f"seg{i}"] = {}  # params live at top level
            else:
                lkeys = jax.random.split(sub, n)
                side_params[f"seg{i}"] = jax.vmap(
                    lambda k: init_block_params(k, cfg, t))(lkeys)
        params[side] = side_params

    if cfg.split.enabled and cfg.split.learnable_codec:
        params["codec"] = split_mod.init_codec_params(
            keys[5], cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------------
# whole-model forward
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ArchConfig, batch: Dict) -> jnp.ndarray:
    dtype = cdtype(cfg)
    if cfg.modality == "vlm":
        if "image_features" in batch:
            # split-serve: the client ran the vision tower + connector and
            # shipped the connector activations over the quantized wire —
            # the server embeds them as-is (core/split.serve_*).
            img = batch["image_features"].astype(dtype)
        else:
            img = mlp_forward(params["connector"],
                              batch["image_embeds"].astype(dtype))
        tok = emb_mod.embed(params["embed"], batch["tokens"], dtype)
        return jnp.concatenate([img, tok], axis=1)
    if cfg.modality == "audio":
        return emb_mod.embed_codebooks(params["embed"], batch["codes"], dtype)
    return emb_mod.embed(params["embed"], batch["tokens"], dtype)


def _run_segments(params, cfg: ArchConfig, side: str, segs, x, *, positions,
                  window, emb0, collect_cache: Optional[int] = None):
    """Run one side's segment list through the stack executor.

    Returns (x, aux_sum, caches)."""
    aux_sum = dict(_EMPTY_AUX)
    caches = {}
    for i, (t, n) in enumerate(segs):
        if t == "shared_attn":
            x, aux, cache = block_forward(
                cfg, t, params["shared_attn"], x, positions=positions,
                window=window, emb0=emb0, collect_cache=collect_cache)
            aux_sum = {k: aux_sum[k] + aux[k] for k in aux_sum}
            if collect_cache is not None:
                caches[f"seg{i}"] = jax.tree_util.tree_map(
                    lambda a: a[None], cache)
            continue

        def body(carry, p, _t=t):
            y, aux, cache = block_forward(
                cfg, _t, p, carry, positions=positions, window=window,
                emb0=emb0, collect_cache=collect_cache)
            return y, (aux, cache)

        stacked = params[side][f"seg{i}"]
        remat_group = cfg.remat_group
        if cfg.remat and remat_group == 0:
            # unset -> bytes-aware auto-tune from the carry entering the
            # segment (the stored layer input of the remat schedule)
            remat_group = stack_mod.auto_group_size(
                stack_mod.stack_len(stacked), x.size * x.dtype.itemsize)
        x, seg_aux, seg_caches = stack_mod.run_stack(
            body, x, stacked, remat=cfg.remat,
            remat_group=remat_group,
            collect=collect_cache is not None)
        aux_sum = {kk: aux_sum[kk] + seg_aux[kk] for kk in aux_sum}
        if collect_cache is not None:
            caches[f"seg{i}"] = seg_caches
    return x, aux_sum, caches


def forward(params, cfg: ArchConfig, batch: Dict, *,
            rng: Optional[jax.Array] = None, window: Optional[int] = None,
            collect_cache: Optional[int] = None,
            last_positions: Optional[jnp.ndarray] = None):
    """Full-sequence forward (train / prefill).

    Returns (logits, aux) or (logits, aux, caches) when
    ``collect_cache`` (a cache length) is given.
    aux = {commit, load_balance, router_z, drop_fraction}.

    ``last_positions`` ((B,) int32, runtime data) applies the final norm
    and the head only at one position per row: logits are then (B, V)
    (or (B, K, V) for audio) instead of (B, S, V).
    """
    x = shard_ctx.constrain(_embed_inputs(params, cfg, batch), "hidden")
    emb0 = x
    s = x.shape[1]
    # positions as RUNTIME data (input_specs provides them): if they were
    # trace-time iota, XLA constant-folds attention masks and widens them
    # into giant stacked buffers inside the layer scans (see attention.py).
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.arange(s)
    positions = positions.astype(jnp.int32)
    client_segs, server_segs = cfg.client_server_segments()

    x, aux_c, caches_c = _run_segments(
        params, cfg, "client", client_segs, x, positions=positions,
        window=window, emb0=emb0, collect_cache=collect_cache)

    # --- the paper's compressor at the cut ---
    x, commit = split_mod.compressor_roundtrip(
        params.get("codec"), cfg.split, x, rng)

    x, aux_s, caches_s = _run_segments(
        params, cfg, "server", server_segs, x, positions=positions,
        window=window, emb0=emb0, collect_cache=collect_cache)

    if last_positions is not None:
        x = jnp.take_along_axis(
            x, last_positions.astype(jnp.int32)[:, None, None], axis=1)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = emb_mod.head_logits(params["head"], x)
    if last_positions is not None:
        logits = logits[:, 0]
    elif logits.ndim == 3:
        logits = shard_ctx.constrain(logits, "logits")
    aux = {k: aux_c[k] + aux_s[k] for k in aux_c}
    aux["commit"] = commit
    if collect_cache is not None:
        return logits, aux, dict(client=caches_c, server=caches_s)
    return logits, aux


def decode_step(params, cfg: ArchConfig, caches: Dict, batch: Dict,
                qpos: jnp.ndarray, *, window: Optional[int] = None,
                rng: Optional[jax.Array] = None):
    """One-token serve step.

    batch: {tokens: (B, 1)} (or codes (B, K, 1) for audio;
    tokens-only for VLM decode — images were consumed at prefill).
    qpos: (B,) absolute positions.  Returns (logits, new_caches).
    """
    dtype = cdtype(cfg)
    if cfg.modality == "audio":
        x = emb_mod.embed_codebooks(params["embed"], batch["codes"], dtype)
    else:
        x = emb_mod.embed(params["embed"], batch["tokens"], dtype)
    emb0 = x
    client_segs, server_segs = cfg.client_server_segments()
    new_caches = {"client": {}, "server": {}}

    def run_side(side, segs, x):
        for i, (t, n) in enumerate(segs):
            cache = caches[side][f"seg{i}"]
            if t == "shared_attn":
                x, c_new = block_decode(
                    cfg, t, params["shared_attn"], x,
                    jax.tree_util.tree_map(lambda a: a[0], cache),
                    qpos=qpos, window=window, emb0=emb0)
                new_caches[side][f"seg{i}"] = jax.tree_util.tree_map(
                    lambda a: a[None], c_new)
                continue
            stacked = params[side][f"seg{i}"]

            def body(carry, pc, _t=t):
                p, c = pc
                y, c_new = block_decode(cfg, _t, p, carry, c, qpos=qpos,
                                        window=window, emb0=emb0)
                return y, c_new

            x, seg_caches = stack_mod.run_decode_stack(body, x, stacked,
                                                       cache)
            new_caches[side][f"seg{i}"] = seg_caches
        return x

    x = run_side("client", client_segs, x)
    x, _ = split_mod.compressor_roundtrip(params.get("codec"), cfg.split, x,
                                          rng)
    x = run_side("server", server_segs, x)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = emb_mod.head_logits(params["head"], x)
    return logits, new_caches


def decode_step_paged(params, cfg: ArchConfig, pools: Dict, batch: Dict,
                      qpos: jnp.ndarray, page_table: jnp.ndarray, *,
                      window: Optional[int] = None,
                      rng: Optional[jax.Array] = None):
    """One decode tick of the serving engine against paged KV pools.

    ``pools``: tree from ``init_paged_caches``; ``page_table``: (S, npp)
    int32, -1 = unallocated; ``qpos``: (S,), -1 = inactive slot (its
    logits are garbage and its KV write lands on the trash page).
    Returns (logits, new_pools)."""
    dtype = cdtype(cfg)
    if cfg.modality == "audio":
        x = emb_mod.embed_codebooks(params["embed"], batch["codes"], dtype)
    else:
        x = emb_mod.embed(params["embed"], batch["tokens"], dtype)
    emb0 = x
    client_segs, server_segs = cfg.client_server_segments()
    new_pools = {"client": {}, "server": {}}

    def run_side(side, segs, x):
        for i, (t, n) in enumerate(segs):
            cache = pools[side][f"seg{i}"]
            if t == "shared_attn":
                x, c_new = block_decode(
                    cfg, t, params["shared_attn"], x,
                    jax.tree_util.tree_map(lambda a: a[0], cache),
                    qpos=qpos, window=window, emb0=emb0,
                    page_table=page_table)
                new_pools[side][f"seg{i}"] = jax.tree_util.tree_map(
                    lambda a: a[None], c_new)
                continue
            stacked = params[side][f"seg{i}"]

            def body(carry, pc, _t=t):
                p, c = pc
                y, c_new = block_decode(cfg, _t, p, carry, c, qpos=qpos,
                                        window=window, emb0=emb0,
                                        page_table=page_table)
                return y, c_new

            x, seg_pools = stack_mod.run_decode_stack(body, x, stacked,
                                                      cache)
            new_pools[side][f"seg{i}"] = seg_pools
        return x

    x = run_side("client", client_segs, x)
    x, _ = split_mod.compressor_roundtrip(params.get("codec"), cfg.split, x,
                                          rng)
    x = run_side("server", server_segs, x)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = emb_mod.head_logits(params["head"], x)
    return logits, new_pools
