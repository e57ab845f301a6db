"""Serving: one-token decode step + batched autoregressive generation.

``make_serve_step(cfg)`` returns the jit-able function lowered by the
decode_32k / long_500k dry-run shapes: ONE new token against a KV/state
cache of the configured length.  ``generate`` drives it autoregressively
(greedy or temperature sampling) for the examples.

Both the prefill (``transformer.forward`` with cache collection) and the
per-token step (``transformer.decode_step``) execute the layer stack
through the unified executor in ``repro.models.stack`` — the serve path
shares one scan implementation with training, so cache layouts stay
structurally identical to the training-time parameter stacking.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import attention_ops
from repro.models import transformer as tf


def cache_length(cfg: ArchConfig, seq_len: int,
                 window: Optional[int]) -> int:
    """Ring-buffer size: full history, or the window for long-context."""
    if window is not None:
        return min(seq_len, window)
    return seq_len


def make_serve_step(cfg: ArchConfig, *,
                    window: Optional[int] = None) -> Callable:
    def serve_step(params, caches, batch: Dict, qpos: jnp.ndarray):
        logits, new_caches = tf.decode_step(params, cfg, caches, batch, qpos,
                                            window=window)
        return logits, new_caches

    return serve_step


@functools.lru_cache(maxsize=32)
def _compiled_serve_step(cfg: ArchConfig, window: Optional[int],
                         attn_impl: str) -> Callable:
    """One jitted serve step per (cfg, window, attention backend).

    ``ArchConfig`` is a frozen (hashable) dataclass, so repeated
    ``generate`` calls — and multiple concurrent generations on the same
    model — reuse a single compiled step instead of re-jitting per call.
    The resolved attention backend is part of the key: REPRO_ATTN_IMPL is
    read at trace time, so flipping it between ``generate`` calls must
    miss the cache rather than silently reuse the other backend's step.

    ``caches`` is DONATED: the per-token step updates the KV ring buffers
    in place (XLA input/output aliasing) instead of materializing a full
    cache copy per token.  Callers must not reuse a caches tree after
    passing it in — rebind it from the step's return value.
    """
    del attn_impl  # cache key only; the traced code reads the env var
    return jax.jit(make_serve_step(cfg, window=window), donate_argnums=(1,))


def compiled_serve_step(cfg: ArchConfig, *, window: Optional[int] = None,
                        impl: Optional[str] = None) -> Callable:
    """Public accessor for the cached jitted step (engine + benches)."""
    return _compiled_serve_step(cfg, window,
                                attention_ops.resolve_impl(impl))


@functools.lru_cache(maxsize=32)
def _compiled_prefill(cfg: ArchConfig, cache_len: int,
                      window: Optional[int], attn_impl: str) -> Callable:
    del attn_impl  # cache key only; the traced code reads the env var

    def _prefill(params, batch, rng, last_positions):
        logits, _aux, caches = tf.forward(params, cfg, batch, rng=rng,
                                          window=window,
                                          collect_cache=cache_len,
                                          last_positions=last_positions)
        return logits, caches

    return jax.jit(_prefill)


def prefill(params, cfg: ArchConfig, batch: Dict, cache_len: int, *,
            window: Optional[int] = None,
            rng: Optional[jax.Array] = None,
            last_positions: Optional[jnp.ndarray] = None):
    """Run the full-sequence pass and return (logits, caches).

    Logits are (B, S, V), every position's, or with ``last_positions``
    ((B,) int32) only those rows' positions, (B, V): the final norm and
    the head then run at B positions, and a caller that right-pads rows
    copies back one vocabulary row each.  The positions are traced as
    data, so each one shares the program of its shape.  The caches are
    the same either way.

    Jitted and cached per (cfg, cache_len, window, backend): the serving
    engine prefills every admission wave through here, so an unjitted
    (op-by-op) forward would dominate its tick time."""
    fn = _compiled_prefill(cfg, cache_len, window,
                           attention_ops.resolve_impl(None))
    return fn(params, batch, rng, last_positions)


def generate(params, cfg: ArchConfig, batch: Dict, *, n_new: int,
             cache_len: int, window: Optional[int] = None,
             temperature: float = 0.0, rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None, pad_id: int = 0) -> jnp.ndarray:
    """Prefill + greedy/sampled generation of ``n_new`` tokens.

    ``eos_id`` enables per-sequence early stop: a row that emits EOS is
    frozen — every later position is ``pad_id`` regardless of continued
    stepping — and the decode loop exits as soon as ALL rows finished
    instead of always paying ``n_new`` steps."""
    if rng is None:
        rng = jax.random.PRNGKey(0)
    # Split BEFORE consuming: prefill (dropout / quantizer noise) and the
    # first sampled token must never share a key — reusing ``rng`` for
    # both correlates the first sample with the prefill randomness.
    rng, prefill_rng = jax.random.split(rng)
    logits, caches = prefill(params, cfg, batch, cache_len, window=window,
                             rng=prefill_rng)
    if cfg.modality == "audio":
        prompt_len = batch["codes"].shape[-1]
        bsz = batch["codes"].shape[0]
    elif cfg.modality == "vlm":
        prompt_len = batch["tokens"].shape[1] + cfg.n_image_tokens
        bsz = batch["tokens"].shape[0]
    else:
        prompt_len = batch["tokens"].shape[1]
        bsz = batch["tokens"].shape[0]

    serve_step = _compiled_serve_step(cfg, window,
                                      attention_ops.resolve_impl(None))

    def pick(logits, key):
        # (B, V), or (B, K, V) for audio — argmax/categorical over the
        # trailing vocab axis handles both (per-codebook picks for audio).
        last = logits[:, -1]
        if temperature <= 0.0:
            return jnp.argmax(last, axis=-1)
        return jax.random.categorical(key, last / temperature, axis=-1)

    def freeze(tok, done):
        d = done if tok.ndim == 1 else done[:, None]
        return jnp.where(d, jnp.asarray(pad_id, tok.dtype), tok)

    out = []
    done = jnp.zeros((bsz,), bool)
    rng, first_key = jax.random.split(rng)
    tok = pick(logits, first_key)
    for i in range(n_new):
        if eos_id is not None:
            tok = freeze(tok, done)
            hit = (tok == eos_id) if tok.ndim == 1 \
                else jnp.all(tok == eos_id, axis=-1)
            done = done | hit
        out.append(tok)
        if eos_id is not None and i + 1 < n_new and bool(jnp.all(done)):
            pad = jnp.full_like(tok, pad_id)
            out.extend([pad] * (n_new - i - 1))
            break
        qpos = jnp.full((bsz,), prompt_len + i, jnp.int32)
        if cfg.modality == "audio":
            step_batch = dict(codes=tok[..., None].astype(jnp.int32)
                              if tok.ndim == 2 else
                              jnp.broadcast_to(tok[:, None, None],
                                               (bsz, cfg.n_codebooks, 1)
                                               ).astype(jnp.int32))
        else:
            step_batch = dict(tokens=tok[:, None].astype(jnp.int32))
        rng, sub = jax.random.split(rng)
        logits, caches = serve_step(params, caches, step_batch, qpos)
        tok = pick(logits, sub)
    return jnp.stack(out, axis=1)
