"""Seeded weights and keys, made by the benchmark and not by the program.

``init_params(cfg, key)`` builds the parameter tree that the program's
dense vision-language stack expects (embedding, connector, codec, one
stacked server segment per side of the cut, final norm, head) in one
jitted call on the device, in the configuration's parameter dtype.  The
distributions are the usual ones (normal at fan-in scale, embedding at
0.02, norms at one, biases at zero, a near-identity codec).

Every leaf, and every layer of a stacked leaf, has a key of its own, so
``layer_leaf`` makes one layer's weights again without the rest: the
reference walks the model layer by layer from the same seed.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed (more than 32 bits allowed)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return key


def sub_key(key: jax.Array, *path: int) -> jax.Array:
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


# leaf name -> (shape of one layer, init kind, scale); the stacked layer
# leaves of a segment.  ``d``, ``f``, ``q`` and ``kv`` are widths.
def _layer_leaves(d: int, f: int, q: int, kv: int) -> Dict[str, Tuple]:
    return {
        "ln1": ((d,), "ones", 1.0),
        "ln2": ((d,), "ones", 1.0),
        "attn.wq": ((d, q), "normal", d ** -0.5),
        "attn.wk": ((d, kv), "normal", d ** -0.5),
        "attn.wv": ((d, kv), "normal", d ** -0.5),
        "attn.wo": ((q, d), "normal", q ** -0.5),
        "ffn.w_gate": ((d, f), "normal", d ** -0.5),
        "ffn.w_up": ((d, f), "normal", d ** -0.5),
        "ffn.w_down": ((f, d), "normal", f ** -0.5),
    }


def top_leaves(cfg) -> Dict[str, Tuple]:
    d, v = cfg.d_model, cfg.vocab_size
    dv, dc = cfg.d_vision, cfg.d_connector or cfg.d_model
    out = {
        "embed.emb": ((v, d), "normal", 0.02),
        "head.w": ((d, v), "normal", d ** -0.5),
        "final_norm": ((d,), "ones", 1.0),
        "connector.w1": ((dv, dc), "normal", dv ** -0.5),
        "connector.b1": ((dc,), "zeros", 0.0),
        "connector.w2": ((dc, d), "normal", dc ** -0.5),
        "connector.b2": ((d,), "zeros", 0.0),
    }
    if cfg.learnable_codec:
        noise = 0.01 / d ** 0.5
        out.update({
            "codec.enc_w": ((d, d), "eye", noise),
            "codec.enc_b": ((d,), "zeros", 0.0),
            "codec.dec_w": ((d, d), "eye", noise),
            "codec.dec_b": ((d,), "zeros", 0.0),
        })
    return out


def segments(cfg) -> List[Tuple[str, str, int, int]]:
    """(side, segment key, first layer, n layers): the cut splits the
    dense stack into a client and a server segment."""
    cut = cfg.cut_layer if cfg.cut_layer >= 0 else cfg.n_layers // 2
    cut = min(max(cut, 0), cfg.n_layers)
    out = []
    if cut > 0:
        out.append(("client", "seg0", 0, cut))
    if cfg.n_layers > cut:
        out.append(("server", "seg0", cut, cfg.n_layers - cut))
    return out


def layer_leaves(cfg) -> Dict[str, Tuple]:
    return _layer_leaves(cfg.d_model, cfg.d_ff, cfg.n_heads * cfg.head_dim,
                         cfg.n_kv_heads * cfg.head_dim)


def _make(kind: str, key, shape, scale, dtype):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    x = jax.random.normal(key, shape, jnp.float32) * scale
    if kind == "eye":
        x = x + jnp.eye(shape[0], dtype=jnp.float32)
    return x.astype(dtype)


_LAYER_TAG = 1 << 20  # keeps layer leaf ids apart from top-level ones


def top_leaf(cfg, key, name: str, dtype=None):
    names = sorted(top_leaves(cfg))
    shape, kind, scale = top_leaves(cfg)[name]
    dtype = dtype or DTYPES[cfg.param_dtype]
    return _make(kind, sub_key(key, names.index(name)), shape, scale, dtype)


def layer_leaf(cfg, key, name: str, layer: int, dtype=None):
    """One layer's copy of a stacked leaf; ``layer`` counts from 0 over
    the whole stack, client and server alike."""
    names = sorted(layer_leaves(cfg))
    shape, kind, scale = layer_leaves(cfg)[name]
    dtype = dtype or DTYPES[cfg.param_dtype]
    k = sub_key(key, _LAYER_TAG + names.index(name), layer)
    return _make(kind, k, shape, scale, dtype)


def nest(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, leaf in flat.items():
        node = out
        parts = path.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out


def _init(cfg, key):
    flat = {name: top_leaf(cfg, key, name) for name in top_leaves(cfg)}
    params = nest(flat)
    params["client"], params["server"] = {}, {}
    for side, seg, first, n in segments(cfg):
        layers = jnp.arange(first, first + n)
        stacked = {name: jax.vmap(
            lambda i, name=name: layer_leaf(cfg, key, name, i))(layers)
            for name in layer_leaves(cfg)}
        params[side][seg] = nest(stacked)
    return params


@functools.lru_cache(maxsize=8)
def _jitted_init(cfg):
    return jax.jit(functools.partial(_init, cfg))


def init_params(cfg, key) -> Dict:
    """The whole parameter tree, made on the device in one call."""
    return _jitted_init(cfg)(key)
