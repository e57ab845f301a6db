"""Plain reference of the dense split vision-language model.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no kernels, no cache, no batching tricks.  It
imports nothing of the program and takes nothing the program made: its
weights come from the benchmark's own seeded generator
(``bench/harness/weights.py``), layer by layer.

The model, as the configuration states it:

* connector: two-layer MLP with tanh-approximated GELU on the
  vision-tower embeddings;
* sequence: connector features, then token embeddings;
* the cut (after ``cut_layer`` decoder layers): a linear encoder, the
  RD-FSQ round trip (clip to mu +- k sigma, min-max scale onto [-1, 1],
  round to 2**bits symmetric levels, fp16 side information, one set of
  statistics per sample) with a straight-through gradient, a linear
  decoder; the commitment loss is 1 - cos((d-1)/2 e, sg(z)), averaged
  over samples;
* decoder layers: pre-RMSNorm GQA attention with interleaved-pair RoPE
  and causal softmax, then a SwiGLU MLP, both residual;
* final RMSNorm and an untied head.

Serving has the split-serve semantics: the client quantizes its
connector features for the wire (RD-FSQ encode -> decode) before the
server embeds them; the server's prefill quantizes each padded prompt
row at the cut with the row's statistics, and each decoded token is
quantized at the cut alone.

``lp=True`` computes every matrix product with float8 (e4m3) operands,
the precision below the configuration's bfloat16: that is the control,
which the comparison must reject.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as W

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
_EPS = 1e-6


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def _lp(x, lp: bool):
    return x.astype(jnp.float8_e4m3fn).astype(F32) if lp else x


def fp16(x):
    """``x`` rounded to float16, kept in float32.  The TPU compiler folds
    a float32 -> float16 -> float32 round trip inside one program away
    (excess precision); ``reduce_precision`` it keeps."""
    return jax.lax.reduce_precision(x, exponent_bits=5, mantissa_bits=10)


def f32(tree):
    """Stored leaves (the configuration's dtype) widened to float32."""
    return jax.tree_util.tree_map(lambda x: x.astype(F32), tree)


def mm(a, b, lp: bool = False):
    return jnp.matmul(_lp(a, lp), _lp(b, lp), precision=HI)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (..., S, H, hd); rotate the pairs (x[2i], x[2i+1])."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, pos, lp=False, block=512):
    """Causal GQA; q (B, S, H, hd), k/v (B, S, KH, hd); query blocks so
    that no (S x S) score tensor of every head is held at once."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    q = q.reshape(b, s, kh, g, hd) * hd ** -0.5
    outs = []
    for lo in range(0, s, block):
        qb = q[:, lo:lo + block]
        sc = jnp.einsum("bqkgd,bskd->bkgqs", _lp(qb, lp), _lp(k, lp),
                        precision=HI)
        causal = pos[lo:lo + block][:, None] >= pos[None, :]
        sc = jnp.where(causal, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bkgqs,bskd->bqkgd", _lp(p, lp), _lp(v, lp),
                               precision=HI))
    return jnp.concatenate(outs, axis=1).reshape(b, s, h * hd)


def layer(c, p: Dict, x, pos, lp=False):
    """One decoder layer on x (B, S, D); ``p`` holds one layer's leaves."""
    b, s, _ = x.shape
    h = rms_norm(x, p["ln1"], c.norm_eps)
    q = mm(h, p["attn.wq"], lp).reshape(b, s, c.n_heads, c.head_dim)
    k = mm(h, p["attn.wk"], lp).reshape(b, s, c.n_kv_heads, c.head_dim)
    v = mm(h, p["attn.wv"], lp).reshape(b, s, c.n_kv_heads, c.head_dim)
    q, k = rope(q, pos, c.rope_theta), rope(k, pos, c.rope_theta)
    x = x + mm(attention(q, k, v, pos, lp), p["attn.wo"], lp)
    h = rms_norm(x, p["ln2"], c.norm_eps)
    f = jax.nn.silu(mm(h, p["ffn.w_gate"], lp)) * mm(h, p["ffn.w_up"], lp)
    return x + mm(f, p["ffn.w_down"], lp)


def rdfsq(c, x):
    """RD-FSQ with per-sample statistics over every axis but the first.
    Returns (x_hat, e, z)."""
    axes = tuple(range(1, x.ndim))
    mu = jnp.mean(x, axis=axes, keepdims=True)
    sd = jnp.std(x, axis=axes, keepdims=True)
    xc = jnp.clip(x, mu - c.clip_sigma * sd, mu + c.clip_sigma * sd)
    lo = jnp.min(xc, axis=axes, keepdims=True)
    hi = jnp.max(xc, axis=axes, keepdims=True)
    e = 2.0 * (xc - lo) / (hi - lo + _EPS) - 1.0
    d = 2 ** c.quant_bits
    half = (d - 1) / 2.0
    if d % 2:
        z = jnp.round(half * e)
    else:
        z = jnp.round(half * e - 0.5) + 0.5
    z = jnp.clip(z, -half, half)
    lo16, hi16 = fp16(lo), fp16(hi)
    x_hat = (z / half + 1.0) / 2.0 * (hi16 - lo16) + lo16
    return x_hat, e, z


def commit_loss(c, e, z):
    """Per-sample 1 - cos((d-1)/2 e, sg(z)); (B,)."""
    half = (2 ** c.quant_bits - 1) / 2.0
    a = (half * e).reshape(e.shape[0], -1)
    bz = jax.lax.stop_gradient(z).reshape(z.shape[0], -1)
    num = jnp.sum(a * bz, axis=-1)
    den = jnp.sqrt(jnp.sum(a * a, axis=-1) * jnp.sum(bz * bz, axis=-1)
                   + _EPS)
    return 1.0 - num / den


def connector(top: Dict, img, lp=False):
    h = gelu_tanh(mm(img, top["connector.w1"], lp) + top["connector.b1"])
    return mm(h, top["connector.w2"], lp) + top["connector.b2"]


def cut(c, top: Dict, x, lp=False):
    """The compressor at the cut; returns (features, per-sample commit)."""
    if not c.learnable_codec:
        enc = x
    else:
        enc = mm(x, top["codec.enc_w"], lp) + top["codec.enc_b"]
    x_hat, e, z = rdfsq(c, enc)
    out = enc + jax.lax.stop_gradient(x_hat - enc)  # straight through
    if c.learnable_codec:
        out = mm(out, top["codec.dec_w"], lp) + top["codec.dec_b"]
    return out, commit_loss(c, e, z)


def head(c, top: Dict, x, lp=False):
    return mm(rms_norm(x, top["final_norm"], c.norm_eps), top["head.w"], lp)


def cut_layer(c) -> int:
    return [first for side, _, first, _ in W.segments(c)
            if side == "server"][0]


# ---------------------------------------------------------------------------
# weights, from the benchmark's generator, stored in the configuration's
# parameter dtype; every computation widens them to float32 (``f32``)
# ---------------------------------------------------------------------------

def top_params(c, key) -> Dict:
    return jax.jit(lambda k: {n: W.top_leaf(c, k, n)
                              for n in W.top_leaves(c)})(key)


@functools.partial(jax.jit, static_argnums=(0,))
def layer_params(c, key, i) -> Dict:
    return {n: W.layer_leaf(c, key, n, i) for n in W.layer_leaves(c)}


def flat_to_tree(c, top: Dict, layers: Sequence[Dict]) -> Dict:
    """The program's tree layout (stacked segments) from flat leaves."""
    tree = W.nest(top)
    tree["client"], tree["server"] = {}, {}
    for side, seg, first, n in W.segments(c):
        tree[side][seg] = W.nest({name: jnp.stack(
            [layers[i][name] for i in range(first, first + n)])
            for name in W.layer_leaves(c)})
    return tree


def tree_to_flat(c, tree: Dict) -> Tuple[Dict, List[Dict]]:
    top = {n: _get(tree, n) for n in W.top_leaves(c)}
    layers = []
    for side, seg, first, n in W.segments(c):
        for j in range(n):
            layers.append({name: _get(tree[side][seg], name)[j]
                           for name in W.layer_leaves(c)})
    return top, layers


def _get(tree, path):
    for p in path.split("."):
        tree = tree[p]
    return tree


# ---------------------------------------------------------------------------
# training: loss and gradients over row blocks, AdamW
# ---------------------------------------------------------------------------

def train_loss(c, params: Dict, batch: Dict, lp=False):
    """(sum of masked token NLL, count of labelled tokens, sum over rows
    of the commitment loss) for a block of rows."""
    top, layers = tree_to_flat(c, params)
    img = connector(top, batch["image_embeds"], lp)
    tok = top["embed.emb"][batch["tokens"]]
    x = jnp.concatenate([img, tok], axis=1)
    pos = jnp.arange(x.shape[1])
    k = cut_layer(c)
    commit = jnp.zeros((x.shape[0],), F32)

    body = jax.checkpoint(lambda p, x: layer(c, p, x, pos, lp))
    for i, p in enumerate(layers):
        if i == k:
            x, commit = cut(c, top, x, lp)
        x = body(p, x)
    if k == len(layers):
        x, commit = cut(c, top, x, lp)
    logits = head(c, top, x, lp)
    labels = batch["labels"]
    mask = labels != -100
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(nll * mask), jnp.sum(mask), jnp.sum(commit)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _block_grads(c, params, batch, scale, lp):
    """Gradient of this block's share of the whole batch's loss."""
    def f(params):
        nll, cnt, commit = train_loss(c, params, batch, lp)
        return nll * scale[0] + c.commit_alpha * commit * scale[1]
    return jax.value_and_grad(f)(f32(params))


def batch_loss_and_grads(c, params, batch: Dict, row_block: int, lp=False):
    """Whole-batch loss and gradients, accumulated over row blocks."""
    b = batch["tokens"].shape[0]
    labels = np.asarray(batch["labels"])
    n_lab = float((labels != -100).sum())
    scale = jnp.asarray([1.0 / n_lab, 1.0 / b], F32)
    loss, grads = 0.0, None
    for lo in range(0, b, row_block):
        blk = {k: (v[lo:lo + row_block] if k != "positions" else v)
               for k, v in batch.items()}
        val, g = _block_grads(c, params, blk, scale, lp)
        loss += float(val)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return loss, grads


@functools.partial(jax.jit, static_argnums=(4, 5))
def adamw(params, grads, m, v, opt: Tuple, step: int):
    """One AdamW step with global-norm clipping, computed in float32; the
    parameters come and go stored in ``opt``'s dtype (the
    configuration's), as the configuration states."""
    lr, b1, b2, eps, wd, clip, dtype = opt
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(
        grads)))
    s = jnp.minimum(1.0, clip / (gn + 1e-9))
    g = jax.tree_util.tree_map(lambda x: x * s, grads)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, m, v):
        p = p.astype(F32)
        d = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if p.ndim >= 2:  # decay on every stored leaf of two or more axes
            d = d + wd * p
        return (p - lr * d).astype(dtype)

    params = jax.tree_util.tree_map(upd, params, m, v)
    return params, m, v, g


def train_reference(c, key, batches: Sequence[Dict], opt: Dict,
                    n_steps: int, row_block: int, lp=False) -> Dict:
    """``n_steps`` steps from the seeded weights.  Returns the loss of
    each step, the first clipped gradient's leaf norms and the leaf
    norms of the parameters' change after the last step."""
    top = top_params(c, key)
    layers = [layer_params(c, key, i) for i in range(c.n_layers)]
    params = flat_to_tree(c, top, layers)
    del top, layers
    p0 = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), params)
    v = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), params)
    o = (opt["lr"], opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
         opt["clip_norm"], W.DTYPES[c.param_dtype])
    losses, g1 = [], None
    with jax.default_matmul_precision("highest"):
        for t in range(n_steps):
            loss, grads = batch_loss_and_grads(c, params, batches[t],
                                               row_block, lp)
            losses.append(loss)
            params, m, v, g = adamw(params, grads, m, v, o, t + 1)
            if t == 0:
                g1 = leaf_norms(g)
            del grads, g
    change = leaf_norms(jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))(params, p0))
    return dict(losses=losses, grad_norms=g1, change_norms=change)


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in
            zip(flat, norms)}


# ---------------------------------------------------------------------------
# serving: the served requests, teacher-forced
# ---------------------------------------------------------------------------

def _embed_rows(c, top, img, prompt, plen, served, lb, lp):
    """Features entering the layers for each request, padded to a common
    length.  img (R, n_img, d_vision); prompt (R, lb - n_img) right-padded
    with 0; plen (R,); served (R, n_out) tokens fed back."""
    n_img = img.shape[1]
    feats = connector(top, img, lp)
    wire, _, _ = rdfsq(c, feats)                       # the wire round trip
    row = jnp.concatenate([wire, top["embed.emb"][prompt]], axis=1)
    row, _ = cut(c, top, row, lp)                      # prefill, row stats
    r, n_out = served.shape
    tok = top["embed.emb"][served].reshape(r * n_out, 1, -1)
    tok, _ = cut(c, top, tok, lp)                      # decode, per token
    tok = tok.reshape(r, n_out, -1)
    # request i: row[:n_img + plen_i] then its decoded tokens
    t = lb + n_out
    idx = jnp.arange(t)[None, :]
    cut_at = n_img + plen[:, None]
    from_row = jnp.pad(row, ((0, 0), (0, n_out), (0, 0)))
    from_tok = jnp.take_along_axis(
        tok, jnp.clip(idx - cut_at, 0, n_out - 1)[..., None], axis=1)
    return jnp.where((idx < cut_at)[..., None], from_row, from_tok)


@functools.partial(jax.jit, static_argnums=(0, 6, 7))
def _serve_embed(c, top, img, prompt, plen, served, lb, lp):
    return _embed_rows(c, f32(top), img, prompt, plen, served, lb, lp)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _serve_layer(c, p, x, lp):
    return layer(c, f32(p), x, jnp.arange(x.shape[1]), lp)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _serve_head(c, top, x, at, lp):
    h = jnp.take_along_axis(x, at[..., None], axis=1)
    return head(c, f32(top), h, lp)


def serve_logits(c, key, img, prompt, plen, served, lb: int, lp=False):
    """Logits (R, n_out, V) at each served token's position: index j
    predicts served[:, j] from the prompt and served[:, :j].  Layer by
    layer, one request at a time, so that it fits beside nothing."""
    with jax.default_matmul_precision("highest"):
        top = top_params(c, key)
        n_img = img.shape[1]
        x = _serve_embed(c, top, img, prompt, plen, served, lb, lp)
        rows = [x[i:i + 1] for i in range(x.shape[0])]
        del x
        if cut_layer(c) != 0:
            raise NotImplementedError("the serving reference cuts at 0")
        for i in range(c.n_layers):
            p = layer_params(c, key, i)
            rows = [_serve_layer(c, p, r, lp) for r in rows]
            del p
        at = n_img + plen[:, None] - 1 + jnp.arange(served.shape[1])[None]
        return jnp.concatenate([_serve_head(c, top, r, at[i:i + 1], lp)
                                for i, r in enumerate(rows)])
