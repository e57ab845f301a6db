"""The dense split vision-language model: the model module of every
configuration that names none.

Its weights are ``bench/harness/weights.py``'s, its reference
``bench/reference/model.py``'s and its counts ``bench/harness/flops.py``'s;
this module gathers them behind the model-module contract
(``bench/models/__init__.py``), and adds what a ``pipeline`` cell needs:
the program's stage-stacked tree and the plain reference of the
pipeline's training job, written on the same reference layers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import flops as F
from bench.harness import weights as W
from bench.reference import model as R

F32 = jnp.float32
IGNORE = -100  # a label the loss leaves out


@dataclasses.dataclass(frozen=True)
class Sizes:
    """A configuration's sizes as the benchmark's own code reads them
    (weights, reference, operation counts); hashable, so it can key a
    jit cache."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float
    norm_eps: float
    n_image_tokens: int
    d_vision: int
    d_connector: int
    param_dtype: str
    compute_dtype: str
    cut_layer: int
    quant_bits: int
    clip_sigma: float
    commit_alpha: float
    learnable_codec: bool


def sizes(config: Dict) -> Sizes:
    s = config["split"]
    kw = {f.name: config[f.name] for f in dataclasses.fields(Sizes)
          if f.name in config}
    return Sizes(cut_layer=s["cut_layer"], quant_bits=s["bits"],
                 clip_sigma=s["clip_sigma"], commit_alpha=s["commit_alpha"],
                 learnable_codec=s["learnable_codec"], **kw)


# -- weights -----------------------------------------------------------------

init_params = W.init_params
PIPELINE_TOP = ("embed.emb", "final_norm", "head.w")


def pipeline_params(c: Sizes, key, n_stages: int) -> Dict:
    """The program's stage-stacked pipeline tree (``launch/split_pipeline``):
    embedding, final norm and head, and every layer stacked as
    ``(n_stages, n_layers / n_stages, ...)``; each leaf the same numbers
    as ``init_params`` gives it.  Traceable: the caller jits it (with the
    mesh's shardings)."""
    per = c.n_layers // n_stages
    layers = jnp.arange(c.n_layers).reshape(n_stages, per)
    tree = W.nest({n: W.top_leaf(c, key, n) for n in PIPELINE_TOP})
    tree["blocks"] = W.nest({name: jax.vmap(jax.vmap(
        lambda i, name=name: W.layer_leaf(c, key, name, i)))(layers)
        for name in W.layer_leaves(c)})
    return tree


# -- the plain reference -----------------------------------------------------

train_reference = R.train_reference
serve_logits = R.serve_logits


def _get(tree, path: str):
    for p in path.split("."):
        tree = tree[p]
    return tree


def _pipeline_loss_sum(c: Sizes, params: Dict, tokens, labels,
                       n_stages: int, lp: bool = False):
    """Summed NLL over the labelled positions of a block of text rows:
    the embedding, each stage's layers one after another (a scan over the
    stage's stack), at each stage boundary the RD-FSQ round trip with a
    straight-through gradient (the gradient crosses back unquantized),
    final norm and head."""
    names = list(W.layer_leaves(c))
    x = params["embed"]["emb"][tokens]
    pos = jnp.arange(tokens.shape[1])
    body = jax.checkpoint(lambda x, p: (R.layer(c, p, x, pos, lp), None))
    for s in range(n_stages):
        if s:
            x_hat, _, _ = R.rdfsq(c, x)
            x = x + jax.lax.stop_gradient(x_hat - x)
        stage = {n: _get(params["blocks"], n)[s] for n in names}
        x, _ = jax.lax.scan(body, x, stage)
    top = {"final_norm": params["final_norm"], "head.w": params["head"]["w"]}
    logp = jax.nn.log_softmax(R.head(c, top, x, lp), axis=-1)
    mask = labels != IGNORE
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(nll * mask)


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _pipeline_block_grads(c, params, tokens, labels, scale, n_stages, lp):
    def f(params):
        return _pipeline_loss_sum(c, params, tokens, labels, n_stages,
                                  lp) * scale
    return jax.value_and_grad(f)(R.f32(params))


def pipeline_train_reference(c: Sizes, key, batches: Sequence[Dict],
                             opt: Dict, n_steps: int, row_block: int,
                             n_stages: int, lp: bool = False) -> Dict:
    """The plain reference of the pipeline's training job: ``n_steps``
    steps from the seeded weights, each step's text rows (``tokens`` and
    ``labels``, (rows, seq)) in blocks of ``row_block``, gradients summed;
    the loss is the mean over every labelled position of the step (the
    program's mean over micro-batches and data shards, all of one size).
    No micro-batches, no fill and drain, no collective.  Returns the loss
    of each step, the first clipped gradient's leaf norms and the leaf
    norms of the parameters' change after the last step, in the tree of
    ``pipeline_params``.  ``lp=True`` is the float8 control."""
    per = c.n_layers // n_stages
    top = R.top_params(c, key)
    layers = [R.layer_params(c, key, i) for i in range(c.n_layers)]
    params = W.nest({n: top[n] for n in PIPELINE_TOP})
    params["blocks"] = W.nest({name: jnp.stack([jnp.stack(
        [layers[s * per + j][name] for j in range(per)])
        for s in range(n_stages)]) for name in W.layer_leaves(c)})
    del top, layers
    p0 = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), params)
    v = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), params)
    o = (opt["lr"], opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
         opt["clip_norm"], W.DTYPES[c.param_dtype])
    losses, g1 = [], None
    with jax.default_matmul_precision("highest"):
        for t in range(n_steps):
            tokens, labels = batches[t]["tokens"], batches[t]["labels"]
            scale = jnp.asarray(
                1.0 / float((np.asarray(labels) != IGNORE).sum()), F32)
            loss, grads = 0.0, None
            for lo in range(0, tokens.shape[0], row_block):
                val, g = _pipeline_block_grads(
                    c, params, tokens[lo:lo + row_block],
                    labels[lo:lo + row_block], scale, n_stages, lp)
                loss += float(val)
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g)
            losses.append(loss)
            params, m, v, g = R.adamw(params, grads, m, v, o, t + 1)
            if t == 0:
                g1 = R.leaf_norms(g)
            del grads, g
    change = R.leaf_norms(jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))(params, p0))
    return dict(losses=losses, grad_norms=g1, change_norms=change)


# -- operation counts --------------------------------------------------------

token_weights = F.token_weights
prefill_flops = F.prefill_flops
decode_flops = F.decode_flops
train_step_flops = F.train_step_flops


def paged_decode(c: Sizes, context: int) -> Dict[str, float]:
    """One decoded token's attention over ``context`` cached positions,
    every layer: operations and bytes."""
    w = F.paged_decode(c, context)
    return dict(flops=w["flops"] * c.n_layers, bytes=w["bytes"] * c.n_layers)
