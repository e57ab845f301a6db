"""The model-module lookup moved nothing: for both configurations the
benchmark runs, at a reduced size, the dense module's weights, plain
reference and operation counts, and the drivers' window counts, equal
what the parent's code gave before the lookup existed.

The parent's readings are kept as constants, taken once on the CPU from
the parent's ``weights.py``, ``reference/model.py`` and ``flops.py``: the
SHA-256 of the bytes of every weight leaf, of the reference's logits and
training readings, and of every count over a grid of prompt lengths and
contexts; the drivers' window counts as numbers.  A change to what any of
these compute changes its digest.
"""
import hashlib
import os
import types

import numpy as np
import pytest

from conftest import ROOT

from bench.harness import serve_cell, spec, train_cell
from bench.harness import weights as W
from bench.models import dense_vlm

CONFIGS = ["tinyllava", "llava-next-34b"]
SMALL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab_size=256, n_image_tokens=8, d_vision=32,
             d_connector=64)
GRID = [(p, t) for p in (1, 16, 48, 256) for t in (1, 300, 777, 4096)]
OPT = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.01,
           clip_norm=1.0)

PARENT = {
    "tinyllava": dict(
        weights="a8d0e8a4a8a8f26f30d5bdef0b17abca"
                "f097b064d551f58c0be41fc9eae51e38",
        serve_logits="9aa6491ae9a10bdd048f338389a9c78d"
                     "e24394e55f97f58436acc921e58f1f19",
        train_reference="d32cef0ef132eea1a13883d197bfc229"
                        "c92c482ef1fa98f581abdfb6b72fe2f5",
        counts="5a2eeabd968c0e2e93369dbf348076d2"
               "a122bf45b36779585d9dab8cb7bc24bd",
        train_window=315746156544.0,
        serve_window=130738432.0),
    "llava-next-34b": dict(
        weights="a8d0e8a4a8a8f26f30d5bdef0b17abca"
                "f097b064d551f58c0be41fc9eae51e38",
        serve_logits="8a3c5312079af48a0c93021a3a51c55f"
                     "5da31587dcc976ce4ef3bfa67e4da7fd",
        train_reference="c5670ca7be5e88f94e0f10be9c8c72d1"
                        "e07b77b6aba354569dcb34cd2d3d6090",
        counts="d852f26678b001d32aaac0798c241176"
               "0cf37c74b7afcbbb7ea6db46000681ac",
        train_window=315746156544.0,
        serve_window=130738432.0),
}


def _config(name):
    conf = spec.load_json(os.path.join(ROOT, "bench", "configs",
                                       name + ".json"))
    return dict(conf, **SMALL)


def _sizes(name):
    conf = _config(name)
    assert spec.model_module(conf) is dense_vlm
    return spec.sizes(conf)


def _sha(*chunks) -> str:
    h = hashlib.sha256()
    for c in chunks:
        h.update(c if isinstance(c, bytes) else repr(c).encode())
    return h.hexdigest()


def weights_digest(module, c) -> str:
    import jax

    tree = module.init_params(c, W.base_key(2 ** 33 + 5))
    return _sha(*[(jax.tree_util.keystr(p), str(x.dtype), x.shape,
                   np.asarray(x).view(np.uint8).tobytes())
                  for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]])


def serve_logits_digest(module, c) -> str:
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    img = jax.random.normal(k1, (2, c.n_image_tokens, c.d_vision))
    lb = 24
    prompt = jax.random.randint(k2, (2, lb - c.n_image_tokens), 1,
                                c.vocab_size)
    served = jax.random.randint(k3, (2, 4), 1, c.vocab_size)
    plen = jnp.asarray([5, 9], jnp.int32)
    return _sha(*[np.asarray(module.serve_logits(
        c, W.base_key(7), img, prompt, plen, served, lb, lp=lp)).tobytes()
        for lp in (False, True)])


def train_reference_digest(module, c) -> str:
    from bench.harness import traffic

    key = W.base_key(11)
    mix = dict(seq_len=c.n_image_tokens + 8, image_std=1.0,
               distinct_batches=2)
    batches = traffic.train_batches(mix, dict(batch=4), c, W.sub_key(key, 2))
    out = module.train_reference(c, key, batches, OPT, 2, 2)
    return _sha(sorted(out.items()))


def counts_digest(module, name) -> str:
    """Every count the readers use, at the reduced size and as
    committed."""
    seen = []
    for conf in (_config(name), spec.load_json(os.path.join(
            ROOT, "bench", "configs", name + ".json"))):
        c = spec.sizes(conf)
        seen.append(module.token_weights(c))
        seen += [module.train_step_flops(c, b, s)
                 for b, s in ((32, 1024), (4, 24), (64, 512))]
        for prompt, ctx in GRID:
            w = module.paged_decode(c, ctx)
            seen += [module.prefill_flops(c, prompt),
                     module.decode_flops(c, ctx), w["flops"], w["bytes"]]
    return _sha(seen)


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_bit_identical(name):
    assert weights_digest(dense_vlm, _sizes(name)) == PARENT[name]["weights"]


@pytest.mark.parametrize("name", CONFIGS)
def test_serve_logits_bit_identical(name):
    assert serve_logits_digest(dense_vlm, _sizes(name)) == \
        PARENT[name]["serve_logits"]


@pytest.mark.parametrize("name", CONFIGS)
def test_train_reference_bit_identical(name):
    assert train_reference_digest(dense_vlm, _sizes(name)) == \
        PARENT[name]["train_reference"]


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_equal_over_a_grid(name):
    assert counts_digest(dense_vlm, name) == PARENT[name]["counts"]


def served_requests():
    return [types.SimpleNamespace(tokens=[1] * p, out=[2] * o)
            for p, o in ((16, 8), (256, 128), (48, 0), (31, 1))]


@pytest.mark.parametrize("name", CONFIGS)
def test_driver_window_counts_equal_the_parent_readers(name):
    """The drivers' counts equal what the parent's ``train_mfu`` (seven
    steps of 32 x 1024) and ``serve_mfu`` (these requests) summed."""
    run = types.SimpleNamespace(
        sizes=_sizes(name), model=dense_vlm,
        cell=types.SimpleNamespace(params={"batch": 32},
                                   traffic={"seq_len": 1024}))
    assert train_cell.window_flops(run, dict(steps=7)) == \
        PARENT[name]["train_window"]
    assert serve_cell.window_flops(run, dict(served=served_requests())) == \
        PARENT[name]["serve_window"]
