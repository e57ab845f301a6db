"""The one traffic generator: reads a mix's parameters and a seed.

Every seed gets the same multiset of sizes and gaps between arrivals,
drawn at fixed quantiles of the mix's distributions, and nearly the same
order: the values are taken in groups of ``order_group`` neighbouring
quantiles, the groups follow one fixed shuffled order, and the seed
orders the members of each group (and picks token ids and images).  So
two seeds do the same work at the same times, to within one group, and
a run's spread is the system's, not the draw's: which requests arrive
together in one tick, and which are still running at the close, no
longer changes with the seed.

Kinds of mix:

* ``train``: batches of (image embeddings, text tokens, next-token labels
  on the text), made on the device;
* ``serve``: requests with one image each, a text prompt and an output
  budget, arriving open-loop (``poisson``: the gaps of a Poisson process
  at the cell's rate) or all at once (``backlog``: an offline queue).
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List

import numpy as np

IGNORE = -100  # label of a position that carries no loss


def quantile_set(dist: Dict, n: int) -> np.ndarray:
    """``n`` integer sizes at the mid-quantiles of a clipped lognormal."""
    nd = statistics.NormalDist()
    u = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in u])
    vals = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def near_order(values: np.ndarray, rng, group: int,
               stream: int) -> np.ndarray:
    """``values`` in an order that every seed shares up to the order of
    ``group`` neighbouring quantiles; ``stream`` keeps the fixed orders of
    different quantities apart."""
    v = np.sort(values)
    groups = [v[i:i + group] for i in range(0, len(v), group)]
    fixed = np.random.default_rng([0, stream]).permutation(len(groups))
    return np.concatenate([rng.permutation(groups[g]) for g in fixed])


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` gaps at the mid-quantiles of an exponential of mean 1/rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclasses.dataclass
class ServeRequest:
    due: float            # seconds after the window opens
    prompt: List[int]
    max_new: int
    image: int            # index into the run's image pool


def serve_requests(mix: Dict, cell: Dict, cfg, seed: int,
                   seconds: float) -> List[ServeRequest]:
    rng = np.random.default_rng([seed, 1])
    group = int(mix["order_group"])
    if mix["arrivals"] == "poisson":
        rate = float(cell["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        due = np.cumsum(near_order(exponential_gaps(rate, n), rng, group, 1))
    elif mix["arrivals"] == "backlog":
        n = int(cell["backlog"])
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    prompts = near_order(quantile_set(mix["prompt_tokens"], n), rng, group, 2)
    outputs = near_order(quantile_set(mix["output_tokens"], n), rng, group, 3)
    images = rng.integers(0, mix["image_pool"], n)
    lo, hi = mix.get("token_ids", [1, cfg.vocab_size])
    return [ServeRequest(due=float(due[i]),
                         prompt=rng.integers(lo, hi, int(prompts[i])).tolist(),
                         max_new=int(outputs[i]), image=int(images[i]))
            for i in range(n)]


def image_pool(mix: Dict, cfg, key) -> np.ndarray:
    """(pool, n_image_tokens, d_vision) float32 vision-tower outputs,
    made on the device and copied to the host once."""
    import jax

    shape = (mix["image_pool"], cfg.n_image_tokens, cfg.d_vision)
    return np.asarray(jax.jit(
        lambda k: jax.random.normal(k, shape) * mix["image_std"])(key))


def train_batches(mix: Dict, cell: Dict, cfg, key) -> List[Dict]:
    """``mix['distinct_batches']`` batches, every row different, made on
    the device in one call."""
    import jax
    import jax.numpy as jnp

    b = int(cell["batch"])
    n_img = cfg.n_image_tokens
    seq = int(mix["seq_len"])
    text = seq - n_img
    if text < 2:
        raise ValueError(f"seq_len {seq} leaves no text after {n_img} "
                         f"image tokens")

    def one(k):
        k1, k2 = jax.random.split(k)
        img = jax.random.normal(k1, (b, n_img, cfg.d_vision)) \
            * mix["image_std"]
        tok = jax.random.randint(k2, (b, text), 0, cfg.vocab_size,
                                 jnp.int32)
        labels = jnp.full((b, seq), IGNORE, jnp.int32)
        labels = labels.at[:, n_img:seq - 1].set(tok[:, 1:])
        return dict(image_embeds=img, tokens=tok, labels=labels,
                    positions=jnp.arange(seq, dtype=jnp.int32))

    keys = jax.random.split(key, int(mix["distinct_batches"]))
    made = jax.jit(lambda ks: [one(k) for k in ks])(keys)
    return list(made)


def tokens_per_batch(mix: Dict, cell: Dict) -> int:
    return int(cell["batch"]) * int(mix["seq_len"])
