"""Faults planted under the timed path, to show that ``correct`` comes
out false for each.  Used by the CPU tests and by ``calibrate.py
--fault`` on the chip; a scored run never plants one.

Each fault is a function of a ``setattr``-like ``patch(obj, name, value)``
that replaces one piece of the program for the rest of the process.
"""
from __future__ import annotations

import numpy as np


def _wrap_train_step(patch, fault) -> None:
    from repro.train import loop

    orig = loop.make_train_step

    def make(cfg, opt, **kw):
        step = orig(cfg, opt, **kw)

        def broken(state, batch, rng):
            return fault(step, state, batch, rng)
        return broken
    patch(loop, "make_train_step", make)


def state_unchanged(patch) -> None:
    """The train step returns its state as it came in."""
    def fault(step, state, batch, rng):
        _, metrics = step(state, batch, rng)
        return state, metrics
    _wrap_train_step(patch, fault)


def half_batch(patch) -> None:
    """The train step leaves out half of the batch: the mean is taken
    over the rest."""
    def fault(step, state, batch, rng):
        half = batch["tokens"].shape[0] // 2
        return step(state, {k: (v[:half] if k != "positions" else v)
                            for k, v in batch.items()}, rng)
    _wrap_train_step(patch, fault)


def altered_token(patch) -> None:
    """The serving engine emits a wrong token at every third pick."""
    from repro.serve.engine import ServeEngine

    orig = ServeEngine._pick
    calls = []

    def pick(self, last_logits):
        toks = np.asarray(orig(self, last_logits))
        calls.append(1)
        if len(calls) % 3 == 0:
            toks = (toks + 1) % last_logits.shape[-1]
        return toks
    patch(ServeEngine, "_pick", pick)


def stale_tick(patch) -> None:
    """The decode tick returns the KV pools it was given, unchanged."""
    import jax

    from repro.serve import paged

    def stale(cfg, window=None, **kw):
        step = paged.make_paged_step(cfg, window=window)
        return jax.jit(lambda params, pools, batch, qpos, page_table: (
            step(params, pools, batch, qpos, page_table)[0], pools))
    patch(paged, "compiled_paged_step", stale)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  altered_token, stale_tick)}
