#!/usr/bin/env python3
"""Chip smoke run: the split-training and split-serve paths on a TPU.

    python chip_smoke.py              # one chip: train phase, serve phase
    python chip_smoke.py --chips 4    # four chips: split-pipeline phase only

Everything runs at tinyllava's published widths (16 layers, d_model 1280,
20/5 heads, d_ff 3456, vocab 32000, 729 image tokens) from random weights
(``tf.init_params``) and synthetic data (``data.pipeline.make_pipeline``)
made from ``--seed``; nothing is downloaded.  All phases run in this one
process, which holds the chip(s).

* train: ``train.loop.make_train_step`` on full tinyllava with the 2-bit
  RD-FSQ cut, batch 8 x 1024 tokens (729 image + 295 text), a few AdamW
  steps.  Every loss must be finite, the compiled step must hold the
  Pallas kernels, and step 0 must match the same step traced with the jnp
  attention reference.
* serve: ``ServeEngine`` in split-serve mode (2-bit RD-FSQ connector
  wire) over a bf16 paged pool; 16 requests of one image + a 64-token
  prompt, 32 new tokens each, over 8 slots.  The wire payload must come
  from the Pallas codec, the compiled decode tick must hold the Pallas
  kernels, the shipped bytes must equal ``WireLink.fwd_wire_bytes``, and
  the prefill and first decode-tick logits must match a jnp-reference
  engine fed the same tokens.
* pipeline (``--chips 4``): ``launch/split_pipeline.train_pipeline`` on
  a (pod=2, data=2) mesh, tinyllava's LM as two 8-layer stages joined by
  the 2-bit RD-FSQ Pallas codec on the ``ppermute``.  Each link's static
  wire bytes must match the compiled HLO, and the first-step loss must
  match a one-device forward that joins the stages by the codec's
  encode -> decode.

Times, rates and memory printed on the way are smoke numbers, not a
benchmark.  The last line of stdout is ``{"ok": true, "device": ...}``
and is printed only when every phase passed; the run exits non-zero when
JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Tolerances, fixed before the first chip run.  Both sides of each
# comparison run bf16 operands with fp32 accumulation; they differ in
# accumulation order (Pallas kernel blocks vs the jnp reference's chunks,
# or a sharded vs a one-device program), i.e. a few bf16 roundings
# (2^-8 relative each) carried through 16 layers.  A wrong kernel gives
# O(1) errors; these bounds sit one to two orders below that.
TRAIN_LOSS_RTOL = 5e-3    # step-0 loss vs the jnp-attention step
TRAIN_GNORM_RTOL = 5e-2   # step-0 gradient norm (flash backward kernels)
LOGITS_REL_L2 = 2e-2      # ||engine - jnp engine|| / ||jnp engine||
# sharded pipeline vs one-device reference: additionally a 2-bit code can
# flip where a bf16 rounding moves a value across a rounding boundary
PIPELINE_LOSS_ATOL = 2e-2


def _log(msg: str) -> None:
    print(msg, flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


@contextlib.contextmanager
def _backends(impl: str):
    """Trace attention and the wire codecs on ``impl`` inside the block
    (the repo's REPRO_ATTN_IMPL / REPRO_QUANT_IMPL selection)."""
    keys = ("REPRO_ATTN_IMPL", "REPRO_QUANT_IMPL")
    old = {k: os.environ.get(k) for k in keys}
    os.environ.update({k: impl for k in keys})
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _peak_gib(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 2 ** 30:.3f}"


def _rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# train phase
# ---------------------------------------------------------------------------

def train_phase(cfg, *, seed: int, batch: int = 8, seq: int = 1024,
                n_steps: int = 5) -> None:
    import jax

    from repro.data.pipeline import make_pipeline
    from repro.optim import AdamWConfig
    from repro.train.loop import init_state, make_train_step

    opt = AdamWConfig(lr=1e-4)
    state = init_state(jax.random.PRNGKey(seed), cfg, opt)
    data = make_pipeline(cfg, batch, seq, seed=seed)
    batches = [jax.device_put(next(data)) for _ in range(n_steps)]
    rngs = jax.random.split(jax.random.PRNGKey(seed + 1), n_steps)
    tokens_per_step = batch * seq

    with _backends("jnp"):
        ref_step = jax.jit(make_train_step(cfg, opt))
        ref = ref_step(state, batches[0], rngs[0])[1]  # drop its new state
        ref_loss = float(ref["loss"])
        ref_gnorm = float(ref["grad_norm"])
    del ref_step
    _log(f"[train] jnp-reference step 0: loss={ref_loss:.6f} "
         f"grad_norm={ref_gnorm:.6f}")

    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
    t0 = time.perf_counter()
    compiled = step.lower(state, batches[0], rngs[0]).compile()
    compile_s = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    _log(f"[train] compile_s={compile_s:.2f} (smoke number) "
         f"tpu_custom_call={n_kernels}")
    _check(n_kernels > 0, "compiled train step holds no Pallas kernel")

    losses = []
    for i in range(n_steps):
        t0 = time.perf_counter()
        state, m = compiled(state, batches[i], rngs[i])
        loss = float(m["loss"])  # waits for the step
        dt = time.perf_counter() - t0
        losses.append(loss)
        if i == 0:
            gnorm = float(m["grad_norm"])
        _log(f"[train] step {i} loss={loss:.6f} step_s={dt:.4f} "
             f"tokens_per_s={tokens_per_step / dt:.1f} (smoke numbers)")
        _check(math.isfinite(loss), f"train loss not finite at step {i}")

    d_loss = abs(losses[0] - ref_loss)
    d_gnorm = abs(gnorm - ref_gnorm)
    _log(f"[train] step 0 pallas vs jnp: |dloss|={d_loss:.3e} "
         f"(tol {TRAIN_LOSS_RTOL * abs(ref_loss):.3e}) "
         f"grad_norm {gnorm:.6f} vs {ref_gnorm:.6f} "
         f"|d|={d_gnorm:.3e} (tol {TRAIN_GNORM_RTOL * ref_gnorm:.3e})")
    _check(d_loss <= TRAIN_LOSS_RTOL * abs(ref_loss),
           "step-0 loss disagrees with the jnp reference")
    _check(d_gnorm <= TRAIN_GNORM_RTOL * ref_gnorm,
           "step-0 gradient norm disagrees with the jnp reference")
    _log(f"[train] PASS peak_gib={_peak_gib(jax)} (smoke number)")


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

def _tap_engine():
    import numpy as np

    from repro.serve.engine import ServeEngine

    class TapEngine(ServeEngine):
        """Records the host logits of every pick.  ``follow`` replays
        another engine's picks, so a reference engine decodes the same
        tokens even where a near-tie argmax would differ."""

        def __init__(self, *a, follow=None, **kw):
            super().__init__(*a, **kw)
            self.logits, self.picks, self._follow = [], [], follow

        def _pick(self, last_logits):
            self.logits.append(np.array(last_logits, np.float32))
            if self._follow is None:
                toks = super()._pick(last_logits)
            else:
                toks = self._follow[len(self.picks)]
            self.picks.append(np.asarray(toks))
            return toks

    return TapEngine


def serve_phase(cfg, *, seed: int, n_requests: int = 16, n_slots: int = 8,
                prompt_len: int = 64, max_new: int = 32,
                page_size: int = 16) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import quantizers
    from repro.core.split import WireLink
    from repro.data.pipeline import make_pipeline
    from repro.models import transformer as tf
    from repro.models.layers.mlp import mlp_forward

    tap = _tap_engine()
    params = tf.init_params(jax.random.PRNGKey(seed), cfg)
    n_img = cfg.n_image_tokens
    req = next(make_pipeline(cfg, n_requests, n_img + prompt_len,
                             seed=seed + 2))
    toks, imgs = np.asarray(req["tokens"]), np.asarray(req["image_embeds"])
    _check(toks.shape == (n_requests, prompt_len), f"prompts {toks.shape}")
    n_pages = 1 + n_slots * -(-(n_img + prompt_len + max_new) // page_size)
    wire = cfg.split.quant

    def engine(n, new, **kw):
        eng = tap(params, cfg, n_slots=n_slots, page_size=page_size,
                  n_pages=n_pages, split_wire=wire, **kw)
        for i in range(n):
            eng.submit(list(toks[i]), max_new=new, image_embeds=imgs[i])
        return eng

    eng = engine(n_requests, max_new)
    t0 = time.perf_counter()
    out = eng.run()
    run_s = time.perf_counter() - t0
    n_tok = sum(len(v) for v in out.values())
    _log(f"[serve] {n_requests} requests x {max_new} tokens over {n_slots} "
         f"slots: run_s={run_s:.2f} tokens_per_s={n_tok / run_s:.1f} "
         f"(smoke numbers, compile included) stats="
         f"{ {k: v for k, v in eng.stats.items() if k != 'page_table_buckets'} }")
    _check(all(len(v) == max_new for v in out.values()),
           "a request did not get all its tokens")

    # the wire: the engine's encode call on one admission wave
    rows = min(n_slots, n_requests)
    feats = mlp_forward(params["connector"],
                        jnp.asarray(imgs[:rows]).astype(tf.cdtype(cfg)))
    payload = quantizers.encode(wire, feats)
    impl = payload.meta.get("impl")
    static = WireLink(src=0, dst=1, quant=wire).fwd_wire_bytes(
        jax.ShapeDtypeStruct(feats.shape, feats.dtype))
    expected = eng.stats["prefill_batches"] * static
    _log(f"[serve] wire payload impl={impl} bytes/wave={payload.wire_bytes()} "
         f"engine wire_bytes={eng.stats['wire_bytes']} "
         f"static WireLink.fwd_wire_bytes x {eng.stats['prefill_batches']} "
         f"waves={expected}")
    _check(impl == "pallas", f"wire payload came from {impl!r}, not pallas")
    _check(eng.stats["prefill_batches"] == -(-n_requests // n_slots),
           f"expected full admission waves, got {eng.stats}")
    _check(eng.stats["wire_bytes"] == expected,
           "engine wire bytes disagree with the static link accounting")

    # the decode tick the engine ran, compiled again for its HLO
    npp = max(eng.stats["page_table_buckets"])
    hlo = eng._step_fn.lower(
        eng.params, eng.pools, dict(tokens=jnp.zeros((n_slots, 1), jnp.int32)),
        jnp.zeros((n_slots,), jnp.int32),
        jnp.zeros((n_slots, npp), jnp.int32)).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    _log(f"[serve] compiled decode tick (npp={npp}): "
         f"tpu_custom_call={n_kernels}")
    _check(n_kernels > 0, "decode tick holds no Pallas kernel")

    # logits parity: a jnp-reference engine replaying the same picks over
    # the first admission wave (prefill pick + first decode tick)
    with _backends("jnp"):
        ref = engine(rows, 2, follow=eng.picks[:2])
        ref.run()
    for name, i in (("prefill", 0), ("first decode tick", 1)):
        err = _rel_l2(eng.logits[i], ref.logits[i])
        _log(f"[serve] {name} logits {eng.logits[i].shape}: rel_l2 vs jnp "
             f"engine={err:.3e} (tol {LOGITS_REL_L2:.0e}) max_abs="
             f"{float(np.max(np.abs(eng.logits[i] - ref.logits[i]))):.3e}")
        _check(np.isfinite(eng.logits[i]).all(), f"{name} logits not finite")
        _check(err <= LOGITS_REL_L2, f"{name} logits disagree with jnp")
    _log(f"[serve] PASS peak_gib={_peak_gib(jax)} (smoke number)")


# ---------------------------------------------------------------------------
# four-chip split pipeline phase
# ---------------------------------------------------------------------------

def pipeline_phase(cfg, *, seed: int, n_steps: int = 3, n_micro: int = 4,
                   micro_batch: int = 8, seq: int = 512) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import quantizers
    from repro.core.split import SplitConfig
    from repro.core.split_stage import embed_tokens, head_ce, run_blocks
    from repro.data.pipeline import make_pipeline
    from repro.launch import split_pipeline as sp
    from repro.launch.mesh import make_mesh
    from repro.optim import AdamWConfig

    n_stages = 2
    mesh = make_mesh((n_stages, 2), ("pod", "data"),
                     devices=jax.devices()[:4])
    split = SplitConfig(quant=cfg.split.quant, learnable_codec=False,
                        n_stages=n_stages)
    params = sp.init_pipeline_params(jax.random.PRNGKey(seed), cfg,
                                     n_stages)
    text = make_pipeline(dataclasses.replace(cfg, modality="text"),
                         n_micro * micro_batch, seq, seed=seed)
    batches = []
    for _ in range(n_steps):
        b = next(text)
        batches.append((jnp.asarray(b["tokens"]).reshape(n_micro,
                                                         micro_batch, seq),
                        jnp.asarray(b["labels"]).reshape(n_micro,
                                                         micro_batch, seq)))

    # per-link wire bytes: static payload accounting vs the compiled HLO
    grad_step = sp.build_pipeline_grad_step(cfg, mesh, split, None, n_micro,
                                            micro_batch, seq)
    t0 = time.perf_counter()
    with mesh:
        compiled = jax.jit(grad_step).lower(params, *batches[0]).compile()
    _log(f"[pipeline] grad step compile_s={time.perf_counter() - t0:.2f} "
         f"(smoke number) tpu_custom_call="
         f"{compiled.as_text().count('tpu_custom_call')}")
    wire = sp.pipeline_wire_bytes(cfg, split, micro_batch, seq,
                                  data_shards=mesh.shape["data"])
    sp.assert_links_match_hlo("tinyllava rdfsq-2bit N=2", compiled.as_text(),
                              mesh, wire, n_micro + n_stages - 1,
                              check_bwd=True)

    # one-device reference of step 0: the stages joined by encode -> decode
    q = split.quant
    blocks = [jax.tree_util.tree_map(lambda a, s=s: a[s], params["blocks"])
              for s in range(n_stages)]

    @jax.jit
    def ref_loss(params, blocks, tokens, labels):
        pos = jnp.arange(seq, dtype=jnp.int32)

        def one(tok, lab):
            h = run_blocks(cfg, blocks[0], embed_tokens(cfg, params, tok),
                           pos)
            h = quantizers.decode(q, quantizers.encode(q, h)).astype(h.dtype)
            return head_ce(cfg, params, run_blocks(cfg, blocks[1], h, pos),
                           lab)

        return jnp.mean(jnp.stack([one(tokens[i], labels[i])
                                   for i in range(n_micro)]))

    ref = float(ref_loss(params, blocks, *batches[0]))

    t0 = time.perf_counter()
    _, _, history, wire_b = sp.train_pipeline(
        cfg, mesh, split, AdamWConfig(lr=1e-4), batches, n_micro=n_micro,
        micro_batch=micro_batch, seq=seq, params=params)
    run_s = time.perf_counter() - t0
    _log(f"[pipeline] losses {' -> '.join(f'{v:.6f}' for v in history)} "
         f"wire_bytes/tick={wire_b:.0f} run_s={run_s:.2f} "
         f"(smoke number, compile included)")
    _check(all(math.isfinite(v) for v in history), "pipeline loss not finite")
    d = abs(history[0] - ref)
    _log(f"[pipeline] step 0 loss {history[0]:.6f} vs one-device reference "
         f"{ref:.6f}: |d|={d:.3e} (tol {PIPELINE_LOSS_ATOL:.0e})")
    _check(d <= PIPELINE_LOSS_ATOL,
           "pipeline step-0 loss disagrees with the one-device reference")
    _log(f"[pipeline] PASS peak_gib(device 0)={_peak_gib(jax)} (smoke number)")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the two-stage split pipeline phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices))
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); nothing was run", file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {device['count']}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.utils.compile_cache import enable_compile_cache

    _log(f"[smoke] device {device} compile cache {enable_compile_cache()}")
    cfg = get_config("tinyllava")
    t0 = time.perf_counter()
    if args.chips == 4:
        pipeline_phase(cfg, seed=args.seed)
    else:
        train_phase(cfg, seed=args.seed)
        serve_phase(cfg, seed=args.seed)
    _check("repro.launch.dryrun" not in sys.modules,
           "the CPU dry-run module (it rewrites XLA_FLAGS) was imported")
    _log(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
