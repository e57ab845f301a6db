"""Schedulers — who ticks when (layer 3 of the split stack).

Layer 1 (``repro.core.split_stage``) defines what one partition computes;
layer 2 (``repro.core.split.WireLink``) defines how activations and
cotangents cross between partitions.  This module composes them into
executable training schedules:

* :func:`build_gpipe_step` / :func:`build_gpipe_grad_step` — the paper's
  lockstep pipeline: ``n_stages`` partitions on the ``pod`` mesh axis,
  GPipe fill/drain over ``n_micro + n_stages - 1`` microbatch ticks, one
  quantized ship per cut group per tick.  This is the former
  ``launch/split_pipeline.build_pipeline_step`` re-expressed over stage
  programs + wire links (``launch/split_pipeline`` is now a thin
  composition that delegates here).

* :func:`build_hub_step` / :func:`build_hub_grad_step` — the many-client
  hub (ROADMAP item 2, BEYOND-PAPER): N client stages share ONE server
  stage.  Clients embed + run their bottom halves in parallel pods; each
  ships across its own :class:`~repro.core.split.WireLink` (per-client
  quantizers — ppermute forbids grouping links into one collective when
  the destination repeats, so hub ships are per-link by construction);
  the server executes its half ONCE, batched over the N arrivals
  ``(N*B, S, D)``, and computes a per-client CE.  The backward pass
  returns each client's cotangent across its link (optionally quantized:
  gradient aggregation across clients crosses the backward wire in wire
  form), while the shared server parameters accumulate gradients from
  all clients' batched execution.

* :func:`arrival_mask` + :func:`build_async_update` — the
  staleness-tolerant async mode: clients tick at different rates
  (``HubConfig.tick_rates``); at every global tick the server applies
  gradients for exactly the clients that arrived (mask-gated, so one
  compiled update serves every arrival pattern).  Client bottom halves
  only update when their own gradient returns, so slow clients train
  against a server that moved on — the staleness the scheduler must
  tolerate.  The transport here is the *in-graph* wire form (STE
  roundtrip forward, :func:`~repro.core.split.quantize_cotangent`
  backward) because client and server are co-located in one program; the
  lockstep schedulers above exercise the real collective-permute wire,
  and their per-link bytes are asserted against the lowered HLO.

Wire-byte accounting contract (the heterogeneous-quant fix): every
helper here reports bytes PER LINK, each link counted exactly once on
the devices that execute it.  ``fwd_tick``/``bwd_tick`` are per-device
per-tick bytes — the MAX over links of the device's payload slice (a
device sources at most one link per tick), NOT the old sum over distinct
cut configs, which overcounted whenever ``stage_quants`` mixed widths.
``links[(src, dst)]`` carries each link's full per-tick traffic (slice x
data shards) — the quantity asserted against the HLO collective-permute
bytes via :func:`pod_link_bytes`.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import entropy as entropy_mod
from repro.core import quantizers
from repro.core.quantizers import QuantConfig
from repro.core.split import (HubConfig, SplitConfig, WireLink, group_links,
                              init_wire_calib, pipeline_links,
                              quantize_cotangent, quantized_ship,
                              update_wire_calib)
from repro.core.split_stage import (embed_tokens, head_ce, init_stage_params,
                                    run_blocks, stage_param_specs)
from repro.models import transformer as tf
from repro.optim import AdamWConfig, adamw_update, init_opt_state
from repro.train.losses import IGNORE, cross_entropy


# ---------------------------------------------------------------------------
# per-link wire accounting
# ---------------------------------------------------------------------------

def _link_bytes(links: Tuple[WireLink, ...], x_sds,
                data_shards: int, grad_sds=None) -> Dict:
    """The per-link byte table shared by chain and hub topologies.

    ``x_sds`` is ONE device's activation slice (micro_batch/data_shards).
    ``grad_sds`` (SplitLoRA) is one stage's adapter-grad slice *tree*:
    each link then carries a ``grad`` entry — ONE direction of the
    adapter-grad return payload, crossed once per step (up and back, not
    per tick).  Full fine-tuning has no gradient-return collective
    (parameters update in place on their own pods), so ``grad`` is 0.
    """
    table = {}
    fwd_slice = []
    bwd_slice = []
    for link in links:
        f = link.fwd_wire_bytes(x_sds)
        b = link.bwd_wire_bytes(x_sds)
        g = link.grad_wire_bytes(grad_sds) if grad_sds is not None else 0
        # grouped plans report their widths tuple (the per-group bit
        # allocation); static links report the single width — both render
        # in the dry-run link tables and key the byte assertions
        table[(link.src, link.dst)] = dict(
            fwd=f * data_shards, bwd=b * data_shards,
            grad=g * data_shards,
            quant=link.quant.method,
            bits=(link.plan if link.quant.grouped else link.quant.bits))
        fwd_slice.append(f)
        bwd_slice.append(b)
    return dict(
        links=table,
        # per-device per-tick: a device sources at most one link per tick,
        # so its wire load is the largest single link slice — NOT the sum
        # over distinct configs (the old heterogeneous-quant overcount)
        fwd_tick=max(fwd_slice),
        bwd_tick=max(bwd_slice),
        # whole-topology traffic per tick, each link counted exactly once
        fwd_total=sum(v["fwd"] for v in table.values()),
        bwd_total=sum(v["bwd"] for v in table.values()),
        # whole-topology adapter-grad return per STEP, one direction
        grad_total=sum(v["grad"] for v in table.values()),
    )


def chain_wire_bytes(cfg: ArchConfig, split: SplitConfig, micro_batch: int,
                     seq: int, bwd_qcfg: Optional[QuantConfig] = None,
                     data_shards: int = 1) -> Dict:
    """Per-link static wire bytes of the lockstep chain pipeline."""
    assert micro_batch % data_shards == 0, (micro_batch, data_shards)
    x_sds = jax.ShapeDtypeStruct(
        (micro_batch // data_shards, seq, cfg.d_model), tf.cdtype(cfg))
    return _link_bytes(pipeline_links(split, bwd_qcfg), x_sds, data_shards)


def hub_wire_bytes(cfg: ArchConfig, hub: HubConfig, micro_batch: int,
                   seq: int, data_shards: int = 1,
                   lora_rank: int = 0) -> Dict:
    """Per-link static wire bytes of the N-client hub.

    With ``lora_rank > 0`` each link additionally reports its SplitLoRA
    adapter-grad return payload (``grad``): the quantized adapter-grad
    tree of ONE stage, crossed up + back once per step.
    """
    assert micro_batch % data_shards == 0, (micro_batch, data_shards)
    x_sds = jax.ShapeDtypeStruct(
        (micro_batch // data_shards, seq, cfg.d_model), tf.cdtype(cfg))
    grad_sds = None
    if lora_rank > 0:
        ad = jax.eval_shape(
            lambda: init_stage_params(jax.random.PRNGKey(0), cfg,
                                      hub.n_clients + 1, cfg.n_layers // 2,
                                      lora_rank=lora_rank))["adapters"]
        # one stage's slice of the stage-stacked adapter tree — what a
        # single client link actually returns
        grad_sds = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), ad)
    return _link_bytes(hub.links(), x_sds, data_shards, grad_sds=grad_sds)


def pod_link_bytes(pair_bytes: Dict[Tuple[int, int], int], mesh,
                   axis: str = "pod") -> Dict[Tuple[int, int], int]:
    """Aggregate HLO per-device-pair collective-permute bytes into
    per-stage-link bytes.

    ``pair_bytes`` comes from ``hlo_analysis.collective_permute_pairs``
    (device ids); the mesh maps each device to its ``axis`` coordinate.
    Summing the data-shard pairs of one stage link recovers that link's
    full traffic — comparable to ``links[(src, dst)]`` in the static
    tables above.  Assumes HLO partition ids coincide with the mesh's
    device ids (true for the fake-device meshes the dry-runs build, where
    ``make_mesh`` lays devices out in id order).
    """
    ax = mesh.axis_names.index(axis)
    devs = np.moveaxis(mesh.devices, ax, 0)
    pod_of = {}
    for pod in range(devs.shape[0]):
        for d in devs[pod].reshape(-1):
            pod_of[d.id] = pod
    out: Dict[Tuple[int, int], int] = {}
    for (a, b), v in pair_bytes.items():
        key = (pod_of[a], pod_of[b])
        out[key] = out.get(key, 0) + v
    return out


# ---------------------------------------------------------------------------
# entropy-adaptive re-planning (between compiled steps)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0, 3))
def boundary_probe(cfg: ArchConfig, params: Dict, tokens: jnp.ndarray,
                   stage: int = 0) -> jnp.ndarray:
    """Host-side probe of one stage's boundary activation (what its
    outgoing wire link ships): embed + that stage's block stack on a
    (B, S) token microbatch.  Runs OUTSIDE the shard_map schedules, on
    replicated parameters, between compiled steps — the adaptive wire's
    entropy signal is a statistic, so a single-microbatch replicated
    probe is enough (and keeps the compiled step plan-static).
    """
    blocks = jax.tree_util.tree_map(lambda a: a[stage], params["blocks"])
    x = embed_tokens(cfg, params, tokens, tf.cdtype(cfg))
    positions = jnp.arange(tokens.shape[-1], dtype=jnp.int32)
    return run_blocks(cfg, blocks, x, positions)


def replan_widths(ema_state: Dict, budget_bytes: float, *, n_groups: int,
                  scalars_per_channel: int,
                  min_bits: int = 1) -> Tuple[int, ...]:
    """One re-planning decision: EMA entropy readout -> greedy allocation.

    ``budget_bytes`` budgets the CODE bytes of one shipped activation
    slice (scale side-info rides on top — it is identical across plans
    of the same group count, so it cancels out of plan comparisons).
    Deterministic for a given state, so repeated calls with an unchanged
    signal return the same plan and the jit caches keyed on it hit.
    """
    ent = entropy_mod.entropy_ema_bits(ema_state)
    group_size = ent.shape[0] // n_groups
    return entropy_mod.allocate_bits(
        ent, budget_bytes, group_size=group_size,
        scalars_per_channel=scalars_per_channel, min_bits=min_bits)


def replan_grouped(ema_state: Dict, budget_bytes: float, *, n_groups: int,
                   scalars_per_channel: int, min_bits: int = 1
                   ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Sorted-grouping re-plan: ``(channel_perm, group_widths)``.

    Like :func:`replan_widths` but channels are gathered into ascending
    entropy order before grouping (``QuantConfig.channel_perm``), which
    keeps the per-channel spread visible to the allocator instead of
    averaging it into near-uniform group means.  Use this on boundaries
    with real channel heterogeneity (e.g. the VLM connector wire).
    """
    ent = entropy_mod.entropy_ema_bits(ema_state)
    group_size = ent.shape[0] // n_groups
    return entropy_mod.plan_grouped(
        ent, budget_bytes, group_size=group_size,
        scalars_per_channel=scalars_per_channel, min_bits=min_bits)


# ---------------------------------------------------------------------------
# lockstep GPipe chain (the paper's pipeline, re-expressed over the layers)
# ---------------------------------------------------------------------------

def build_gpipe_step(cfg: ArchConfig, mesh, split: SplitConfig,
                     n_micro: int, micro_batch: int, seq: int,
                     bwd_qcfg: Optional[QuantConfig] = None,
                     lora_rank: int = 0):
    """Lockstep fill/drain pipeline step over stage programs + wire links.

    Returns fn(params, tokens, labels) -> (loss, wire_bytes) with
    ``tokens``/``labels`` (n_micro, B, S) int32 and ``wire_bytes`` the
    per-device per-tick forward payload (compile-time constant; see the
    module docstring for the per-link contract).

    ``lora_rank > 0`` (SplitLoRA): ``params`` carries an ``"adapters"``
    stack mirroring ``"blocks"``; every stage runs on the effective
    weights ``w + A @ B`` while the base leaves stay frozen.
    """
    n_stages = split.n_stages
    assert cfg.n_layers % n_stages == 0
    assert mesh.shape["pod"] == n_stages, \
        f"mesh pod axis {mesh.shape['pod']} != n_stages {n_stages}"
    dtype = tf.cdtype(cfg)
    links = pipeline_links(split, bwd_qcfg)
    # chain cuts with identical configs share ONE multi-pair collective
    groups = group_links(links)
    wire = chain_wire_bytes(cfg, split, micro_batch, seq, bwd_qcfg,
                            data_shards=mesh.shape["data"])
    last = n_stages - 1

    param_specs = stage_param_specs(cfg, n_stages, lora_rank=lora_rank)
    tok_spec = P(None, "data", None)  # (n_micro, B, S)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(param_specs, tok_spec, tok_spec),
             out_specs=(P(), P()),
             check_vma=False)
    def step(params, tokens, labels):
        stage = jax.lax.axis_index("pod")
        my_blocks = jax.tree_util.tree_map(lambda a: a[0],
                                           params["blocks"])
        my_adapters = None if lora_rank == 0 else \
            jax.tree_util.tree_map(lambda a: a[0], params["adapters"])
        positions = jnp.arange(seq, dtype=jnp.int32)

        def tick(carry, xs):
            recv = carry  # activation received on the previous tick
            tok, lab = xs
            x_emb = embed_tokens(cfg, params, tok, dtype)
            x_in = jnp.where(stage == 0, x_emb, recv.astype(x_emb.dtype))
            h = run_blocks(cfg, my_blocks, x_in, positions,
                           adapters=my_adapters)
            # ship across every cut; a stage keeps the payload arriving
            # from its own upstream cut (cut c feeds stage c+1)
            recv_new = jnp.zeros_like(h)
            for qcfg, bq, glinks in groups:
                perm = tuple((lk.src, lk.dst) for lk in glinks)
                out_q = quantized_ship(qcfg, h, "pod", perm, bq)
                is_dst = jnp.zeros((), jnp.bool_)
                for lk in glinks:
                    is_dst = is_dst | (stage == lk.dst)
                recv_new = jnp.where(is_dst, out_q.astype(h.dtype),
                                     recv_new)
            # last-stage head + next-token CE on this tick's microbatch.
            # lax.cond, not a computed-then-masked jnp.where: the vocab
            # projection is the widest matmul in the model and only 1/N
            # of the stages needs it — the branch keeps the SPMD program
            # identical while sparing the other stages the work.
            ce = jax.lax.cond(stage == last,
                              lambda hh: head_ce(cfg, params, hh, lab),
                              lambda hh: jnp.zeros((), jnp.float32), h)
            return recv_new, ce

        # GPipe fill/drain: microbatch j enters stage 0 at tick j and
        # reaches the last stage at tick j + (n_stages - 1), so the scan
        # runs n_micro + n_stages - 1 ticks; stage 0 consumes dummy
        # tokens while draining and the last stage sees IGNORE labels
        # while filling (masked to CE = 0 by cross_entropy).
        pad_tok = jnp.zeros((last,) + tokens.shape[1:], tokens.dtype)
        tok_feed = jnp.concatenate([tokens, pad_tok], axis=0)
        pad_lab = jnp.full((last,) + labels.shape[1:], IGNORE, labels.dtype)
        lab_feed = jnp.concatenate([pad_lab, labels], axis=0)

        init = jnp.zeros((tokens.shape[1], seq, cfg.d_model), dtype)
        _, ces = jax.lax.scan(tick, init, (tok_feed, lab_feed))
        # sum over pod (only the last stage contributes), mean over the
        # data shards (each computed CE on its local microbatch slice)
        loss = jax.lax.pmean(jax.lax.psum(jnp.sum(ces), "pod"),
                             "data") / n_micro
        return loss, jnp.asarray(wire["fwd_tick"], jnp.float32)

    return step


def build_gpipe_grad_step(cfg: ArchConfig, mesh, split: SplitConfig,
                          bwd_qcfg: Optional[QuantConfig], n_micro: int,
                          micro_batch: int, seq: int, lora_rank: int = 0):
    """Differentiates the chain pipeline loss wrt the stage parameters,
    exercising the gradient-return wire.  Returns
    fn(params, tokens, labels) -> (loss, grads, wire_bytes).

    ``lora_rank > 0``: differentiates wrt ``params["adapters"]`` ONLY —
    ``grads`` mirrors the adapter tree, base weights are never touched by
    autodiff (frozen by construction, not by masking)."""
    step = build_gpipe_step(cfg, mesh, split, n_micro, micro_batch, seq,
                            bwd_qcfg=bwd_qcfg, lora_rank=lora_rank)
    wire = chain_wire_bytes(cfg, split, micro_batch, seq, bwd_qcfg,
                            data_shards=mesh.shape["data"])
    tick_bytes = float(wire["fwd_tick"] + wire["bwd_tick"])

    def grad_step(params, tokens, labels):
        if lora_rank > 0:
            base = {k: v for k, v in params.items() if k != "adapters"}

            def loss_fn_ad(ad):
                loss, _ = step(dict(base, adapters=ad), tokens, labels)
                return loss

            loss, grads = jax.value_and_grad(loss_fn_ad)(
                params["adapters"])
            return loss, grads, jnp.asarray(tick_bytes, jnp.float32)

        def loss_fn(p):
            loss, _ = step(p, tokens, labels)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return loss, grads, jnp.asarray(tick_bytes, jnp.float32)

    return grad_step


# ---------------------------------------------------------------------------
# lockstep hub: N clients + 1 shared server stage
# ---------------------------------------------------------------------------

def build_hub_step(cfg: ArchConfig, mesh, hub: HubConfig, n_micro: int,
                   micro_batch: int, seq: int, lora_rank: int = 0):
    """Lockstep hub step: pods 0..N-1 run client stages, pod N the server.

    Returns fn(params, tokens, labels) -> (loss, per_client_ce, wire_bytes)
    with ``tokens``/``labels`` (n_micro, n_clients, B, S) int32,
    ``per_client_ce`` (n_clients,) microbatch-averaged CE per client and
    ``wire_bytes`` the per-device per-tick forward payload constant.

    Schedule: at tick t every client embeds + runs microbatch t and ships
    across its own link; the server runs its half ONCE over the N
    payloads that arrived at tick t-1 — batched ``(N*B, S, D)`` stage
    execution — and computes each client's CE.  ``n_micro + 1`` ticks
    (1-tick fill/drain, the 2-stage GPipe special case per client).  With
    ``n_clients == 1`` this is exactly the paper's 2-partition pipeline
    and reproduces its loss (parity-tested to 3e-6).
    """
    n_clients = hub.n_clients
    assert cfg.n_layers % 2 == 0, cfg.n_layers
    per_stage = cfg.n_layers // 2
    assert mesh.shape["pod"] == n_clients + 1, \
        f"mesh pod axis {mesh.shape['pod']} != n_clients+1 {n_clients + 1}"
    dtype = tf.cdtype(cfg)
    links = hub.links()
    wire = hub_wire_bytes(cfg, hub, micro_batch, seq,
                          data_shards=mesh.shape["data"],
                          lora_rank=lora_rank)

    param_specs = stage_param_specs(cfg, n_clients + 1, per_stage,
                                    lora_rank=lora_rank)
    tok_spec = P(None, None, "data", None)  # (n_micro, N, B, S)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(param_specs, tok_spec, tok_spec),
             out_specs=(P(), P(), P()),
             check_vma=False)
    def step(params, tokens, labels):
        pod = jax.lax.axis_index("pod")
        is_server = pod == n_clients
        my_blocks = jax.tree_util.tree_map(lambda a: a[0],
                                           params["blocks"])
        my_adapters = None if lora_rank == 0 else \
            jax.tree_util.tree_map(lambda a: a[0], params["adapters"])
        positions = jnp.arange(seq, dtype=jnp.int32)
        b_local = tokens.shape[2]

        def tick(recv, xs):
            # recv: (N, B, S, D) — the payloads the server received on the
            # previous tick (zeros on client pods, which ignore it)
            tok, lab = xs  # (N, B, S) replicated over pod
            my_tok = tok[jnp.clip(pod, 0, n_clients - 1)]

            def client_fwd(r):
                x = embed_tokens(cfg, params, my_tok, dtype)
                h = run_blocks(cfg, my_blocks, x, positions,
                               adapters=my_adapters)
                # slot 0 carries this client's payload to the ship ops
                out = jnp.zeros_like(r)
                return out.at[0].set(h)

            def server_fwd(r):
                # batched stage execution over the N arrivals
                hs = r.reshape((n_clients * b_local, seq, cfg.d_model))
                hs = run_blocks(cfg, my_blocks, hs, positions,
                                adapters=my_adapters)
                return hs.reshape(r.shape)

            h_all = jax.lax.cond(is_server, server_fwd, client_fwd, recv)

            # one ship per link (a shared destination cannot be grouped
            # into one ppermute); link c moves pod c's slot-0 activation
            # to the server, which files it under arrival slot c
            recv_new = jnp.zeros_like(recv)
            for link in links:
                y = link.ship(h_all[0], "pod")
                recv_new = recv_new.at[link.client].set(
                    jnp.where(is_server, y.astype(recv.dtype),
                              recv_new[link.client]))

            def server_ce(hh):
                return jax.vmap(lambda h, l: head_ce(cfg, params, h, l))(
                    hh, lab)

            ces = jax.lax.cond(
                is_server, server_ce,
                lambda hh: jnp.zeros((n_clients,), jnp.float32), h_all)
            return recv_new, ces

        # 1-tick fill: microbatch t ships at tick t, is served at t+1
        pad_tok = jnp.zeros((1,) + tokens.shape[1:], tokens.dtype)
        tok_feed = jnp.concatenate([tokens, pad_tok], axis=0)
        pad_lab = jnp.full((1,) + labels.shape[1:], IGNORE, labels.dtype)
        lab_feed = jnp.concatenate([pad_lab, labels], axis=0)

        init = jnp.zeros((n_clients, b_local, seq, cfg.d_model), dtype)
        _, ces = jax.lax.scan(tick, init, (tok_feed, lab_feed))
        per_client = jax.lax.pmean(
            jax.lax.psum(jnp.sum(ces, axis=0), "pod"), "data") / n_micro
        loss = jnp.mean(per_client)
        return (loss, per_client,
                jnp.asarray(wire["fwd_tick"], jnp.float32))

    return step


def build_hub_grad_step(cfg: ArchConfig, mesh, hub: HubConfig,
                        n_micro: int, micro_batch: int, seq: int,
                        lora_rank: int = 0):
    """Differentiates the hub loss wrt the stage parameters.  The shared
    server stage accumulates gradients from every client's batched
    execution; each client's cotangent returns across its own link
    (quantized when ``hub.bwd_quant`` is set).  Returns
    fn(params, tokens, labels) -> (loss, per_client_ce, grads, bytes).

    ``lora_rank > 0`` (SplitLoRA): differentiates wrt
    ``params["adapters"]`` only, and the returned/applied gradient
    traffic shrinks to the adapter-grad payload: each client link
    round-trips its stage's quantized adapter-grad tree across the wire
    (``hub.grad_quant`` codec; see ``core.split.grad_return_trip``) and
    the DECODED gradients are what the optimizer applies — the traffic is
    real collective-permutes, asserted against HLO by the extended
    ``assert_links_match_hlo``.
    """
    step = build_hub_step(cfg, mesh, hub, n_micro, micro_batch, seq,
                          lora_rank=lora_rank)
    wire = hub_wire_bytes(cfg, hub, micro_batch, seq,
                          data_shards=mesh.shape["data"],
                          lora_rank=lora_rank)
    tick_bytes = float(wire["fwd_tick"] + wire["bwd_tick"])

    if lora_rank == 0:
        def grad_step(params, tokens, labels):
            def loss_fn(p):
                loss, per_client, _ = step(p, tokens, labels)
                return loss, per_client

            (loss, per_client), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return loss, per_client, grads, jnp.asarray(tick_bytes,
                                                        jnp.float32)

        return grad_step

    # -- SplitLoRA: adapter-grad-only gradient return over the real wire
    n_clients = hub.n_clients
    links = hub.links()
    ad_specs = stage_param_specs(cfg, n_clients + 1, cfg.n_layers // 2,
                                 lora_rank=lora_rank)["adapters"]

    @partial(jax.shard_map, mesh=mesh, in_specs=(ad_specs,),
             out_specs=ad_specs, check_vma=False)
    def grad_return(g):
        # every pod holds its own stage's adapter-grad slice; each client
        # link round-trips that slice (encode -> ship to server -> server
        # returns the accepted payload -> decode) so the grads the
        # optimizer sees have honestly crossed the codec both ways.  The
        # server's own adapter grads are local (no wire).
        pod = jax.lax.axis_index("pod")
        g0 = jax.tree_util.tree_map(lambda a: a[0], g)
        out = g0
        for link in links:
            trip = link.grad_trip(g0, "pod")
            out = jax.tree_util.tree_map(
                lambda t, o: jnp.where(pod == link.src, t, o), trip, out)
        return jax.tree_util.tree_map(lambda a: a[None], out)

    def grad_step(params, tokens, labels):
        base = {k: v for k, v in params.items() if k != "adapters"}

        def loss_fn(ad):
            loss, per_client, _ = step(dict(base, adapters=ad),
                                       tokens, labels)
            return loss, per_client

        (loss, per_client), g_ad = jax.value_and_grad(
            loss_fn, has_aux=True)(params["adapters"])
        g_ad = grad_return(g_ad)
        return loss, per_client, g_ad, jnp.asarray(tick_bytes,
                                                   jnp.float32)

    return grad_step


# ---------------------------------------------------------------------------
# async mode: per-arrival server updates, staleness-tolerant clients
# ---------------------------------------------------------------------------

def arrival_mask(tick_rates: Tuple[int, ...],
                 n_ticks: int) -> np.ndarray:
    """(n_ticks, n_clients) bool: client c arrives when t % rate_c == 0."""
    t = np.arange(n_ticks)[:, None]
    rates = np.asarray(tick_rates)[None, :]
    return (t % rates) == 0


def init_hub_state(key, cfg: ArchConfig, hub: HubConfig,
                   opt_cfg: AdamWConfig, lora_rank: int = 0) -> Dict:
    """Async-hub training state.

    ``server``: the shared pieces (server blocks, embed table, head, final
    norm) with one optimizer, stepped per arrival.  ``client``: the
    per-client bottom-half block stacks (N, L/2, ...) with per-client
    AdamW moments and step counts — a client's state only advances when
    its own gradient arrives.  ``calib``: per-client wire calibration
    EMAs (N-stacked :func:`~repro.core.split.init_wire_calib`), isolated
    per client.

    ``lora_rank > 0`` (SplitLoRA): every block stack is frozen; the state
    instead carries ``client_adapters`` (N-stacked LoRA trees) and the
    server params gain an ``"adapters"`` entry, with BOTH optimizers
    sized by the adapter trees only.
    """
    from repro.train.loop import TrainState

    n = hub.n_clients
    params = init_stage_params(key, cfg, n + 1, cfg.n_layers // 2,
                               lora_rank=lora_rank)
    client_blocks = jax.tree_util.tree_map(lambda a: a[:n],
                                           params["blocks"])
    server_params = dict(
        blocks=jax.tree_util.tree_map(lambda a: a[n], params["blocks"]),
        embed=params["embed"], head=params["head"],
        final_norm=params["final_norm"])
    calib = jax.tree_util.tree_map(
        lambda z: jnp.zeros((n,) + z.shape, z.dtype), init_wire_calib())
    if lora_rank > 0:
        client_adapters = jax.tree_util.tree_map(lambda a: a[:n],
                                                 params["adapters"])
        server_params["adapters"] = jax.tree_util.tree_map(
            lambda a: a[n], params["adapters"])
        client_opt = init_opt_state(client_adapters, opt_cfg)
        client_opt["step"] = jnp.zeros((n,), jnp.int32)
        return dict(
            server=TrainState(
                params=server_params,
                opt=init_opt_state(server_params["adapters"], opt_cfg),
                step=jnp.zeros((), jnp.int32)),
            client_params=client_blocks,
            client_adapters=client_adapters,
            client_opt=client_opt,
            calib=calib,
        )
    client_opt = init_opt_state(client_blocks, opt_cfg)
    client_opt["step"] = jnp.zeros((n,), jnp.int32)
    return dict(
        server=TrainState(params=server_params,
                          opt=init_opt_state(server_params, opt_cfg),
                          step=jnp.zeros((), jnp.int32)),
        client_params=client_blocks,
        client_opt=client_opt,
        calib=calib,
    )


def build_async_update(cfg: ArchConfig, hub: HubConfig,
                       opt_cfg: AdamWConfig, micro_batch: int, seq: int,
                       calib_decay: float = 0.9, lora_rank: int = 0):
    """One global tick of the async hub, mask-gated per arrival.

    Returns fn(state, tokens, labels, mask) -> (state, metrics) with
    ``tokens``/``labels`` (N, B, S) int32 and ``mask`` (N,) float32 — 1
    for clients whose microbatch arrives this tick.  The mask is a traced
    operand, so ONE compiled update serves every arrival pattern (no
    recompile as tick rates interleave).

    Per tick: every client's bottom half runs on its (possibly stale)
    parameters against the CURRENT server; arrivals cross the in-graph
    wire (STE roundtrip forward, ``quantize_cotangent`` backward when
    ``hub.bwd_quant`` is set); the server executes ONCE batched over all
    N slots and applies the mask-aggregated gradient immediately
    (per-arrival update); each arriving client then applies its returned
    gradient and advances its calibration EMA.  Non-arriving clients are
    fully gated: zero loss weight, no parameter/moment/step/calib change.
    """
    from repro.train.loop import TrainState, apply_gradients

    n = hub.n_clients
    links = hub.links()
    positions = jnp.arange(seq, dtype=jnp.int32)
    dtype = tf.cdtype(cfg)

    if lora_rank > 0:
        return _build_async_lora_update(cfg, hub, opt_cfg, micro_batch,
                                        seq, calib_decay)

    def update(state, tokens, labels, mask):
        def loss_fn(server_params, client_blocks):
            x = embed_tokens(cfg, server_params, tokens, dtype)  # (N,B,S,D)
            h_pre, h_q = [], []
            for c, link in enumerate(links):
                blocks_c = jax.tree_util.tree_map(lambda a: a[c],
                                                  client_blocks)
                hc = run_blocks(cfg, blocks_c, x[c], positions)
                h_hat, _ = quantizers.roundtrip(link.quant, hc)
                if link.bwd_quant is not None:
                    h_hat = quantize_cotangent(link.bwd_quant, h_hat)
                h_pre.append(hc)
                h_q.append(h_hat)
            h_pre = jnp.stack(h_pre)
            h_q = jnp.stack(h_q)
            # batched shared-server stage execution over all N slots
            hs = h_q.reshape((n * micro_batch, seq, cfg.d_model))
            hs = run_blocks(cfg, server_params["blocks"], hs, positions)
            h_out = hs.reshape((n, micro_batch, seq, cfg.d_model))
            ces = jnp.stack([head_ce(cfg, server_params, h_out[c],
                                     labels[c]) for c in range(n)])
            loss = jnp.sum(ces * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return loss, (ces, h_pre, h_q)

        (loss, (ces, h_pre, h_q)), grads = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(
                state["server"].params, state["client_params"])
        g_server, g_client = grads

        # per-arrival server update: the shared stack aggregates exactly
        # this tick's arrivals (the mask already zeroed everyone else);
        # with no arrivals at all, the server holds still
        server_new, opt_metrics = apply_gradients(state["server"],
                                                  g_server, opt_cfg)
        any_arrival = jnp.sum(mask) > 0.0
        server = jax.tree_util.tree_map(
            lambda a, b: jnp.where(any_arrival, a, b),
            server_new, state["server"])

        # per-client updates, gated: a non-arriving client's params,
        # moments, step count and calibration are bit-identical before
        # and after (AdamW with a zero grad would still decay weights
        # and moments — that would leak training into idle clients)
        def one_client(p, g, m, v, s):
            newp, news, _ = adamw_update(p, g, dict(m=m, v=v, step=s),
                                         opt_cfg, 1.0)
            return newp, news["m"], news["v"], news["step"]

        newp, newm, newv, news = jax.vmap(one_client)(
            state["client_params"], g_client, state["client_opt"]["m"],
            state["client_opt"]["v"], state["client_opt"]["step"])

        def gate(new, old):
            m = mask.reshape((n,) + (1,) * (new.ndim - 1))
            return jnp.where(m > 0.0, new, old)

        client_params = jax.tree_util.tree_map(gate, newp,
                                               state["client_params"])
        client_opt = dict(
            m=jax.tree_util.tree_map(gate, newm, state["client_opt"]["m"]),
            v=jax.tree_util.tree_map(gate, newv, state["client_opt"]["v"]),
            step=gate(news, state["client_opt"]["step"]),
        )

        calib_new = jax.vmap(partial(update_wire_calib,
                                     decay=calib_decay))(state["calib"],
                                                         h_pre)
        calib = jax.tree_util.tree_map(gate, calib_new, state["calib"])

        # per-client relative reconstruction error of the forward wire —
        # the calibration-isolation tests compare this against solo runs
        num = jnp.mean(jnp.square(h_pre - h_q), axis=(1, 2, 3))
        den = jnp.mean(jnp.square(h_pre), axis=(1, 2, 3)) + 1e-12
        metrics = dict(loss=loss, ces=ces, quant_rel_err=num / den,
                       mask=mask, grad_norm=opt_metrics["grad_norm"])
        return (dict(server=server, client_params=client_params,
                     client_opt=client_opt, calib=calib), metrics)

    return jax.jit(update)


def _build_async_lora_update(cfg: ArchConfig, hub: HubConfig,
                             opt_cfg: AdamWConfig, micro_batch: int,
                             seq: int, calib_decay: float = 0.9):
    """SplitLoRA async tick: the adapter-only twin of
    :func:`build_async_update`.

    Base block stacks (client AND server) plus embed/head/norm are
    frozen by construction — autodiff runs wrt the adapter trees only,
    so the state's optimizers are sized by adapter params.  When
    ``hub.grad_quant`` is set, every client's adapter gradient crosses
    the codec (encode -> decode, the in-graph twin of the lockstep
    schedulers' collective grad-return wire) before it is applied.
    """
    from repro.train.loop import apply_adapter_gradients

    n = hub.n_clients
    links = hub.links()
    positions = jnp.arange(seq, dtype=jnp.int32)
    dtype = tf.cdtype(cfg)

    def _grad_roundtrip(g_client):
        if hub.grad_quant is None:
            return g_client
        q = hub.grad_quant

        def one(leaf):  # leading axis = client
            return jax.vmap(lambda v: quantizers.decode(
                q, quantizers.encode(q, v)).astype(v.dtype))(leaf)

        return jax.tree_util.tree_map(one, g_client)

    def update(state, tokens, labels, mask):
        client_blocks = state["client_params"]  # frozen base halves
        server_base = state["server"].params    # frozen base + adapters

        def loss_fn(server_adapters, client_adapters):
            x = embed_tokens(cfg, server_base, tokens, dtype)  # (N,B,S,D)
            h_pre, h_q = [], []
            for c, link in enumerate(links):
                blocks_c = jax.tree_util.tree_map(lambda a: a[c],
                                                  client_blocks)
                ad_c = jax.tree_util.tree_map(lambda a: a[c],
                                              client_adapters)
                hc = run_blocks(cfg, blocks_c, x[c], positions,
                                adapters=ad_c)
                h_hat, _ = quantizers.roundtrip(link.quant, hc)
                if link.bwd_quant is not None:
                    h_hat = quantize_cotangent(link.bwd_quant, h_hat)
                h_pre.append(hc)
                h_q.append(h_hat)
            h_pre = jnp.stack(h_pre)
            h_q = jnp.stack(h_q)
            hs = h_q.reshape((n * micro_batch, seq, cfg.d_model))
            hs = run_blocks(cfg, server_base["blocks"], hs, positions,
                            adapters=server_adapters)
            h_out = hs.reshape((n, micro_batch, seq, cfg.d_model))
            ces = jnp.stack([head_ce(cfg, server_base, h_out[c],
                                     labels[c]) for c in range(n)])
            loss = jnp.sum(ces * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return loss, (ces, h_pre, h_q)

        (loss, (ces, h_pre, h_q)), grads = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(
                server_base["adapters"], state["client_adapters"])
        g_server_ad, g_client_ad = grads
        # the quantized gradient return: adapter grads only
        g_client_ad = _grad_roundtrip(g_client_ad)

        server_new, opt_metrics = apply_adapter_gradients(
            state["server"], g_server_ad, opt_cfg)
        any_arrival = jnp.sum(mask) > 0.0
        server = jax.tree_util.tree_map(
            lambda a, b: jnp.where(any_arrival, a, b),
            server_new, state["server"])

        def one_client(p, g, m, v, s):
            newp, news, _ = adamw_update(p, g, dict(m=m, v=v, step=s),
                                         opt_cfg, 1.0)
            return newp, news["m"], news["v"], news["step"]

        newp, newm, newv, news = jax.vmap(one_client)(
            state["client_adapters"], g_client_ad,
            state["client_opt"]["m"], state["client_opt"]["v"],
            state["client_opt"]["step"])

        def gate(new, old):
            m = mask.reshape((n,) + (1,) * (new.ndim - 1))
            return jnp.where(m > 0.0, new, old)

        client_adapters = jax.tree_util.tree_map(
            gate, newp, state["client_adapters"])
        client_opt = dict(
            m=jax.tree_util.tree_map(gate, newm, state["client_opt"]["m"]),
            v=jax.tree_util.tree_map(gate, newv, state["client_opt"]["v"]),
            step=gate(news, state["client_opt"]["step"]),
        )

        calib_new = jax.vmap(partial(update_wire_calib,
                                     decay=calib_decay))(state["calib"],
                                                         h_pre)
        calib = jax.tree_util.tree_map(gate, calib_new, state["calib"])

        num = jnp.mean(jnp.square(h_pre - h_q), axis=(1, 2, 3))
        den = jnp.mean(jnp.square(h_pre), axis=(1, 2, 3)) + 1e-12
        metrics = dict(loss=loss, ces=ces, quant_rel_err=num / den,
                       mask=mask, grad_norm=opt_metrics["grad_norm"])
        return (dict(server=server, client_params=client_blocks,
                     client_adapters=client_adapters,
                     client_opt=client_opt, calib=calib), metrics)

    return jax.jit(update)


def async_tick_stream(batches: Iterable, tick_rates: Tuple[int, ...],
                      n_ticks: int):
    """Host-side arrival schedule: yields (tick, mask, (tokens, labels)).

    ``batches`` yields (tokens, labels) of shape (N, B, S) — one
    candidate microbatch per client per global tick; the mask says whose
    actually arrives (non-arriving clients' slots are computed but fully
    gated in :func:`build_async_update`).
    """
    pattern = arrival_mask(tick_rates, n_ticks)
    it = iter(batches)
    for t in range(n_ticks):
        yield t, pattern[t].astype(np.float32), next(it)
