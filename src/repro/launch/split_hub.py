"""Many-client split-learning hub: N clients sharing one server stack.

BEYOND-PAPER (ROADMAP item 2): the paper deploys exactly one client and
one server; the SL-for-LLM survey and VFLAIR-LLM (PAPERS.md) frame the
real setting as N clients — each with its own data distribution,
quantizer calibration and tick rate — sharing one server.  Topology:

  pod 0 (client 0): embed + layers[:L/2] -> quantize -> ship  \\
  pod 1 (client 1): embed + layers[:L/2] -> quantize -> ship   > star
  ...                                                         /
  pod N (server): dequantize x N -> layers[L/2:] -> head -> CE/client

Each client->server edge is its own ``core.split.WireLink`` with its own
``QuantConfig`` (heterogeneous clients exercise the per-link byte
accounting) — and its own collective: ppermute forbids one destination
receiving from two sources, so hub ships are per-link by construction.
The server runs its half ONCE per tick, batched over the N arrivals.

Two schedules (``repro.launch.schedules``):

* **lockstep** — every client ships every tick; GPipe-style 1-tick
  fill/drain.  With ``n_clients == 1`` this is exactly the paper's
  2-partition pipeline (``launch/split_pipeline``) and reproduces its
  loss to 3e-6 (asserted by the parity dry-run below).
* **async** — clients tick at different rates (``HubConfig.tick_rates``);
  the server applies the aggregated gradient per arrival while each
  client updates only when its own gradient returns, tolerating the
  staleness.  Per-client NF/RD-FSQ calibration EMAs stay isolated.

The __main__ dry-run lowers the lockstep hub on a fake-device mesh and
asserts every link's static CommPayload bytes against the lowered HLO's
collective-permute traffic for that link's device pairs, runs the N=1
parity check, and trains the async hub for a few ticks:

    PYTHONPATH=src python -m repro.launch.split_hub --smoke
      (3 clients + 1 server on 8 fake devices, heterogeneous 2/4-bit)
"""
import os
import sys

if __name__ == "__main__":  # must run before any jax import
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

# ruff: noqa: E402
import functools
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.quantizers import QuantConfig
from repro.core.split import HubConfig
from repro.core.split_stage import init_stage_params
from repro.launch import schedules
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig, init_opt_state


def hub_mesh(n_clients: int, data_shards: int = 2):
    """(pod, data) mesh with one pod per client plus one for the server."""
    return make_mesh((n_clients + 1, data_shards), ("pod", "data"))


def init_hub_params(key, cfg: ArchConfig, hub: HubConfig,
                    lora_rank: int = 0) -> Dict:
    """Stage-stacked hub parameters: blocks (N+1, L/2, ...) — N client
    bottom halves + 1 server top half; embed/head/final norm shared.
    ``lora_rank > 0`` adds the stage-stacked ``"adapters"`` LoRA tree."""
    assert cfg.n_layers % 2 == 0, cfg.n_layers
    return init_stage_params(key, cfg, hub.n_clients + 1, cfg.n_layers // 2,
                             lora_rank=lora_rank)


def hub_wire_bytes(cfg: ArchConfig, hub: HubConfig, micro_batch: int,
                   seq: int, data_shards: int = 1,
                   lora_rank: int = 0) -> Dict:
    """Per-link static wire bytes of the hub (see schedules.hub_wire_bytes)."""
    return schedules.hub_wire_bytes(cfg, hub, micro_batch, seq,
                                    data_shards=data_shards,
                                    lora_rank=lora_rank)


def hlo_link_bytes(hlo_text: str, mesh, axis: str = "pod"
                   ) -> Dict[Tuple[int, int], int]:
    """Measured per-link collective-permute bytes of a lowered program:
    device-pair traffic (``hlo_analysis.collective_permute_pairs``)
    aggregated to stage links through the mesh's ``axis`` coordinates."""
    from repro.launch.hlo_analysis import collective_permute_pairs

    return schedules.pod_link_bytes(collective_permute_pairs(hlo_text),
                                    mesh, axis)


build_hub_step = schedules.build_hub_step
build_hub_grad_step = schedules.build_hub_grad_step


@functools.lru_cache(maxsize=16)
def _cached_hub_update(cfg: ArchConfig, mesh, hub: HubConfig,
                       opt_cfg: AdamWConfig, n_micro: int,
                       micro_batch: int, seq: int, warmup_steps: int,
                       total_steps: int, lora_rank: int = 0):
    """One jitted lockstep (hub grad step + AdamW apply) per configuration
    — the same recompile-avoidance cache as
    ``split_pipeline._cached_pipeline_update``.  ``lora_rank`` joins the
    cache key: the SplitLoRA update differentiates and steps the adapter
    tree only (the grads crossing the wire are the quantized adapter-grad
    return payloads of ``build_hub_grad_step``)."""
    from repro.train.loop import apply_adapter_gradients, apply_gradients

    grad_step = build_hub_grad_step(cfg, mesh, hub, n_micro, micro_batch,
                                    seq, lora_rank=lora_rank)

    @jax.jit
    def update(state, tokens, labels):
        loss, per_client, grads, wire_b = grad_step(state.params, tokens,
                                                    labels)
        if lora_rank > 0:
            state, _ = apply_adapter_gradients(state, grads, opt_cfg,
                                               warmup_steps=warmup_steps,
                                               total_steps=total_steps)
        else:
            state, _ = apply_gradients(state, grads, opt_cfg,
                                       warmup_steps=warmup_steps,
                                       total_steps=total_steps)
        return state, loss, per_client, wire_b

    return update


def train_hub(cfg: ArchConfig, hub: HubConfig, opt_cfg: AdamWConfig,
              batches: Iterable[Tuple[jnp.ndarray, jnp.ndarray]], *,
              micro_batch: int, seq: int, mode: str = "lockstep",
              mesh=None, n_micro: int = 1, n_ticks: Optional[int] = None,
              params: Optional[Dict] = None, warmup_steps: int = 0,
              total_steps: int = 0, seed: int = 0,
              wire_budget_bytes: Optional[float] = None,
              plan_groups: int = 8, replan_every: int = 1,
              plan_log: Optional[List] = None,
              lora_rank: int = 0) -> Dict:
    """Train the N-client hub.

    ``mode="lockstep"``: every client ships every tick on the SPMD mesh
    (``mesh`` required, pod axis of n_clients + 1); each element of
    ``batches`` is (tokens, labels) of shape (n_micro, N, B, S) and one
    optimizer step consumes one element.  Returns dict(params, opt,
    history, per_client, wire_bytes_per_tick).

    ``mode="async"``: the staleness-tolerant host loop — clients arrive
    per ``hub.tick_rates``, the server applies gradients per arrival,
    per-client calibration EMAs advance only for arrivals.  ``batches``
    yields (N, B, S) candidate microbatches, one per global tick
    (``n_ticks`` of them).  Mesh-free (in-graph wire form).  Returns
    dict(state, history, masks, quant_rel_err).

    Entropy-adaptive wire (lockstep only): ``wire_budget_bytes`` turns
    on per-client re-planning between compiled steps — each client's
    boundary activation feeds its OWN per-channel entropy EMA (clients
    have different data distributions; their plans must stay isolated,
    like their codec calibration), and each link gets its own
    ``plan_groups``-group width plan under the shared per-link budget.
    Plans live on the clients' ``QuantConfig.group_widths``, so the
    update cache compiles once per distinct plan vector.  ``plan_log``
    receives (step, plans) tuples on change.

    SplitLoRA (ROADMAP item 4): ``lora_rank > 0`` freezes the base
    weights and trains only the LoRA adapter tree in BOTH modes.  In
    lockstep the server's quantized gradient return shrinks to the
    adapter-grad payload (``hub.grad_quant`` codec); async runs the
    in-graph twin.  Optimizer moments are sized by adapter params only.
    """
    if mode == "lockstep":
        from repro.core import entropy as entropy_mod
        from repro.train.loop import TrainState, init_adapter_state

        assert mesh is not None, "lockstep mode needs the hub mesh"
        adaptive = wire_budget_bytes is not None
        update = _cached_hub_update(cfg, mesh, hub, opt_cfg, n_micro,
                                    micro_batch, seq, warmup_steps,
                                    total_steps, lora_rank)
        if params is None:
            params = init_hub_params(jax.random.PRNGKey(seed), cfg, hub,
                                     lora_rank=lora_rank)
        if lora_rank > 0:
            state = init_adapter_state(params, opt_cfg)
        else:
            state = TrainState(params=params,
                               opt=init_opt_state(params, opt_cfg),
                               step=jnp.zeros((), jnp.int32))
        n = hub.n_clients
        emas = ([entropy_mod.init_entropy_ema(cfg.d_model)
                 for _ in range(n)] if adaptive else None)
        scalars_per_ch = (micro_batch // mesh.shape["data"]) * seq
        plans: Tuple[Tuple[int, ...], ...] = ((),) * n
        history: List[float] = []
        per_client = None
        wire_b = 0.0
        with mesh:
            for step_i, (tokens, labels) in enumerate(batches):
                if adaptive and step_i % max(replan_every, 1) == 0:
                    new_plans = []
                    for c in range(n):
                        h = schedules.boundary_probe(cfg, state.params,
                                                     tokens[0, c], c)
                        emas[c] = entropy_mod.update_entropy_ema(emas[c], h)
                        new_plans.append(schedules.replan_widths(
                            emas[c], wire_budget_bytes,
                            n_groups=plan_groups,
                            scalars_per_channel=scalars_per_ch))
                    if tuple(new_plans) != plans:
                        plans = tuple(new_plans)
                        if plan_log is not None:
                            plan_log.append((step_i, plans))
                        hub = hub.with_plans(plans)
                        update = _cached_hub_update(
                            cfg, mesh, hub, opt_cfg, n_micro, micro_batch,
                            seq, warmup_steps, total_steps, lora_rank)
                state, loss, pc, wb = update(state, tokens, labels)
                history.append(float(loss))
                per_client = np.asarray(pc)
                wire_b = float(wb)
        return dict(params=state.params, opt=state.opt, history=history,
                    per_client=per_client, wire_bytes_per_tick=wire_b)

    if mode != "async":
        raise ValueError(f"unknown hub mode {mode!r}")

    rates = hub.resolve_tick_rates()
    assert n_ticks is not None, "async mode needs n_ticks"
    state = schedules.init_hub_state(jax.random.PRNGKey(seed), cfg, hub,
                                     opt_cfg, lora_rank=lora_rank)
    update = schedules.build_async_update(cfg, hub, opt_cfg, micro_batch,
                                          seq, lora_rank=lora_rank)
    history: List[float] = []
    masks: List[np.ndarray] = []
    rel_err = None
    for _t, mask, (tokens, labels) in schedules.async_tick_stream(
            batches, rates, n_ticks):
        state, metrics = update(state, jnp.asarray(tokens),
                                jnp.asarray(labels), jnp.asarray(mask))
        history.append(float(metrics["loss"]))
        masks.append(mask)
        rel_err = np.asarray(metrics["quant_rel_err"])
    return dict(state=state, history=history, masks=masks,
                quant_rel_err=rel_err)


# ---------------------------------------------------------------------------
# dry-runs
# ---------------------------------------------------------------------------

def _hub_quants(n_clients: int) -> Tuple[QuantConfig, ...]:
    """Heterogeneous per-client compressors: alternate 2-bit RD-FSQ and
    4-bit NF so neighbouring links carry different payloads."""
    return tuple(QuantConfig(method="rdfsq", bits=2) if c % 2 == 0
                 else QuantConfig(method="nf", bits=4)
                 for c in range(n_clients))


def dryrun_hub(arch: str = "llama3_2_3b", n_clients: int = 3,
               n_micro: int = 3, micro_batch: int = 4, seq: int = 16,
               reduced: bool = True) -> Dict:
    """Lower + compile the lockstep hub (N clients + 1 server) and assert
    every client->server link's static CommPayload bytes against the HLO
    collective-permute traffic of that link's device pairs, within 1%."""
    from repro.configs import get_config
    from repro.launch.split_pipeline import assert_links_match_hlo

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    assert cfg.n_layers % 2 == 0, cfg.n_layers
    hub = HubConfig(n_clients=n_clients,
                    client_quants=_hub_quants(n_clients))
    mesh = hub_mesh(n_clients)
    params_sds = jax.eval_shape(
        lambda: init_hub_params(jax.random.PRNGKey(0), cfg, hub))
    tok_sds = jax.ShapeDtypeStruct(
        (n_micro, n_clients, micro_batch, seq), jnp.int32)
    n_ticks = n_micro + 1  # 1-tick fill/drain: served one tick after ship

    step = build_hub_step(cfg, mesh, hub, n_micro, micro_batch, seq)
    with mesh:
        compiled = jax.jit(step).lower(params_sds, tok_sds,
                                       tok_sds).compile()
    hlo = compiled.as_text()
    wire = hub_wire_bytes(cfg, hub, micro_batch, seq,
                          data_shards=mesh.shape["data"])
    assert_links_match_hlo(f"hub {arch} N={n_clients}", hlo, mesh, wire,
                           n_ticks)
    measured = hlo_link_bytes(hlo, mesh)
    print(f"[split-hub {arch} N={n_clients}] per-link HLO bytes: "
          + ", ".join(f"{s}->{d}: {v / 1024:.1f} KiB"
                      for (s, d), v in sorted(measured.items())))
    return dict(
        wire_links={f"{s}->{d}": v["fwd"]
                    for (s, d), v in wire["links"].items()},
        hlo_links={f"{s}->{d}": v for (s, d), v in measured.items()},
        wire_bytes_per_tick=wire["fwd_tick"],
    )


def dryrun_parity(arch: str = "llama3_2_3b", n_micro: int = 3,
                  micro_batch: int = 4, seq: int = 16,
                  tol: float = 3e-6) -> Dict:
    """The hub with ONE client is the paper's 2-partition pipeline: same
    parameters, same quantized wire, same loss — to ``tol``."""
    from repro.launch import split_pipeline as sp
    from repro.train.losses import IGNORE

    cfg = sp._homogeneous_cfg(arch, reduced=True, n_stages=2)
    q = QuantConfig(method="rdfsq", bits=2)
    key = jax.random.PRNGKey(0)
    params = sp.init_pipeline_params(key, cfg)  # == init_hub_params(N=1)
    tokens = jax.random.randint(key, (n_micro, micro_batch, seq), 0,
                                cfg.vocab_size)
    labels = jnp.concatenate(
        [tokens[:, :, 1:],
         jnp.full((n_micro, micro_batch, 1), IGNORE, tokens.dtype)],
        axis=-1)
    mesh = hub_mesh(1)

    pipe_step = sp.build_pipeline_step(cfg, mesh, q, n_micro, micro_batch,
                                       seq)
    hub = HubConfig(n_clients=1, quant=q)
    hub_step = build_hub_step(cfg, mesh, hub, n_micro, micro_batch, seq)
    with mesh:
        loss_pipe, _ = jax.jit(pipe_step)(params, tokens, labels)
        loss_hub, per_client, _ = jax.jit(hub_step)(
            params, tokens[:, None], labels[:, None])
    diff = abs(float(loss_pipe) - float(loss_hub))
    print(f"[split-hub parity] pipeline {float(loss_pipe):.6f} vs "
          f"hub(N=1) {float(loss_hub):.6f} (|diff| {diff:.2e})")
    assert diff < tol, (float(loss_pipe), float(loss_hub), diff)
    return dict(loss_pipeline=float(loss_pipe), loss_hub=float(loss_hub),
                diff=diff)


def dryrun_hub_grouped(arch: str = "llama3_2_3b", n_clients: int = 3,
                       n_micro: int = 3, micro_batch: int = 4,
                       seq: int = 16) -> Dict:
    """Grouped mixed-precision hub links, HLO-asserted per client.

    Client 0 ships a uniform 3-bit grouped FSQ plan (pure code bytes —
    must cost exactly 3/16 of the identity bf16 wire), client 1 the
    identity wire (the 16-bit reference on the same topology), and the
    remaining clients adaptive-shaped mixed-width RD-FSQ plans.  Every
    link's static ``GroupedPayload`` bytes are asserted against the HLO
    collective-permute traffic of that link's device pairs, within 1%.
    """
    from repro.configs import get_config
    from repro.launch.split_pipeline import assert_links_match_hlo

    cfg = get_config(arch).reduced()
    assert cfg.d_model % 8 == 0, cfg.d_model
    quants = [QuantConfig(method="fsq", group_widths=(3,) * 8),
              QuantConfig(method="identity")]
    quants += [QuantConfig(method="rdfsq", group_widths=(1, 2, 3, 8))
               for _ in range(n_clients - 2)]
    hub = HubConfig(n_clients=n_clients, client_quants=tuple(quants))
    mesh = hub_mesh(n_clients)
    params_sds = jax.eval_shape(
        lambda: init_hub_params(jax.random.PRNGKey(0), cfg, hub))
    tok_sds = jax.ShapeDtypeStruct(
        (n_micro, n_clients, micro_batch, seq), jnp.int32)
    n_ticks = n_micro + 1

    step = build_hub_step(cfg, mesh, hub, n_micro, micro_batch, seq)
    with mesh:
        compiled = jax.jit(step).lower(params_sds, tok_sds,
                                       tok_sds).compile()
    hlo = compiled.as_text()
    wire = hub_wire_bytes(cfg, hub, micro_batch, seq,
                          data_shards=mesh.shape["data"])
    assert_links_match_hlo(f"hub grouped {arch} N={n_clients}", hlo, mesh,
                           wire, n_ticks)
    links = wire["links"]
    ratio = (links[(0, hub.server_stage)]["fwd"]
             / links[(1, hub.server_stage)]["fwd"])
    print(f"[split-hub grouped] 3-bit/bf16 link ratio {ratio:.6f} "
          f"(exact 3/16 = {3 / 16:.6f})")
    assert abs(ratio - 3.0 / 16.0) < 0.01 * (3.0 / 16.0), ratio
    return dict(
        wire_links={f"{s}->{d}": v["fwd"] for (s, d), v in links.items()},
        ratio_3bit=ratio,
    )


def dryrun_parity_grouped(arch: str = "llama3_2_3b", n_micro: int = 3,
                          micro_batch: int = 4, seq: int = 16,
                          tol: float = 3e-6) -> Dict:
    """The identity plan: a single-group grouped wire IS the static wire.

    ``group_widths=(2,)`` slices the channel axis into one group whose
    scale statistics cover the whole tensor — numerically the static
    2-bit codec, shipped as a 1-group ``GroupedPayload``.  The hub(N=1)
    loss under that plan must match the monolithic static-2-bit pipeline
    loss to ``tol`` — the refactor's no-behavior-change anchor.
    """
    from repro.launch import split_pipeline as sp
    from repro.train.losses import IGNORE

    cfg = sp._homogeneous_cfg(arch, reduced=True, n_stages=2)
    q_static = QuantConfig(method="rdfsq", bits=2)
    q_plan = QuantConfig(method="rdfsq", bits=2, group_widths=(2,))
    key = jax.random.PRNGKey(0)
    params = sp.init_pipeline_params(key, cfg)
    tokens = jax.random.randint(key, (n_micro, micro_batch, seq), 0,
                                cfg.vocab_size)
    labels = jnp.concatenate(
        [tokens[:, :, 1:],
         jnp.full((n_micro, micro_batch, 1), IGNORE, tokens.dtype)],
        axis=-1)
    mesh = hub_mesh(1)

    pipe_step = sp.build_pipeline_step(cfg, mesh, q_static, n_micro,
                                       micro_batch, seq)
    hub = HubConfig(n_clients=1, quant=q_plan)
    hub_step = build_hub_step(cfg, mesh, hub, n_micro, micro_batch, seq)
    with mesh:
        loss_pipe, _ = jax.jit(pipe_step)(params, tokens, labels)
        loss_hub, _, _ = jax.jit(hub_step)(
            params, tokens[:, None], labels[:, None])
    diff = abs(float(loss_pipe) - float(loss_hub))
    print(f"[split-hub parity grouped] static-2bit pipeline "
          f"{float(loss_pipe):.6f} vs hub(N=1) identity-plan "
          f"{float(loss_hub):.6f} (|diff| {diff:.2e})")
    assert diff < tol, (float(loss_pipe), float(loss_hub), diff)
    return dict(loss_pipeline=float(loss_pipe), loss_hub=float(loss_hub),
                diff=diff)


def dryrun_train_adaptive(arch: str = "llama3_2_3b", n_clients: int = 3,
                          n_steps: int = 4, n_micro: int = 2,
                          micro_batch: int = 4, seq: int = 32,
                          lr: float = 5e-3) -> Dict:
    """Execute the per-client re-planning lockstep hub end to end: every
    client's entropy EMA drives its own plan under a shared ~2-bit code
    budget; asserts the loss decreases and the adopted plans respect the
    budget."""
    from repro.configs import get_config
    from repro.data.pipeline import make_pipeline

    cfg = get_config(arch).reduced()
    hub = HubConfig(n_clients=n_clients,
                    quant=QuantConfig(method="rdfsq", bits=2))
    mesh = hub_mesh(n_clients)
    pipe = make_pipeline(cfg, n_micro * n_clients * micro_batch, seq,
                         seed=0)

    def batches():
        for _ in range(n_steps):
            b = next(pipe)
            yield (b["tokens"].reshape(n_micro, n_clients, micro_batch,
                                       seq),
                   b["labels"].reshape(n_micro, n_clients, micro_batch,
                                       seq))

    budget = (micro_batch // 2) * seq * cfg.d_model * 2 / 8
    plan_log: List = []
    opt = AdamWConfig(lr=lr, weight_decay=0.0)
    out = train_hub(cfg, hub, opt, batches(), micro_batch=micro_batch,
                    seq=seq, mode="lockstep", mesh=mesh, n_micro=n_micro,
                    wire_budget_bytes=budget, plan_groups=8,
                    plan_log=plan_log)
    hist = out["history"]
    plans = plan_log[-1][1] if plan_log else ()
    print(f"[split-hub adaptive N={n_clients}] loss "
          + " -> ".join(f"{v:.4f}" for v in hist)
          + f" (plans {plans})")
    assert hist[-1] < hist[0], f"adaptive hub loss did not decrease: {hist}"
    assert plan_log, "adaptive hub never adopted a plan"
    for per_client_plans in (p for _, p in plan_log):
        for p in per_client_plans:
            assert len(p) == 8 and all(1 <= w <= 8 for w in p), p
            assert sum(p) / len(p) <= 2.0 + 1e-9, p
    return dict(loss_history=hist,
                plans=[list(p) for p in plans],
                wire_bytes_per_tick=out["wire_bytes_per_tick"])


def dryrun_train_async(arch: str = "llama3_2_3b", n_clients: int = 3,
                       n_ticks: int = 24, micro_batch: int = 4,
                       seq: int = 32, lr: float = 5e-3) -> Dict:
    """Execute the staleness-tolerant async hub for a few dozen global
    ticks — heterogeneous quants AND tick rates — and check the arrival
    loss decreases (monotone-ish: windowed means, not per-tick)."""
    from repro.configs import get_config
    from repro.data.pipeline import make_pipeline

    cfg = get_config(arch).reduced()
    hub = HubConfig(n_clients=n_clients,
                    client_quants=_hub_quants(n_clients),
                    bwd_quant=QuantConfig(method="rdfsq", bits=2),
                    tick_rates=tuple(1 + c % 3 for c in range(n_clients)))
    pipe = make_pipeline(cfg, n_clients * micro_batch, seq, seed=0)

    def batches():
        while True:
            b = next(pipe)
            yield (b["tokens"].reshape(n_clients, micro_batch, seq),
                   b["labels"].reshape(n_clients, micro_batch, seq))

    opt = AdamWConfig(lr=lr, weight_decay=0.0)
    out = train_hub(cfg, hub, opt, batches(), micro_batch=micro_batch,
                    seq=seq, mode="async", n_ticks=n_ticks)
    hist = out["history"]
    k = max(3, n_ticks // 6)
    head, tail = float(np.mean(hist[:k])), float(np.mean(hist[-k:]))
    n_arrivals = int(sum(m.sum() for m in out["masks"]))
    print(f"[split-hub async N={n_clients}] loss "
          + " -> ".join(f"{v:.4f}" for v in hist[:4])
          + f" ... {hist[-1]:.4f} (first-{k} mean {head:.4f}, last-{k} "
          f"mean {tail:.4f}; {n_arrivals} arrivals/{n_ticks} ticks)")
    assert tail < head, f"async hub loss did not decrease: {hist}"
    calib = out["state"]["calib"]
    assert float(jnp.min(calib["count"])) > 0, \
        "some client's calibration never updated"
    return dict(loss_history=hist, head_mean=head, tail_mean=tail,
                n_arrivals=n_arrivals,
                quant_rel_err=[float(v) for v in out["quant_rel_err"]])


def dryrun_lora(arch: str = "llama3_2_3b", n_clients: int = 3,
                n_steps: int = 4, n_micro: int = 2, micro_batch: int = 4,
                seq: int = 32, lora_rank: int = 4,
                lr: float = 3e-2) -> Dict:
    """SplitLoRA hub acceptance gate (ROADMAP item 4).

    Three checks:

    1. **adapter-grad wire vs HLO** — lower the LoRA lockstep grad step
       (heterogeneous client quants, 8-bit RD-FSQ adapter-grad codec) and
       assert every link's static bytes against the compiled HLO
       collective-permute traffic: forward ships x ticks PLUS the
       adapter-grad round trip once per step, in both directions.
    2. **lockstep trains** — loss decreases with every base weight
       bit-frozen and AdamW moments sized by the adapter params only.
    3. **async trains** — the in-graph twin also learns (windowed means)
       with its per-client adapter state advancing.
    """
    from repro.configs import get_config
    from repro.core.split import tree_payload_bytes
    from repro.data.pipeline import make_pipeline
    from repro.launch.split_pipeline import assert_links_match_hlo
    from repro.optim import param_bytes
    from repro.peft import adapter_bytes

    cfg = get_config(arch).reduced()
    grad_q = QuantConfig(method="rdfsq", bits=8, stats_axis="tensor")
    hub = HubConfig(n_clients=n_clients,
                    client_quants=_hub_quants(n_clients),
                    grad_quant=grad_q)
    mesh = hub_mesh(n_clients)

    # 1. HLO assertion on the adapter-grad return wire
    params_sds = jax.eval_shape(
        lambda: init_hub_params(jax.random.PRNGKey(0), cfg, hub,
                                lora_rank=lora_rank))
    tok_sds = jax.ShapeDtypeStruct(
        (n_micro, n_clients, micro_batch, seq), jnp.int32)
    grad_step = build_hub_grad_step(cfg, mesh, hub, n_micro, micro_batch,
                                    seq, lora_rank=lora_rank)
    with mesh:
        compiled = jax.jit(grad_step).lower(params_sds, tok_sds,
                                            tok_sds).compile()
    wire = hub_wire_bytes(cfg, hub, micro_batch, seq,
                          data_shards=mesh.shape["data"],
                          lora_rank=lora_rank)
    assert_links_match_hlo(f"hub lora r={lora_rank} {arch} N={n_clients}",
                           compiled.as_text(), mesh, wire,
                           n_micro + 1, check_bwd=True, check_grad=True)
    # the reduction claim: the adapter-grad payload vs shipping one
    # stage's FULL param-grads through the same 8-bit codec
    ad_stage = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        params_sds["adapters"])
    full_stage = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        params_sds["blocks"])
    ad_payload = tree_payload_bytes(grad_q, ad_stage)
    full_payload = tree_payload_bytes(grad_q, full_stage)
    print(f"[split-hub lora] adapter-grad payload {ad_payload / 1024:.1f} "
          f"KiB vs full param-grad {full_payload / 1024:.1f} KiB "
          f"({full_payload / max(ad_payload, 1):.1f}x smaller)")
    assert ad_payload < full_payload / 4, (ad_payload, full_payload)

    # 2. lockstep LoRA training: loss down, base frozen, opt adapter-sized
    params0 = init_hub_params(jax.random.PRNGKey(0), cfg, hub,
                              lora_rank=lora_rank)
    base0 = jax.tree_util.tree_map(
        jnp.copy, {k: v for k, v in params0.items() if k != "adapters"})
    pipe = make_pipeline(cfg, n_micro * n_clients * micro_batch, seq,
                         seed=0)

    def batches():
        for _ in range(n_steps):
            b = next(pipe)
            yield (b["tokens"].reshape(n_micro, n_clients, micro_batch,
                                       seq),
                   b["labels"].reshape(n_micro, n_clients, micro_batch,
                                       seq))

    opt_cfg = AdamWConfig(lr=lr, weight_decay=0.0)
    out = train_hub(cfg, hub, opt_cfg, batches(), micro_batch=micro_batch,
                    seq=seq, mode="lockstep", mesh=mesh, n_micro=n_micro,
                    params=params0, lora_rank=lora_rank)
    hist = out["history"]
    print(f"[split-hub lora lockstep N={n_clients} r={lora_rank}] loss "
          + " -> ".join(f"{v:.4f}" for v in hist))
    assert hist[-1] < hist[0], f"LoRA hub loss did not decrease: {hist}"
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(base0),
            jax.tree_util.tree_leaves_with_path(
                {k: v for k, v in out["params"].items()
                 if k != "adapters"})):
        assert bool(jnp.array_equal(a, b)), \
            f"base weight changed during LoRA hub training: {pa}"
    ad_bytes = adapter_bytes(out["params"]["adapters"])
    m_bytes = param_bytes(out["opt"]["m"])
    assert m_bytes == ad_bytes, (m_bytes, ad_bytes)

    # 3. async LoRA: the in-graph twin learns too
    hub_async = HubConfig(n_clients=n_clients,
                          client_quants=_hub_quants(n_clients),
                          grad_quant=grad_q,
                          tick_rates=tuple(1 + c % 2
                                           for c in range(n_clients)))
    pipe2 = make_pipeline(cfg, n_clients * micro_batch, seq, seed=1)

    def async_batches():
        while True:
            b = next(pipe2)
            yield (b["tokens"].reshape(n_clients, micro_batch, seq),
                   b["labels"].reshape(n_clients, micro_batch, seq))

    n_ticks = 18
    out_a = train_hub(cfg, hub_async, opt_cfg, async_batches(),
                      micro_batch=micro_batch, seq=seq, mode="async",
                      n_ticks=n_ticks, lora_rank=lora_rank)
    hist_a = out_a["history"]
    k = max(3, n_ticks // 6)
    head, tail = float(np.mean(hist_a[:k])), float(np.mean(hist_a[-k:]))
    print(f"[split-hub lora async N={n_clients} r={lora_rank}] "
          f"first-{k} mean {head:.4f} -> last-{k} mean {tail:.4f}")
    assert tail < head, f"async LoRA hub loss did not decrease: {hist_a}"
    assert "client_adapters" in out_a["state"], list(out_a["state"])
    return dict(loss_history=hist, async_head=head, async_tail=tail,
                adapter_grad_payload=ad_payload,
                full_grad_payload=full_payload,
                adapter_bytes=ad_bytes, opt_moment_bytes=m_bytes)


def main(smoke: bool = False) -> Dict:
    # the smoke profile IS the dry-run: 3 clients + 1 server on 8 fake
    # devices; the full profile only trains async longer
    out: Dict = {}
    out["hub"] = dryrun_hub()
    out["hub_grouped"] = dryrun_hub_grouped()
    out["parity"] = dryrun_parity()
    out["parity_grouped"] = dryrun_parity_grouped()
    out["adaptive"] = dryrun_train_adaptive()
    out["async"] = dryrun_train_async(n_ticks=18 if smoke else 36)
    out["lora"] = dryrun_lora()
    return out


if __name__ == "__main__":
    import json

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = main(smoke="--smoke" in sys.argv)
    os.makedirs(os.path.join(os.path.dirname(__file__), "..", "..", "..",
                             "results"), exist_ok=True)
    path = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                        "results", "split_hub.json")
    with open(path, "w") as f:
        json.dump({str(k): v for k, v in out.items()}, f, indent=1)
    print("saved", path)
