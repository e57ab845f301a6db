"""Cross-pod split learning: the paper's deployment, TPU-native.

The paper runs the client on one GPU box and the server on another,
shipping pickled activations over TCP.  The TPU-idiomatic equivalent
(DESIGN.md SS3) maps the partitions onto the ``pod`` mesh axis and
streams microbatches GPipe-style.  For the paper's 2-partition case:

  pod 0 (client): embed + layers[:L/2] -> quantize -> pack -> ppermute
  pod 1 (server): dequantize -> layers[L/2:] -> head -> next-token CE

This module is now a thin composition of the three split-stack layers
(the monolith it used to be was refactored apart, ROADMAP item 2):

  * stage programs — ``repro.core.split_stage`` (what a partition runs)
  * wire links     — ``repro.core.split.WireLink`` (how cuts ship)
  * schedulers     — ``repro.launch.schedules`` (who ticks when)

The public API is unchanged: ``build_pipeline_step`` /
``build_pipeline_grad_step`` build the N-stage lockstep GPipe schedule
(``SplitConfig.n_stages`` equal partitions, per-cut ``stage_quants``),
``train_pipeline`` runs AdamW over it, and the __main__ dry-run asserts
the static wire accounting against the lowered HLO.  The paper's
2-partition case is also exactly ``launch/split_hub.py`` with one
client (loss parity is tested to 3e-6).

``pipeline_wire_bytes`` now reports PER-LINK bytes (each link counted
once, on the devices that execute it) instead of summing one payload
per distinct cut config over every device — the SPMD accounting fix
for heterogeneous ``stage_quants``.  Accordingly the dry-run asserts
each link's bytes against the HLO collective-permute traffic of that
link's device pairs (``hlo_analysis.collective_permute_pairs``), which
also lets it cover mixed 2-bit/4-bit topologies.

Run the dry-run (512 fake devices, multi-pod mesh):
    PYTHONPATH=src python -m repro.launch.split_pipeline
Fast CI variant (8 fake devices, reduced config, 4-stage topology):
    PYTHONPATH=src python -m repro.launch.split_pipeline --smoke
"""
import os
import sys

if __name__ == "__main__":  # must run before any jax import
    _n_dev = 8 if "--smoke" in sys.argv else 512
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={_n_dev}"

# ruff: noqa: E402
import dataclasses
import functools
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core.quantizers import QuantConfig
from repro.core.split import SplitConfig
from repro.core.split_stage import init_stage_params, stage_param_specs
from repro.launch import schedules
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig, init_opt_state


def _as_split(q) -> SplitConfig:
    """Accept a bare QuantConfig (the paper's 2-stage case) or a full
    SplitConfig describing an N-stage topology."""
    if isinstance(q, SplitConfig):
        return q
    return SplitConfig(quant=q, learnable_codec=False)


def _homogeneous_cfg(arch: str = "llama3_2_3b", reduced: bool = False,
                     n_stages: int = 2) -> ArchConfig:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
        if cfg.n_layers % n_stages:
            # reduced() pins 2 layers; deeper-than-2-stage smoke
            # topologies need one layer per stage
            cfg = dataclasses.replace(cfg, n_layers=n_stages)
    assert all(t == "dense" for t in cfg.block_pattern()), \
        "pipeline stages must be structurally identical"
    assert cfg.n_layers % n_stages == 0, \
        f"{cfg.n_layers} layers do not divide into {n_stages} stages"
    return cfg


def init_pipeline_params(key, cfg: ArchConfig, n_stages: int = 2,
                         lora_rank: int = 0) -> Dict:
    """Stage-stacked parameters: blocks (N, L/N, ...); embed/head shared.
    ``lora_rank > 0`` adds the stage-stacked ``"adapters"`` LoRA tree."""
    return init_stage_params(key, cfg, n_stages, lora_rank=lora_rank)


def pipeline_specs(cfg: ArchConfig, n_stages: int = 2,
                   lora_rank: int = 0) -> Dict:
    """shard_map in_specs for the parameter tree."""
    return stage_param_specs(cfg, n_stages, lora_rank=lora_rank)


def pipeline_wire_bytes(cfg: ArchConfig, split, micro_batch: int, seq: int,
                        bwd_qcfg: Optional[QuantConfig] = None,
                        data_shards: int = 1) -> Dict:
    """Per-link static wire bytes of the pipeline, from payload shapes.

    ``data_shards`` is the mesh's data-axis size: the microbatch is
    sharded over it, so each device encodes and ships a
    ``micro_batch / data_shards`` slice.  Returns the
    ``schedules.chain_wire_bytes`` table: ``links[(src, dst)]`` is each
    cut's FULL per-tick traffic (slice x data shards — the quantity the
    dry-run asserts against the HLO collective-permute bytes of that
    link's device pairs); ``fwd_tick`` / ``bwd_tick`` are the per-device
    per-tick bytes — the MAX over links of the device's payload slice,
    since a device sources at most one cut per tick.  The old sum over
    distinct cut configs charged every device with every cut's payload,
    overcounting heterogeneous ``stage_quants`` topologies.
    """
    return schedules.chain_wire_bytes(cfg, _as_split(split), micro_batch,
                                      seq, bwd_qcfg,
                                      data_shards=data_shards)


def build_pipeline_step(cfg: ArchConfig, mesh, split, n_micro: int,
                        micro_batch: int, seq: int,
                        bwd_qcfg: Optional[QuantConfig] = None,
                        lora_rank: int = 0):
    """Returns a jit-able fn(params, tokens, labels) -> (loss, wire_bytes).

    ``tokens``/``labels`` are (n_micro, B, S) int32; ``loss`` is the
    next-token cross-entropy computed by the last stage, averaged over
    the ``n_micro`` microbatches; ``wire_bytes`` is the per-device
    per-tick forward wire payload in bytes — a compile-time constant
    derived from the static ``CommPayload`` shapes (NOT a measured
    quantity; the dry-run asserts it against the lowered HLO).
    """
    return schedules.build_gpipe_step(cfg, mesh, _as_split(split), n_micro,
                                      micro_batch, seq, bwd_qcfg=bwd_qcfg,
                                      lora_rank=lora_rank)


def build_pipeline_grad_step(cfg, mesh, split, bwd_qcfg, n_micro,
                             micro_batch, seq, lora_rank: int = 0):
    """Like build_pipeline_step but differentiates the pipeline loss wrt
    the stage parameters, exercising the gradient-return wire.

    Returns fn(params, tokens, labels) -> (loss, grads, wire_bytes) with
    ``wire_bytes`` the per-device per-tick forward + backward payload
    (compile-time constant, same contract as build_pipeline_step).
    ``lora_rank > 0`` differentiates wrt the adapter tree only (``grads``
    mirrors ``params["adapters"]``).
    """
    return schedules.build_gpipe_grad_step(cfg, mesh, _as_split(split),
                                           bwd_qcfg, n_micro, micro_batch,
                                           seq, lora_rank=lora_rank)


@functools.lru_cache(maxsize=16)
def _cached_pipeline_update(cfg: ArchConfig, mesh, split: SplitConfig,
                            bwd_qcfg: Optional[QuantConfig],
                            opt_cfg: AdamWConfig, n_micro: int,
                            micro_batch: int, seq: int, warmup_steps: int,
                            total_steps: int, lora_rank: int = 0):
    """One jitted (grad step + AdamW apply) per pipeline configuration.

    Same pattern as ``serve/decode._compiled_serve_step``: every config
    in the key is a frozen (hashable) dataclass and ``jax.Mesh`` hashes
    by value, so repeated ``train_pipeline`` calls — resumed runs, sweep
    loops — reuse one traced update instead of rebuilding the shard_map
    closure and re-jitting per call (the recompile cost noted in ROADMAP
    item 1).  ``lora_rank`` joins the cache key: the SplitLoRA update
    differentiates and steps the adapter tree only.
    """
    from repro.train.loop import apply_adapter_gradients, apply_gradients

    grad_step = build_pipeline_grad_step(cfg, mesh, split, bwd_qcfg,
                                         n_micro, micro_batch, seq,
                                         lora_rank=lora_rank)

    @jax.jit
    def update(state, tokens, labels):
        loss, grads, wire_b = grad_step(state.params, tokens, labels)
        if lora_rank > 0:
            state, _ = apply_adapter_gradients(state, grads, opt_cfg,
                                               warmup_steps=warmup_steps,
                                               total_steps=total_steps)
        else:
            state, _ = apply_gradients(state, grads, opt_cfg,
                                       warmup_steps=warmup_steps,
                                       total_steps=total_steps)
        return state, loss, wire_b

    return update


def train_pipeline(cfg: ArchConfig, mesh, split, opt_cfg: AdamWConfig,
                   batches: Iterable[Tuple[jnp.ndarray, jnp.ndarray]], *,
                   n_micro: int, micro_batch: int, seq: int,
                   bwd_qcfg: Optional[QuantConfig] = None,
                   params: Optional[Dict] = None,
                   warmup_steps: int = 0, total_steps: int = 0,
                   seed: int = 0,
                   wire_budget_bytes: Optional[float] = None,
                   plan_groups: int = 8, replan_every: int = 1,
                   entropy_decay: float = 0.9,
                   plan_log: Optional[List] = None,
                   lora_rank: int = 0
                   ) -> Tuple[Dict, Dict, List[float], float]:
    """AdamW training loop over the N-stage quantized pipeline.

    Each element of ``batches`` is a (tokens, labels) pair of shape
    (n_micro, B, S); one optimizer step consumes one element, with the
    pipeline scan playing the role of microbatch gradient accumulation
    (the per-tick CE terms sum into one loss before differentiation).
    The update is ``train.loop.apply_gradients`` — the same scheduled
    AdamW the monolithic trainer uses (``total_steps == 0`` = constant
    lr) — compiled once per configuration via the lru cache above.
    Returns (params, opt_state, per-step losses, wire bytes/tick).

    Entropy-adaptive wire (ROADMAP item 3): passing ``wire_budget_bytes``
    turns on re-planning BETWEEN compiled steps.  Every ``replan_every``
    steps the stage-0 boundary activation is probed on the incoming
    microbatch (``schedules.boundary_probe``), a per-channel EMA entropy
    estimate advances, and the greedy allocator turns it into a
    ``plan_groups``-group width plan under the per-device code-byte
    budget.  The plan rides on the cuts' ``QuantConfig.group_widths``
    (hashable), so the lru cache above compiles once per DISTINCT plan
    and re-planning to a previously seen plan is a cache hit, not a
    recompile.  ``plan_log`` (optional list) receives (step, plan)
    tuples whenever the plan changes.

    SplitLoRA (ROADMAP item 4): ``lora_rank > 0`` freezes the base stage
    weights and trains only the LoRA adapter tree — the gradient step
    differentiates wrt ``params["adapters"]`` alone and the optimizer
    moments are sized by the adapter params (``init_adapter_state``).
    """
    from repro.core import entropy as entropy_mod
    from repro.train.loop import TrainState, init_adapter_state

    split = _as_split(split)
    adaptive = wire_budget_bytes is not None
    if adaptive and split.quant.method not in ("fsq", "rdfsq", "nf"):
        raise ValueError(
            f"adaptive wire needs a grouped-capable codec, not "
            f"{split.quant.method!r}")
    update = _cached_pipeline_update(cfg, mesh, split, bwd_qcfg, opt_cfg,
                                     n_micro, micro_batch, seq,
                                     warmup_steps, total_steps, lora_rank)
    if params is None:
        params = init_pipeline_params(jax.random.PRNGKey(seed), cfg,
                                      split.n_stages, lora_rank=lora_rank)
    if lora_rank > 0:
        state = init_adapter_state(params, opt_cfg)
    else:
        state = TrainState(params=params,
                           opt=init_opt_state(params, opt_cfg),
                           step=jnp.zeros((), jnp.int32))

    ema = entropy_mod.init_entropy_ema(cfg.d_model) if adaptive else None
    scalars_per_ch = (micro_batch // mesh.shape["data"]) * seq
    n_cuts = split.n_stages - 1
    plan: Tuple[int, ...] = ()

    history: List[float] = []
    wire_b = 0.0
    with mesh:
        for step_i, (tokens, labels) in enumerate(batches):
            if adaptive and step_i % max(replan_every, 1) == 0:
                h = schedules.boundary_probe(cfg, state.params, tokens[0])
                ema = entropy_mod.update_entropy_ema(ema, h,
                                                     decay=entropy_decay)
                new_plan = schedules.replan_widths(
                    ema, wire_budget_bytes, n_groups=plan_groups,
                    scalars_per_channel=scalars_per_ch)
                if new_plan != plan:
                    plan = new_plan
                    if plan_log is not None:
                        plan_log.append((step_i, plan))
                    split = split.with_plans((plan,) * n_cuts)
                    update = _cached_pipeline_update(
                        cfg, mesh, split, bwd_qcfg, opt_cfg, n_micro,
                        micro_batch, seq, warmup_steps, total_steps,
                        lora_rank)
            state, loss, wb = update(state, tokens, labels)
            history.append(float(loss))
            wire_b = float(wb)
    return state.params, state.opt, history, wire_b


# ---------------------------------------------------------------------------
# dry-runs
# ---------------------------------------------------------------------------

def _pipeline_mesh(n_stages: int, smoke: bool = False):
    """(pod, data[, model]) mesh with a pod axis of n_stages."""
    if smoke:
        return make_mesh((n_stages, 2), ("pod", "data"))
    n_dev = len(jax.devices())
    model = max(1, n_dev // (n_stages * 16))
    return make_mesh((n_stages, 16, model), ("pod", "data", "model"))


def _micro_batch_sds(n_micro, micro_batch, seq):
    tok = jax.ShapeDtypeStruct((n_micro, micro_batch, seq), jnp.int32)
    return tok, tok


def assert_links_match_hlo(name: str, hlo_text: str, mesh, wire: Dict,
                           n_ticks: int, check_bwd: bool = False,
                           check_grad: bool = False) -> None:
    """Per-link wire assertion: for every link the static CommPayload
    bytes (x scan ticks) must match the HLO collective-permute bytes
    attributed to that link's device pairs, within 1%.  ``check_bwd``
    additionally asserts the gradient-return direction (dst -> src).
    ``check_grad`` adds each link's quantized adapter-grad return trip
    (SplitLoRA) — one round trip per STEP, not per tick, so the grad
    payload is added once to each direction's expected total."""
    from repro.launch.hlo_analysis import collective_permute_pairs

    by_link = schedules.pod_link_bytes(
        collective_permute_pairs(hlo_text), mesh)
    for (src, dst), entry in sorted(wire["links"].items()):
        grad_b = entry.get("grad", 0) if check_grad else 0
        checks = [("fwd", (src, dst), entry["fwd"] * n_ticks + grad_b)]
        if check_bwd:
            checks.append(("bwd", (dst, src),
                           entry["bwd"] * n_ticks + grad_b))
        for direction, key, expected in checks:
            got = by_link.get(key, 0)
            rel = abs(got - expected) / max(expected, 1)
            print(f"[split-pipeline {name}] link {key[0]}->{key[1]} "
                  f"({direction}, {entry['quant']}-{entry['bits']}bit): "
                  f"HLO {got / 2 ** 20:.3f} MiB vs static "
                  f"{expected / 2 ** 20:.3f} MiB (rel err {rel:.4f})")
            assert rel < 0.01, (
                f"{name} link {key}: HLO collective-permute bytes {got} "
                f"disagree with static accounting {expected} "
                f"(rel err {rel:.3f})")


def dryrun(arch: str = "llama3_2_3b", n_micro: int = 4,
           micro_batch: int = 32, seq: int = 1024,
           bits_list=(16, 4, 2), n_stages: int = 2,
           reduced: bool = False, smoke: bool = False) -> Dict:
    """Lower + compile the N-stage pipeline on the multi-pod mesh, measure
    the collective-permute bytes per bit-width, and assert every link
    matches the static CommPayload wire accounting."""
    from repro.launch.hlo_analysis import analyze

    mesh = _pipeline_mesh(n_stages, smoke=smoke)
    cfg = _homogeneous_cfg(arch, reduced=reduced, n_stages=n_stages)
    params_sds = jax.eval_shape(
        lambda: init_pipeline_params(jax.random.PRNGKey(0), cfg, n_stages))
    tok_sds, lab_sds = _micro_batch_sds(n_micro, micro_batch, seq)
    n_ticks = n_micro + n_stages - 1

    results = {}
    for bits in bits_list:
        method = "identity" if bits == 16 else "rdfsq"
        split = SplitConfig(quant=QuantConfig(method=method,
                                              bits=min(bits, 8)),
                            learnable_codec=False, n_stages=n_stages)
        step = build_pipeline_step(cfg, mesh, split, n_micro, micro_batch,
                                   seq)
        with mesh:
            compiled = jax.jit(step).lower(params_sds, tok_sds,
                                           lab_sds).compile()
        hlo = compiled.as_text()
        hl = analyze(hlo)
        cp = hl["collective_by_op"].get("collective-permute", 0)
        wire = pipeline_wire_bytes(cfg, split, micro_batch, seq,
                                   data_shards=mesh.shape["data"])
        assert_links_match_hlo(f"{arch} {method}-{bits}bit N={n_stages}",
                               hlo, mesh, wire, n_ticks)
        results[bits] = dict(
            collective_permute_bytes=cp,
            wire_bytes_per_tick=wire["fwd_tick"],
            wire_links={f"{s}->{d}": v["fwd"]
                        for (s, d), v in wire["links"].items()},
            total_collective_bytes=hl["collective_bytes"],
            peak_gib=compiled.memory_analysis().temp_size_in_bytes / 2 ** 30,
        )
        print(f"[split-pipeline {arch} {method}-{bits}bit N={n_stages}] "
              f"collective-permute/dev = {cp / 2 ** 20:.2f} MiB "
              f"(total coll {hl['collective_bytes'] / 2 ** 20:.1f} MiB)")
    if 16 in results and 2 in results:
        r = 1 - results[2]["collective_permute_bytes"] / \
            max(results[16]["collective_permute_bytes"], 1)
        print(f"[split-pipeline] 2-bit wire reduction vs 16-bit: {r:.4f} "
              f"(paper claims 0.875)")
        results["reduction_2bit"] = r
    return results


def dryrun_heterogeneous(arch: str = "llama3_2_3b", n_micro: int = 3,
                         micro_batch: int = 4, seq: int = 16,
                         smoke: bool = True) -> Dict:
    """Mixed 2-bit/4-bit 4-stage topology with per-link HLO assertions.

    The satellite the per-link refactor unlocks: the old per-device sum
    could not be asserted against heterogeneous ``stage_quants`` (every
    device was charged with every cut group's payload), so only
    homogeneous configs were HLO-checked.  Each link now carries its own
    quant config and its own assertion.
    """
    n_stages = 4
    mesh = _pipeline_mesh(n_stages, smoke=smoke)
    cfg = _homogeneous_cfg(arch, reduced=smoke, n_stages=n_stages)
    quants = (QuantConfig(method="rdfsq", bits=2),
              QuantConfig(method="nf", bits=4),
              QuantConfig(method="rdfsq", bits=2))
    split = SplitConfig(quant=quants[0], learnable_codec=False,
                        n_stages=n_stages, stage_quants=quants)
    params_sds = jax.eval_shape(
        lambda: init_pipeline_params(jax.random.PRNGKey(0), cfg, n_stages))
    tok_sds, lab_sds = _micro_batch_sds(n_micro, micro_batch, seq)
    n_ticks = n_micro + n_stages - 1

    step = build_pipeline_step(cfg, mesh, split, n_micro, micro_batch, seq)
    with mesh:
        compiled = jax.jit(step).lower(params_sds, tok_sds,
                                       lab_sds).compile()
    wire = pipeline_wire_bytes(cfg, split, micro_batch, seq,
                               data_shards=mesh.shape["data"])
    assert_links_match_hlo(f"{arch} mixed-2/4bit N={n_stages}",
                           compiled.as_text(), mesh, wire, n_ticks)
    return dict(wire_links={f"{s}->{d}": v["fwd"]
                            for (s, d), v in wire["links"].items()},
                wire_bytes_per_tick=wire["fwd_tick"])


def dryrun_grouped(arch: str = "llama3_2_3b", n_micro: int = 3,
                   micro_batch: int = 4, seq: int = 16,
                   smoke: bool = True) -> Dict:
    """Grouped mixed-precision wire with per-link HLO assertions.

    Two checks the exact bitstream packers unlock:

    1. **3/16 exactness** — a uniform 3-bit grouped FSQ plan (FSQ ships
       no scale side-info, so the payload is pure code bytes) must cost
       exactly 3/16 of the identity bf16 wire.  Under the old
       power-of-two slot packing it cost 4/16; the static accounting AND
       the lowered HLO collective-permute bytes now both sit at 3/16.
    2. **mixed widths** — an adaptive-shaped plan (1/2/3/8 bits across
       channel groups) lowers to a collective whose bytes match the
       static ``GroupedPayload`` accounting per link, within 1%.
    """
    from repro.launch.hlo_analysis import analyze

    n_stages = 2
    mesh = _pipeline_mesh(n_stages, smoke=smoke)
    cfg = _homogeneous_cfg(arch, reduced=smoke, n_stages=n_stages)
    params_sds = jax.eval_shape(
        lambda: init_pipeline_params(jax.random.PRNGKey(0), cfg, n_stages))
    tok_sds, lab_sds = _micro_batch_sds(n_micro, micro_batch, seq)
    n_ticks = n_micro + n_stages - 1
    assert cfg.d_model % 8 == 0, cfg.d_model

    plans = {
        "identity-bf16": QuantConfig(method="identity"),
        "fsq-3bit-grouped": QuantConfig(method="fsq",
                                        group_widths=(3,) * 8),
        "rdfsq-mixed-1238": QuantConfig(
            method="rdfsq", group_widths=(1, 2, 3, 8)),
    }
    results: Dict = {}
    for name, q in plans.items():
        split = SplitConfig(quant=q, learnable_codec=False,
                            n_stages=n_stages)
        step = build_pipeline_step(cfg, mesh, split, n_micro, micro_batch,
                                   seq)
        with mesh:
            compiled = jax.jit(step).lower(params_sds, tok_sds,
                                           lab_sds).compile()
        hlo = compiled.as_text()
        wire = pipeline_wire_bytes(cfg, split, micro_batch, seq,
                                   data_shards=mesh.shape["data"])
        assert_links_match_hlo(f"{arch} grouped {name}", hlo, mesh, wire,
                               n_ticks)
        hl = analyze(hlo)
        results[name] = dict(
            wire_bytes_per_tick=wire["fwd_tick"],
            collective_permute_bytes=hl["collective_by_op"].get(
                "collective-permute", 0),
        )

    # the exactness claim: 3-bit costs 3/16 of bf16, not the 4/16 a
    # power-of-two storage slot would charge — in the static accounting
    # AND in the compiled collective bytes
    for field in ("wire_bytes_per_tick", "collective_permute_bytes"):
        got = results["fsq-3bit-grouped"][field]
        full = results["identity-bf16"][field]
        ratio = got / max(full, 1)
        print(f"[split-pipeline grouped] 3-bit/bf16 {field} ratio "
              f"{ratio:.6f} (exact 3/16 = {3 / 16:.6f})")
        assert abs(ratio - 3.0 / 16.0) < 0.01 * (3.0 / 16.0), (
            f"3-bit grouped wire is not 3/16 of bf16 ({field}): "
            f"{got} / {full} = {ratio:.6f}")
    results["ratio_3bit"] = (results["fsq-3bit-grouped"]
                             ["collective_permute_bytes"]
                             / max(results["identity-bf16"]
                                   ["collective_permute_bytes"], 1))
    return results


def dryrun_train_adaptive(arch: str = "llama3_2_3b", n_steps: int = 6,
                          n_micro: int = 2, micro_batch: int = 4,
                          seq: int = 32, lr: float = 5e-3) -> Dict:
    """Execute the re-planning trainer end to end on the reduced config.

    Budgets the wire at ~2 bits/scalar of code bytes; the allocator
    spends them per channel group by entropy.  Asserts the loss
    decreases, at least one plan was adopted, and the adopted plans
    respect the budget (mean width <= 2 bits over 8 equal groups).
    """
    from repro.data.pipeline import make_pipeline

    n_stages = 2
    cfg = _homogeneous_cfg(arch, reduced=True, n_stages=n_stages)
    mesh = make_mesh((n_stages, 2), ("pod", "data"))
    split = SplitConfig(quant=QuantConfig(method="rdfsq", bits=2),
                        learnable_codec=False, n_stages=n_stages)
    pipe = make_pipeline(cfg, n_micro * micro_batch, seq, seed=0)

    def batches():
        for _ in range(n_steps):
            b = next(pipe)
            yield (b["tokens"].reshape(n_micro, micro_batch, seq),
                   b["labels"].reshape(n_micro, micro_batch, seq))

    # 2-bit-average code budget for one device's activation slice
    budget = (micro_batch // 2) * seq * cfg.d_model * 2 / 8
    plan_log: List = []
    opt = AdamWConfig(lr=lr, weight_decay=0.0)
    _, _, history, wire_b = train_pipeline(
        cfg, mesh, split, opt, batches(), n_micro=n_micro,
        micro_batch=micro_batch, seq=seq, wire_budget_bytes=budget,
        plan_groups=8, plan_log=plan_log)
    plans = [p for _, p in plan_log]
    print(f"[split-pipeline adaptive N={n_stages}] loss "
          + " -> ".join(f"{v:.4f}" for v in history)
          + f" (wire {wire_b / 1024:.1f} KiB/tick; plans {plans})")
    assert history[-1] < history[0], \
        f"adaptive pipeline loss did not decrease: {history}"
    assert plans, "adaptive trainer never adopted a plan"
    for p in plans:
        assert len(p) == 8 and all(1 <= w <= 8 for w in p), p
        assert sum(p) / len(p) <= 2.0 + 1e-9, f"plan over budget: {p}"
    return dict(loss_history=history, wire_bytes_per_tick=wire_b,
                plans=[list(p) for p in plans])


def dryrun_backward(arch: str = "llama3_2_3b", n_micro: int = 4,
                    micro_batch: int = 32, seq: int = 1024,
                    n_stages: int = 2, reduced: bool = False,
                    smoke: bool = False) -> Dict:
    """BEYOND-PAPER: quantize the gradient-return wire too.

    The paper compresses only the forward activations (its Table 4 scope);
    the cotangent crossing back client<-server stays bf16.  Measuring the
    pipeline's total collective-permute bytes with and without 2-bit
    RD-FSQ gradient compression shows the remaining half of the wire."""
    from repro.launch.hlo_analysis import analyze

    mesh = _pipeline_mesh(n_stages, smoke=smoke)
    cfg = _homogeneous_cfg(arch, reduced=reduced, n_stages=n_stages)
    params_sds = jax.eval_shape(
        lambda: init_pipeline_params(jax.random.PRNGKey(0), cfg, n_stages))
    tok_sds, lab_sds = _micro_batch_sds(n_micro, micro_batch, seq)
    fwd_split = SplitConfig(quant=QuantConfig(method="rdfsq", bits=2),
                            learnable_codec=False, n_stages=n_stages)
    n_ticks = n_micro + n_stages - 1

    results = {}
    for name, bwd_q in (("paper_fwd_only", None),
                        ("beyond_fwd_bwd", QuantConfig(method="rdfsq",
                                                       bits=2))):
        step = build_pipeline_grad_step(cfg, mesh, fwd_split, bwd_q,
                                        n_micro, micro_batch, seq)
        with mesh:
            compiled = jax.jit(step).lower(params_sds, tok_sds,
                                           lab_sds).compile()
        hlo = compiled.as_text()
        hl = analyze(hlo)
        cp = hl["collective_by_op"].get("collective-permute", 0)
        wire = pipeline_wire_bytes(cfg, fwd_split, micro_batch, seq, bwd_q,
                                   data_shards=mesh.shape["data"])
        assert_links_match_hlo(f"train {name} N={n_stages}", hlo, mesh,
                               wire, n_ticks, check_bwd=True)
        results[name] = cp
        print(f"[split-pipeline-train {name}] collective-permute/dev = "
              f"{cp / 2 ** 20:.2f} MiB")
    red = 1 - results["beyond_fwd_bwd"] / max(results["paper_fwd_only"], 1)
    print(f"[split-pipeline-train] beyond-paper bwd compression saves "
          f"{red:.4f} of wire bytes vs paper (fwd-only) baseline")
    results["reduction"] = red
    return results


def dryrun_train(arch: str = "llama3_2_3b", n_steps: int = 6,
                 n_micro: int = 4, micro_batch: int = 8, seq: int = 32,
                 n_stages: int = 2, lr: float = 5e-3) -> Dict:
    """Actually train the reduced-config pipeline for a few AdamW steps.

    Executes (not just lowers) the quantized 2-bit wire end to end on a
    small (n_stages x 2) fake-device mesh and checks the loss decreases —
    the acceptance gate for 'the deployment path trains'."""
    from repro.data.pipeline import make_pipeline

    cfg = _homogeneous_cfg(arch, reduced=True, n_stages=n_stages)
    mesh = make_mesh((n_stages, 2), ("pod", "data"))
    split = SplitConfig(quant=QuantConfig(method="rdfsq", bits=2),
                        learnable_codec=False, n_stages=n_stages)
    pipe = make_pipeline(cfg, n_micro * micro_batch, seq, seed=0)

    def batches():
        for _ in range(n_steps):
            b = next(pipe)
            yield (b["tokens"].reshape(n_micro, micro_batch, seq),
                   b["labels"].reshape(n_micro, micro_batch, seq))

    opt = AdamWConfig(lr=lr, weight_decay=0.0)
    _, _, history, wire_b = train_pipeline(
        cfg, mesh, split, opt, batches(), n_micro=n_micro,
        micro_batch=micro_batch, seq=seq)
    print(f"[split-pipeline-train reduced N={n_stages}] loss "
          + " -> ".join(f"{v:.4f}" for v in history)
          + f" (wire {wire_b / 1024:.1f} KiB/tick)")
    assert wire_b > 0, "pipeline reported zero wire bytes"
    assert history[-1] < history[0], \
        f"pipeline loss did not decrease: {history}"
    return dict(loss_history=history, wire_bytes_per_tick=wire_b)


def dryrun_lora_train(arch: str = "llama3_2_3b", n_steps: int = 6,
                      n_micro: int = 2, micro_batch: int = 4, seq: int = 32,
                      n_stages: int = 2, lora_rank: int = 4,
                      lr: float = 3e-2) -> Dict:
    """SplitLoRA pipeline acceptance gate (ROADMAP item 4).

    Trains the reduced pipeline with ``lora_rank`` adapters over the
    quantized wire and asserts the three SplitLoRA invariants:

    1. the loss decreases while every BASE weight stays bit-frozen
       (host-side snapshot compare over all non-adapter leaves);
    2. the AdamW moments are sized by the adapter params only —
       ``param_bytes(opt["m"]) == adapter_bytes(adapters)``;
    3. only the adapter leaves moved.
    """
    from repro.data.pipeline import make_pipeline
    from repro.optim import param_bytes
    from repro.peft import adapter_bytes, adapter_param_count

    cfg = _homogeneous_cfg(arch, reduced=True, n_stages=n_stages)
    mesh = make_mesh((n_stages, 2), ("pod", "data"))
    split = SplitConfig(quant=QuantConfig(method="rdfsq", bits=2),
                        learnable_codec=False, n_stages=n_stages)
    params0 = init_pipeline_params(jax.random.PRNGKey(0), cfg, n_stages,
                                   lora_rank=lora_rank)
    base0 = jax.tree_util.tree_map(
        jnp.copy, {k: v for k, v in params0.items() if k != "adapters"})
    pipe = make_pipeline(cfg, n_micro * micro_batch, seq, seed=0)

    def batches():
        for _ in range(n_steps):
            b = next(pipe)
            yield (b["tokens"].reshape(n_micro, micro_batch, seq),
                   b["labels"].reshape(n_micro, micro_batch, seq))

    opt_cfg = AdamWConfig(lr=lr, weight_decay=0.0)
    params, opt, history, wire_b = train_pipeline(
        cfg, mesh, split, opt_cfg, batches(), n_micro=n_micro,
        micro_batch=micro_batch, seq=seq, params=params0,
        lora_rank=lora_rank)

    # 1. loss decreases over the quantized wire
    assert history[-1] < history[0], \
        f"LoRA pipeline loss did not decrease: {history}"
    # 2. base weights bit-frozen
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(base0),
            jax.tree_util.tree_leaves_with_path(
                {k: v for k, v in params.items() if k != "adapters"})):
        assert bool(jnp.array_equal(a, b)), \
            f"base weight changed during LoRA training: {pa}"
    # 3. moments sized by the adapters, not the base
    ad_bytes = adapter_bytes(params["adapters"])
    m_bytes = param_bytes(opt["m"])
    assert m_bytes == ad_bytes, (
        f"optimizer moments ({m_bytes} B) not sized by adapter params "
        f"({ad_bytes} B)")
    full_bytes = param_bytes(params0)
    print(f"[split-pipeline-lora N={n_stages} r={lora_rank}] loss "
          + " -> ".join(f"{v:.4f}" for v in history)
          + f" | adapters {adapter_param_count(params['adapters'])} params"
          f" ({ad_bytes / 1024:.1f} KiB), moments {m_bytes / 1024:.1f} KiB"
          f" vs full-param {full_bytes / 1024:.1f} KiB"
          f" ({full_bytes / max(ad_bytes, 1):.1f}x smaller opt state)")
    return dict(loss_history=history, wire_bytes_per_tick=wire_b,
                adapter_bytes=ad_bytes, opt_moment_bytes=m_bytes,
                full_param_bytes=full_bytes)


def main(smoke: bool = False) -> Dict:
    out: Dict = {}
    if smoke:
        # CI: reduced config, 4-stage topology, 8 fake devices
        cfg_kw = dict(reduced=True, smoke=True, n_stages=4,
                      n_micro=3, micro_batch=4, seq=16)
        out = dryrun(bits_list=(16, 2), **cfg_kw)
        out["heterogeneous"] = dryrun_heterogeneous()
        out["grouped"] = dryrun_grouped()
        out["train"] = dryrun_train(n_steps=4, n_micro=2, micro_batch=4,
                                    seq=32, n_stages=2)
        out["adaptive"] = dryrun_train_adaptive(n_steps=4)
        out["lora"] = dryrun_lora_train(n_steps=4)
        return out
    out = dryrun()
    out["heterogeneous"] = dryrun_heterogeneous(smoke=False, n_micro=4,
                                                micro_batch=32, seq=1024)
    out["grouped"] = dryrun_grouped(smoke=False, n_micro=4,
                                    micro_batch=32, seq=1024)
    out["backward"] = dryrun_backward()
    out["train"] = dryrun_train()
    out["adaptive"] = dryrun_train_adaptive()
    out["lora"] = dryrun_lora_train()
    return out


if __name__ == "__main__":
    import json

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = main(smoke="--smoke" in sys.argv)
    os.makedirs(os.path.join(os.path.dirname(__file__), "..", "..", "..",
                             "results"), exist_ok=True)
    path = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                        "results", "split_pipeline.json")
    with open(path, "w") as f:
        json.dump({str(k): v for k, v in out.items()}, f, indent=1)
    print("saved", path)
