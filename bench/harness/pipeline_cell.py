"""A pipeline cell: the program's split pipeline (``launch/split_pipeline``)
on a (pod, data) mesh of the cell's chips.

The configuration states the deployment, and the driver runs it as
stated or not at all: its ``split`` group gives ``n_stages`` partitions
of equal runs of the decoder's layers (``cut_layer`` is the first
partition's end), the codec (``learnable_codec`` has to be false: the
pipeline path has none) and the wire's quantizer; ``n_image_tokens`` has
to be 0 (the path embeds text rows only); the cell's mesh puts one
partition on each ``pod``.  The first partition embeds the text, the
last runs the final norm, the head and the loss.  Between them each
micro-batch's activations cross the chips as the RD-FSQ code
(``quantized_ship``: encode, ``ppermute``, decode), GPipe fill and drain
over ``n_micro + n_stages - 1`` ticks; the gradient comes back
unquantized.  The step is the pipeline's gradient
(``build_pipeline_grad_step``) and the program's AdamW update
(``train.loop.apply_gradients``, constant rate) in one jitted call with
the state donated, driven as a training cell's (``train_cell.drive``).

Set-up makes the state on the mesh from the seed in one call and drives
the compiled step through its first ``check_steps`` steps on distinct
rows, reading their losses, the first gradient and the parameters'
change; the check compares them with the model module's plain reference
of the same job (``pipeline_train_reference``, ``bench/models/``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from bench.harness import traffic, train_cell, weights as W

end_to_end = train_cell.end_to_end
attempted = train_cell.attempted
failed = train_cell.failed


def _rows(mix: Dict) -> int:
    return int(mix["micro_batch"]) * int(mix["n_micro"])


def _n_stages(run) -> int:
    """The configuration's partitions, checked against what the pipeline
    path can run: equal partitions, one to a pod, no learnable codec, no
    image tokens."""
    split = run.cell.config["split"]
    n = int(split["n_stages"])
    per, rest = divmod(run.sizes.n_layers, n)
    faults = []
    if rest or int(split["cut_layer"]) != per:
        faults.append(f"cut_layer {split['cut_layer']} does not cut "
                      f"{run.sizes.n_layers} layers into {n} equal "
                      f"partitions")
    if split["learnable_codec"]:
        faults.append("learnable_codec is true; the pipeline path has no "
                      "learnable codec")
    if run.sizes.n_image_tokens:
        faults.append(f"n_image_tokens {run.sizes.n_image_tokens}; the "
                      f"pipeline path embeds text rows only")
    if int(run.cell.params["mesh"]["pod"]) != n:
        faults.append(f"the mesh's pod axis {run.cell.params['mesh']['pod']}"
                      f" is not the {n} partitions")
    if faults:
        raise ValueError("the pipeline path cannot run this configuration "
                         "as stated: " + "; ".join(faults))
    return n


def text_batches(run, key) -> List[Dict]:
    """The mix's distinct steps of text rows, tokens and next-token labels
    each (n_micro, micro_batch, seq_len), from the one traffic generator
    (its image part is empty: the configuration has no image tokens)."""
    mix = run.cell.traffic
    made = traffic.train_batches(dict(mix, image_std=0.0),
                                 dict(batch=_rows(mix)), run.sizes, key)
    shape = (int(mix["n_micro"]), int(mix["micro_batch"]),
             int(mix["seq_len"]))
    return [dict(tokens=b["tokens"].reshape(shape),
                 labels=b["labels"].reshape(shape)) for b in made]


def build(run) -> Dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import split_pipeline as sp
    from repro.launch.mesh import make_mesh
    from repro.optim import init_opt_state
    from repro.train import loop

    c, cfg, cp, mix = run.sizes, run.arch, run.cell.params, run.cell.traffic
    n_stages = _n_stages(run)
    split = dataclasses.replace(cfg.split, n_stages=n_stages)
    mesh = make_mesh((n_stages, int(cp["mesh"]["data"])), ("pod", "data"),
                     devices=run.devices)
    opt = train_cell._opt(cp)
    key = W.base_key(run.seed)

    def named(tree):
        return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), tree,
                                      is_leaf=lambda x: isinstance(x, P))

    p_sh, one = named(sp.pipeline_specs(cfg, n_stages)), named(P())
    s_sh = loop.TrainState(params=p_sh, opt=dict(m=p_sh, v=p_sh, step=one),
                           step=one)
    b_sh = named(P(None, "data", None))

    def fresh(k):
        params = run.model.pipeline_params(c, k, n_stages)
        return loop.TrainState(params=params,
                               opt=init_opt_state(params, opt),
                               step=jnp.zeros((), jnp.int32))

    state = jax.jit(fresh, out_shardings=s_sh)(key)
    batches = [jax.device_put(b, b_sh)
               for b in text_batches(run, W.sub_key(key, 2))]
    grad_step = sp.build_pipeline_grad_step(
        cfg, mesh, split, None, int(mix["n_micro"]), int(mix["micro_batch"]),
        int(mix["seq_len"]))

    def update(state, tokens, labels):
        loss, grads, _ = grad_step(state.params, tokens, labels)
        state, _ = loop.apply_gradients(state, grads, opt)
        return state, loss

    compiled = jax.jit(update, in_shardings=(s_sh, b_sh, b_sh),
                       out_shardings=(s_sh, one), donate_argnums=(0,)).lower(
        state, batches[0]["tokens"], batches[0]["labels"]).compile()

    def step(state, batch):
        return compiled(state, batch["tokens"], batch["labels"])

    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
    change = jax.jit(lambda p, k: norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        p, run.model.pipeline_params(c, k, n_stages))))

    losses, grad_norms = [], None
    for t in range(int(cp["check_steps"])):
        state, loss = step(state, batches[t])
        losses.append(loss)
        if t == 0:
            grad_norms = norms(jax.tree_util.tree_map(
                lambda x: x / (1.0 - opt.b1), state.opt["m"]))
    readings = dict(losses=[float(x) for x in losses],
                    grad_norms=train_cell.named(grad_norms),
                    change_norms=train_cell.named(change(state.params, key)))
    return dict(compiled=compiled, step=step, state=state, batches=batches,
                readings=readings)


def program_texts(run, built: Dict) -> list:
    """The compiled HLO of the step the window ran."""
    return [built["compiled"].as_text()]


def window(run, built: Dict, seconds: float) -> Dict:
    """The compiled step driven by ``train_cell.drive`` from the first step
    after the checked ones.  Returns the counters of the window."""
    cp, mix = run.cell.params, run.cell.traffic
    built["state"], done, elapsed = train_cell.drive(
        built["step"], built["state"], built["batches"],
        int(cp["check_steps"]), int(cp["ahead_steps"]), seconds)
    tokens = done * traffic.tokens_per_batch(mix, dict(batch=_rows(mix)))
    return dict(steps=done, tokens=tokens, window_s=elapsed)


def window_flops(run, counters: Dict) -> float:
    """Forward and backward of every step the window completed (text
    rows: the configuration has no image tokens and no learnable
    codec)."""
    mix = run.cell.traffic
    return counters["steps"] * run.model.train_step_flops(
        run.sizes, _rows(mix), int(mix["seq_len"]))


def reference_readings(run, lp: bool = False) -> Dict:
    """The reference over the first ``check_steps`` steps' rows, each block
    of ``reference_row_block`` rows split between the cell's chips."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cp = run.cell.params
    key = W.base_key(run.seed)
    n = int(cp["check_steps"])
    rows = NamedSharding(Mesh(np.array(run.devices), ("rows",)), P("rows"))
    batches = [jax.device_put({k: v.reshape(-1, v.shape[-1])
                               for k, v in b.items()}, rows)
               for b in text_batches(run, W.sub_key(key, 2))[:n]]
    return run.model.pipeline_train_reference(
        run.sizes, key, batches, cp["optimizer"], n,
        int(cp["reference_row_block"]), _n_stages(run), lp=lp)


def check(run, built: Dict, counters: Dict) -> Dict[str, float]:
    prog = built["readings"]
    for k in ("state", "batches", "compiled", "step"):
        built.pop(k, None)
    if "reference" not in built:  # a fault run takes its seed's reference
        built["reference"] = reference_readings(run)
    return train_cell.compare(prog, built["reference"], run.cell.params)


def control(run, built: Dict, counters: Dict) -> Dict[str, float]:
    """The reference in float8 put in the program's place."""
    ref = built.get("reference") or reference_readings(run)
    return train_cell.compare(reference_readings(run, lp=True), ref,
                              run.cell.params)


# -- faults of this path, planted by the tests and ``calibrate.py --fault`` --

def half_batch(patch) -> None:
    """The pipeline's gradient leaves out half of each micro-batch: the
    mean is taken over the rest."""
    from repro.launch import split_pipeline as sp

    orig = sp.build_pipeline_grad_step

    def build_step(*a, **kw):
        step = orig(*a, **kw)

        def broken(params, tokens, labels):
            half = tokens.shape[1] // 2
            return step(params, tokens[:, :half], labels[:, :half])
        return broken
    patch(sp, "build_pipeline_grad_step", build_step)


def state_unchanged(patch) -> None:
    """The update returns the state it was given."""
    from repro.train import loop

    patch(loop, "apply_gradients", lambda state, grads, opt, **kw: (
        state, {}))


def wire_dropped(patch) -> None:
    """The exchange between the stages left out: each stage takes the
    round trip of its own code (straight through, as the wire's
    gradient), and nothing crosses the chips."""
    import jax

    from repro.core import quantizers
    from repro.launch import schedules

    def ship(q, x, axis, perm, bwd=None):
        code = quantizers.encode(q, jax.lax.stop_gradient(x))
        return x + (quantizers.decode(q, code).astype(x.dtype)
                    - jax.lax.stop_gradient(x))
    patch(schedules, "quantized_ship", ship)


FAULTS = {f.__name__: f for f in (half_batch, state_unchanged,
                                  wire_dropped)}
