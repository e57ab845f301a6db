"""A training cell: the program's jitted train step, driven from the seed.

Set-up builds one object, the compiled step with its donated state, and
drives it through its first ``check_steps`` steps on distinct rows; the
window then runs the same compiled call back to back.  The first steps'
losses, the first gradient (from Adam's first moment after one step) and
the parameters' change after the last of them are compared with the
plain reference once the window has closed.
"""
from __future__ import annotations

import time
from typing import Dict

from bench.harness import checks as C
from bench.harness import traffic, weights as W


def _opt(cell_params: Dict):
    from repro.optim import AdamWConfig

    return AdamWConfig(**cell_params["optimizer"])


def build(run) -> Dict:
    """Everything up to the window: the compiled step, its state driven
    through the checked steps, and the readings of those steps."""
    import jax
    import jax.numpy as jnp

    from repro.optim import init_opt_state
    from repro.train import loop as train_loop

    c, cfg, cp, model = run.sizes, run.arch, run.cell.params, run.model
    key = W.base_key(run.seed)
    opt = _opt(cp)
    params = model.init_params(c, key)
    state = train_loop.TrainState(params=params,
                                  opt=init_opt_state(params, opt),
                                  step=jnp.zeros((), jnp.int32))
    batches = traffic.train_batches(run.cell.traffic, cp, c,
                                    W.sub_key(key, 2))
    rng = W.sub_key(key, 3)
    step = jax.jit(train_loop.make_train_step(cfg, opt, total_steps=0),
                   donate_argnums=(0,))
    compiled = step.lower(state, batches[0], rng).compile()

    b1 = opt.b1
    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
    change = jax.jit(lambda p, k: norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        p, model.init_params(c, k))))

    n_check = int(cp["check_steps"])
    losses, grad_norms = [], None
    for t in range(n_check):
        state, m = compiled(state, batches[t], rng)
        losses.append(m["loss"])
        if t == 0:
            grad_norms = norms(jax.tree_util.tree_map(
                lambda x: x / (1.0 - b1), state.opt["m"]))
    change_norms = change(state.params, key)
    readings = dict(losses=[float(x) for x in losses],
                    grad_norms=named(grad_norms),
                    change_norms=named(change_norms))
    return dict(compiled=compiled, state=state, batches=batches, rng=rng,
                readings=readings)


def program_texts(run, built: Dict) -> list:
    """The compiled HLO of the programs the window ran."""
    return [built["compiled"].as_text()]


def named(tree) -> Dict[str, float]:
    """{leaf path: value} of a tree of scalars."""
    import jax

    return {jax.tree_util.keystr(p): float(v) for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def drive(step, state, batches: list, start: int, ahead: int,
          seconds: float):
    """Steps back to back for ``seconds``, with ``ahead`` steps dispatched
    ahead of the one the host waits for, so that the chip stays fed while
    the host stands still.  ``step(state, batch)`` returns the new state
    and the step's loss.  Once the time is up nothing more is sent; every
    step sent is waited for and counted, and the clock is read after that
    wait.  Returns (state, steps done, seconds)."""
    from collections import deque

    from jax.profiler import TraceAnnotation

    n_b = len(batches)
    t0 = time.perf_counter()
    sent, i = deque(), start
    while time.perf_counter() - t0 < seconds:
        with TraceAnnotation("bench.train_step"):
            state, loss = step(state, batches[i % n_b])
        i += 1
        sent.append(loss)
        if len(sent) > ahead:
            with TraceAnnotation("bench.wait_step"):
                sent.popleft().block_until_ready()
    with TraceAnnotation("bench.wait_step"):
        for loss in sent:
            loss.block_until_ready()
    return state, i - start, time.perf_counter() - t0


def window(run, built: Dict, seconds: float) -> Dict:
    """The compiled step driven by ``drive`` from the first step after the
    checked ones.  Returns the counters of the window."""
    compiled, rng = built["compiled"], built["rng"]

    def step(state, batch):
        state, m = compiled(state, batch, rng)
        return state, m["loss"]

    cp = run.cell.params
    built["state"], done, elapsed = drive(
        step, built["state"], built["batches"], int(cp["check_steps"]),
        int(cp["ahead_steps"]), seconds)
    tokens = done * traffic.tokens_per_batch(run.cell.traffic, cp)
    return dict(steps=done, tokens=tokens, window_s=elapsed)


def end_to_end(counters: Dict) -> Dict[str, float]:
    return dict(train_tokens_per_s=counters["tokens"] / counters["window_s"])


def window_flops(run, counters: Dict) -> float:
    """Forward and backward of every step the window completed."""
    return counters["steps"] * run.model.train_step_flops(
        run.sizes, int(run.cell.params["batch"]),
        int(run.cell.traffic["seq_len"]))


def reference_readings(run, lp: bool = False) -> Dict:
    cp = run.cell.params
    key = W.base_key(run.seed)
    batches = traffic.train_batches(run.cell.traffic, cp, run.sizes,
                                    W.sub_key(key, 2))
    n = int(cp["check_steps"])
    return run.model.train_reference(run.sizes, key, batches[:n],
                                     cp["optimizer"], n,
                                     int(cp["reference_row_block"]), lp=lp)


def compare(prog: Dict, ref: Dict, cell_params: Dict) -> Dict[str, float]:
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))
    keep = C.moving_leaves(ref["grad_norms"],
                           float(cell_params["still_leaf_share"]))
    return dict(
        loss_gap=loss,
        grad_norm_gap=C.gap_of_norms(prog["grad_norms"], ref["grad_norms"]),
        change_norm_gap=C.gap_of_norms(prog["change_norms"],
                                       ref["change_norms"], keep))


def check(run, built: Dict, counters: Dict) -> Dict[str, float]:
    prog = built["readings"]
    for k in ("state", "batches", "compiled"):
        built.pop(k, None)
    if "reference" not in built:  # a fault run takes its seed's reference
        built["reference"] = reference_readings(run)
    return compare(prog, built["reference"], run.cell.params)


def control(run, built: Dict, counters: Dict) -> Dict[str, float]:
    """The reference in float8 put in the program's place."""
    ref = built.get("reference") or reference_readings(run)
    return compare(reference_readings(run, lp=True), ref, run.cell.params)


def attempted(counters: Dict) -> int:
    return counters["steps"]


def failed(counters: Dict) -> int:
    return 0
