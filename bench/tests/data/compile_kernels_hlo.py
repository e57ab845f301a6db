#!/usr/bin/env python3
"""Write ``v5e_kernels.hlo.txt``, which ``test_bench_trace.py`` reads: the
Pallas custom calls of the programs the benchmark traces, compiled for a
described TPU v5e (no chip needed).

    python3 bench/tests/data/compile_kernels_hlo.py <out_file>

The programs are the train step and the paged decode tick of tinyllava
cut to 2 layers, at batch 2 x 1024 and 8 slots.  For each program the
file keeps its ``HloModule`` line and its ``tpu_custom_call``
instructions, which is all the trace reduction reads from a compiled
program, with the checkout's own path taken out of the kernels' source
locations.
"""
import base64
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(out: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_ATTN_IMPL"] = "pallas"
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import spec, traffic, weights as W
    from repro.kernels import attention_ops
    from repro.optim import AdamWConfig, init_opt_state
    from repro.serve import paged
    from repro.train import loop

    jax.config.update("jax_enable_compilation_cache", False)
    attention_ops._interpret = lambda: False      # compile the kernels
    dev = topologies.get_topology_desc(platform="tpu",
                                       topology_name="v5e:2x2").devices[0]
    sh = SingleDeviceSharding(dev)

    def put(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree)

    with open(os.path.join(ROOT, "bench", "configs", "tinyllava.json")) as f:
        conf = json.load(f)
    conf["n_layers"] = 2
    c, cfg = spec.sizes(conf), spec.arch_config(conf)
    opt = AdamWConfig(lr=1e-3)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: W.init_params(c, k), key)
    state = jax.eval_shape(lambda p: loop.TrainState(
        params=p, opt=init_opt_state(p, opt),
        step=jnp.zeros((), jnp.int32)), params)
    batch = jax.eval_shape(lambda k: traffic.train_batches(
        dict(seq_len=1024, image_std=1.0, distinct_batches=1),
        dict(batch=2), c, k)[0], key)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sh)
    train = jax.jit(loop.make_train_step(cfg, opt, total_steps=0),
                    donate_argnums=(0,))
    texts = [train.lower(put(state), put(batch), rng).compile().as_text()]
    i32 = jnp.int32
    pools = jax.eval_shape(lambda: paged.init_pools(cfg, 1 + 8 * 70, 16))
    tick = paged.compiled_paged_step(cfg)
    texts.append(tick.lower(
        put(params), put(pools),
        dict(tokens=jax.ShapeDtypeStruct((8, 1), i32, sharding=sh)),
        jax.ShapeDtypeStruct((8,), i32, sharding=sh),
        jax.ShapeDtypeStruct((8, 64), i32, sharding=sh)).compile().as_text())
    prefix = (ROOT + os.sep).encode()

    def relative(m):
        body = base64.b64decode(m.group(1)).replace(prefix, b"")
        return '"body":"' + base64.b64encode(body).decode() + '"'

    with open(out, "w") as f:
        for text in texts:
            lines = text.splitlines()
            f.write(lines[0] + "\n")
            f.writelines(re.sub(r'"body":"([^"]*)"', relative, ln) + "\n"
                         for ln in lines
                         if 'custom_call_target="tpu_custom_call"' in ln)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
