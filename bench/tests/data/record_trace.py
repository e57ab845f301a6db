#!/usr/bin/env python3
"""Record a small TPU trace, to check the trace reduction's line names,
program names and host spans against a real one (needs a chip).

    python3 bench/tests/data/record_trace.py <out_dir>

On one TPU chip: a jitted step holding the flash attention forward
kernel at a small shape runs five times inside a ``bench.window`` host
span, with host sleeps between the calls (idle gaps, inside a
``bench.host_wait`` span).  Writes
``v5e_trace.xplane.pb`` and the step's compiled HLO
(``v5e_trace.hlo.txt``) to ``out_dir``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from bench.harness.trace import WINDOW_SPAN
    from repro.kernels import flash_kernel

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    b, h, kh, s, d = 2, 4, 2, 256, 64
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k0, (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(k1, (b, kh, s, d), jnp.bfloat16)
    v = jax.random.normal(k2, (b, kh, s, d), jnp.bfloat16)
    pos = jnp.arange(s, dtype=jnp.int32)

    @jax.jit
    def step(q, k, v, pos):
        o = flash_kernel.forward(q, k, v, pos[:, None], pos[None, :],
                                 window=None, block=128, interpret=False)
        return jnp.tanh(o[0].astype(jnp.float32)).sum()

    compiled = step.lower(q, k, v, pos).compile()
    compiled(q, k, v, pos).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench.step"):
                compiled(q, k, v, pos).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, os.path.join(out_dir, "v5e_trace.xplane.pb"))
    with open(os.path.join(out_dir, "v5e_trace.hlo.txt"), "w") as f:
        f.write(compiled.as_text())
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
