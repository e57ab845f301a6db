"""Training launcher.

Single-host (CPU/dev) by default; ``--mesh`` runs the sharded step on a
fake-device mesh (the production entry point on a real pod is identical —
jax.distributed.initialize + make_production_mesh).

    PYTHONPATH=src python -m repro.launch.train --arch tinyllava \
        --steps 200 --batch 8 --seq 64 [--method rdfsq --bits 2]
"""
from __future__ import annotations

import argparse
import dataclasses
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllava")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--method", default=None,
                    help="compressor method: any registered quantizer "
                         "(fsq|rdfsq|nf|topk|identity) or 'none' to "
                         "disable the cut")
    ap.add_argument("--bits", type=int, default=None)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", dest="remat", action="store_true",
                    default=None, help="force layer remat on")
    ap.add_argument("--no-remat", dest="remat", action="store_false",
                    help="force layer remat off")
    ap.add_argument("--remat-group", type=int, default=None,
                    help=">1 enables two-level (sqrt-L) checkpointing "
                         "with this group size")
    ap.add_argument("--mesh", default=None,
                    help="DxM fake-device mesh, e.g. 4x2")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    if args.mesh:
        d, m = (int(v) for v in args.mesh.split("x"))
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={d * m}"

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro import checkpoint
    from repro.configs import get_config
    from repro.core.quantizers import QuantConfig
    from repro.data.pipeline import make_pipeline
    from repro.launch.mesh import make_mesh
    from repro.optim import AdamWConfig
    from repro.sharding import batch_pspecs, mesh_axes, state_pspecs
    from repro.sharding import ctx as shard_ctx
    from repro.train.loop import init_state, make_train_step

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.method:
        from repro.core.quantizers import methods
        known = sorted(set(methods()) | {"none"})
        if args.method not in known:
            ap.error(f"--method {args.method!r} is not a registered "
                     f"quantizer (choose from {', '.join(known)})")
        split = dataclasses.replace(
            cfg.split, quant=QuantConfig(method=args.method,
                                         bits=args.bits or 2),
            enabled=args.method != "identity")
        cfg = dataclasses.replace(cfg, split=split)

    opt_cfg = AdamWConfig(lr=args.lr)
    key = jax.random.PRNGKey(0)
    state = init_state(key, cfg, opt_cfg)
    step = make_train_step(cfg, opt_cfg, total_steps=args.steps,
                           grad_accum=args.grad_accum, remat=args.remat,
                           remat_group=args.remat_group)
    data = make_pipeline(cfg, args.batch, args.seq)

    if args.mesh:
        d, m = (int(v) for v in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
        axes = mesh_axes(mesh)
        shard_ctx.install(("data",), axes=axes)
        st_specs = state_pspecs(state, axes, fsdp=True)
        named = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        sample = next(data)
        # out_shardings must pin the returned state to the SAME specs as
        # the input state: left to the compiler, step N's output sharding
        # can differ from the declared in_shardings and the step N+1 call
        # fails with a sharding mismatch.
        step_fn = jax.jit(step, in_shardings=(
            named(st_specs),
            named(batch_pspecs(sample, ("data",), axes)),
            NamedSharding(mesh, P())),
            out_shardings=(named(st_specs), NamedSharding(mesh, P())))
        ctx = mesh
    else:
        step_fn = jax.jit(step)
        import contextlib
        ctx = contextlib.nullcontext()

    with ctx:
        for i in range(args.steps):
            batch = next(data)
            key, sub = jax.random.split(key)
            state, metrics = step_fn(state, batch, sub)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"step {i:5d}  loss={float(metrics['loss']):.4f}  "
                      f"ce={float(metrics['ce']):.4f}  "
                      f"commit={float(metrics['commit']):.4f}  "
                      f"gnorm={float(metrics['grad_norm']):.3f}")
    if args.ckpt:
        checkpoint.save(args.ckpt, state)
        print(f"saved checkpoint to {args.ckpt}")


if __name__ == "__main__":
    main()
