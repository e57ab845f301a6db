"""Sharded execution on a small fake-device mesh.

Device count locks at first jax init, so the mesh tests run in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 —
the same mechanism the production dry-run uses with 512.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=420)


def test_sharded_train_step_matches_single_device():
    """4x2 mesh train step == unsharded train step (same math)."""
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.data.pipeline import make_pipeline
        from repro.launch.mesh import make_mesh
        from repro.optim import AdamWConfig
        from repro.sharding import mesh_axes, state_pspecs, batch_pspecs
        from repro.train.loop import init_state, make_train_step

        cfg = get_config("llama3_2_3b").reduced()
        opt = AdamWConfig(lr=1e-3)
        key = jax.random.PRNGKey(0)
        state = init_state(key, cfg, opt)
        batch = next(make_pipeline(cfg, 8, 16))
        step = make_train_step(cfg, opt)

        # single device reference
        s_ref, m_ref = jax.jit(step)(state, batch, key)

        mesh = make_mesh((4, 2), ("data", "model"))
        axes = mesh_axes(mesh)
        st_specs = state_pspecs(state, axes, fsdp=True)
        b_specs = batch_pspecs(batch, ("data",), axes)
        named = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        with mesh:
            f = jax.jit(step, in_shardings=(named(st_specs),
                                            named(b_specs),
                                            NamedSharding(mesh, P())))
            s_sh, m_sh = f(state, batch, key)
        assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-3, \\
            (float(m_ref["loss"]), float(m_sh["loss"]))
        for a, b in zip(jax.tree_util.tree_leaves(s_ref.params),
                        jax.tree_util.tree_leaves(s_sh.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-3)
        print("MESH_TRAIN_OK")
    """)
    assert "MESH_TRAIN_OK" in r.stdout, r.stdout + r.stderr


def test_quantized_ship_across_pod_axis():
    """quantized_ship moves bit-packed payloads over a pod axis inside
    shard_map, and the gradient returns on the reverse permutation."""
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.core import QuantConfig, quantized_ship, roundtrip
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("pod", "data"))
        qcfg = QuantConfig(method="rdfsq", bits=2)
        perm = [(0, 1), (1, 0)]

        # replicate over data so per-sample quantizer stats match the
        # single-device reference (RD-FSQ stats are per local sample)
        @partial(jax.shard_map, mesh=mesh, in_specs=P("pod", None, None),
                 out_specs=P("pod", None, None))
        def ship(x):
            return quantized_ship(qcfg, x, "pod", tuple(perm))

        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 64))
        y = jax.jit(ship)(x)
        # pod 1 receives pod 0's dequantized activation and vice versa
        ref0, _ = roundtrip(qcfg, x[:2])
        np.testing.assert_allclose(np.asarray(y[2:]), np.asarray(ref0),
                                   atol=1e-4)
        # gradient passes back through the reverse permutation
        g = jax.grad(lambda x: jnp.sum(jax.jit(ship)(x) * 2.0))(x)
        np.testing.assert_allclose(np.asarray(g), 2.0, atol=1e-5)
        print("SHIP_OK")
    """)
    assert "SHIP_OK" in r.stdout, r.stdout + r.stderr


def test_dryrun_one_small_arch():
    """End-to-end dryrun_one on the 512-device production mesh (1 combo)."""
    r = _run("""
        from repro.launch.dryrun import dryrun_one  # sets XLA_FLAGS first
        res = dryrun_one("musicgen_large", "long_500k", multi_pod=False,
                         save=False, verbose=False)
        assert res["chips"] == 256  # 16x16 single pod
        assert res["roofline"]["dominant"] in ("compute", "memory",
                                               "collective")
        print("DRYRUN_OK")
    """)
    assert "DRYRUN_OK" in r.stdout, r.stdout + r.stderr


def test_split_pipeline_loss_matches_monolithic():
    """Pipeline next-token CE == monolithic forward + CE, and the
    reported per-tick wire bytes are the static payload constant."""
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.core import quantizers as Q
        from repro.core.quantizers import QuantConfig
        from repro.launch import split_pipeline as sp
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as tf
        from repro.models.layers import embedding as emb_mod
        from repro.models.layers.norms import rms_norm
        from repro.train.losses import IGNORE, cross_entropy

        cfg = sp._homogeneous_cfg("llama3_2_3b", reduced=True)
        mesh = make_mesh((2, 4), ("pod", "data"))
        key = jax.random.PRNGKey(0)
        params = sp.init_pipeline_params(key, cfg)
        n_micro, mb, seq = 3, 4, 16
        tokens = jax.random.randint(key, (n_micro, mb, seq), 0,
                                    cfg.vocab_size)
        labels = jnp.concatenate(
            [tokens[:, :, 1:],
             jnp.full((n_micro, mb, 1), IGNORE, tokens.dtype)], axis=-1)

        def mono_loss(tok, lab, qcfg):
            x = emb_mod.embed(params["embed"], tok, jnp.float32)
            pos = jnp.arange(seq, dtype=jnp.int32)
            for stage in range(2):
                blocks = jax.tree_util.tree_map(lambda a: a[stage],
                                                params["blocks"])
                def body(h, p):
                    h, _, _ = tf.block_forward(cfg, "dense", p, h,
                                               positions=pos, window=None)
                    return h, None
                x, _ = jax.lax.scan(body, x, blocks)
                if stage == 0:  # the wire: quantize -> dequantize
                    x, _ = Q.roundtrip(qcfg, x)
            out = rms_norm(x, params["final_norm"], cfg.norm_eps)
            logits = emb_mod.head_logits(params["head"], out)
            return cross_entropy(logits, lab)

        for method in ("identity", "rdfsq"):
            qcfg = QuantConfig(method=method, bits=2)
            ref = np.mean([float(mono_loss(tokens[i], labels[i], qcfg))
                           for i in range(n_micro)])
            step = sp.build_pipeline_step(cfg, mesh, qcfg, n_micro, mb,
                                          seq)
            with mesh:
                loss, wire_b = jax.jit(step)(params, tokens, labels)
            assert abs(float(loss) - ref) < 2e-2, (method, float(loss),
                                                   ref)
            expected = sp.pipeline_wire_bytes(
                cfg, qcfg, mb, seq, data_shards=4)["fwd_tick"]
            assert float(wire_b) == expected > 0, (float(wire_b),
                                                   expected)
        print("PIPELINE_OK")
    """)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr


def test_split_pipeline_grad_and_nstage():
    """Gradients cross the quantized wire into every stage (incl. the
    embed on stage 0), and a 4-stage topology runs fill/drain right."""
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.quantizers import QuantConfig
        from repro.core.split import SplitConfig
        from repro.launch import split_pipeline as sp
        from repro.launch.mesh import make_mesh
        from repro.train.losses import IGNORE

        cfg = sp._homogeneous_cfg("llama3_2_3b", reduced=True)
        key = jax.random.PRNGKey(0)
        n_micro, mb, seq = 3, 4, 16
        tokens = jax.random.randint(key, (n_micro, mb, seq), 0,
                                    cfg.vocab_size)
        labels = jnp.concatenate(
            [tokens[:, :, 1:],
             jnp.full((n_micro, mb, 1), IGNORE, tokens.dtype)], axis=-1)

        mesh = make_mesh((2, 4), ("pod", "data"))
        params = sp.init_pipeline_params(key, cfg)
        qcfg = QuantConfig(method="rdfsq", bits=2)
        gstep = sp.build_pipeline_grad_step(cfg, mesh, qcfg,
                                            QuantConfig(method="rdfsq",
                                                        bits=2),
                                            n_micro, mb, seq)
        with mesh:
            loss, grads, wire_b = jax.jit(gstep)(params, tokens, labels)
        assert np.isfinite(float(loss)) and float(wire_b) > 0
        for s in range(2):
            g = sum(float(jnp.sum(jnp.abs(v[s]))) for v in
                    jax.tree_util.tree_leaves(grads["blocks"]))
            assert g > 0, (s, g)
        assert float(jnp.sum(jnp.abs(grads["embed"]["emb"]))) > 0

        # 4 stages x 1 layer with HETEROGENEOUS per-cut compression:
        # fill/drain over n_micro + 3 ticks, loss parity against the
        # monolithic forward applying each cut's roundtrip in place
        from repro.core import quantizers as Q
        from repro.models import transformer as tf
        from repro.models.layers import embedding as emb_mod
        from repro.models.layers.norms import rms_norm
        from repro.train.losses import cross_entropy

        cfg4 = dataclasses.replace(cfg, n_layers=4)
        mesh4 = make_mesh((4, 2), ("pod", "data"))
        quants = (QuantConfig(method="rdfsq", bits=2),
                  QuantConfig(method="nf", bits=4),
                  QuantConfig(method="rdfsq", bits=2))
        split4 = SplitConfig(quant=qcfg, learnable_codec=False,
                             n_stages=4, stage_quants=quants)
        params4 = sp.init_pipeline_params(key, cfg4, 4)

        def mono_loss(tok, lab):
            x = emb_mod.embed(params4["embed"], tok, jnp.float32)
            pos = jnp.arange(seq, dtype=jnp.int32)
            for stage in range(4):
                p = jax.tree_util.tree_map(lambda a: a[stage, 0],
                                           params4["blocks"])
                x, _, _ = tf.block_forward(cfg4, "dense", p, x,
                                           positions=pos, window=None)
                if stage < 3:
                    x, _ = Q.roundtrip(quants[stage], x)
            out = rms_norm(x, params4["final_norm"], cfg4.norm_eps)
            return cross_entropy(
                emb_mod.head_logits(params4["head"], out), lab)

        ref = np.mean([float(mono_loss(tokens[i], labels[i]))
                       for i in range(n_micro)])
        step4 = sp.build_pipeline_step(cfg4, mesh4, split4, n_micro, mb,
                                       seq)
        with mesh4:
            loss4, wire4 = jax.jit(step4)(params4, tokens, labels)
        assert abs(float(loss4) - ref) < 2e-2, (float(loss4), ref)
        # two distinct cut configs -> wire bytes sum over both groups
        expected4 = sp.pipeline_wire_bytes(cfg4, split4, mb, seq,
                                           data_shards=2)["fwd_tick"]
        assert float(wire4) == expected4 > 0
        print("GRAD_NSTAGE_OK")
    """)
    assert "GRAD_NSTAGE_OK" in r.stdout, r.stdout + r.stderr


def test_split_pipeline_trains():
    """train_pipeline: AdamW over the 2-bit wire decreases the loss."""
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        from repro.launch import split_pipeline as sp
        res = sp.dryrun_train(n_steps=4, n_micro=2, micro_batch=4,
                              seq=32, n_stages=2)
        hist = res["loss_history"]
        assert hist[-1] < hist[0], hist
        assert res["wire_bytes_per_tick"] > 0
        print("TRAIN_OK")
    """)
    assert "TRAIN_OK" in r.stdout, r.stdout + r.stderr
