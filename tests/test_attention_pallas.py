"""Pallas attention kernels (interpret mode) vs the jnp reference.

Covers the acceptance criteria of the flash-kernel tentpole: forward AND
``jax.grad`` parity across causal / sliding-window / GQA / MLA
(Dv != Dk) / ragged ``kv_valid_len`` shapes, bf16 operands, both decode
kernels (incl. ``decode_attention_q8`` vs a dequantize-then-attend
oracle), and the ``REPRO_ATTN_IMPL`` env-var dispatch end-to-end through
``gqa_decode``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import attention_ops
from repro.models.layers.attention import (decode_attention,
                                           decode_attention_q8,
                                           flash_attention, gqa_decode,
                                           gqa_forward,
                                           init_attention_params,
                                           init_kv_cache, quantize_kv_token)

KEY = jax.random.PRNGKey(0)


def _qkv(sq, h, kh, d, dv, skv=None, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    skv = sq if skv is None else skv
    q = jax.random.normal(ks[0], (2, sq, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (2, skv, kh, d)).astype(dtype)
    v = jax.random.normal(ks[2], (2, skv, kh, dv)).astype(dtype)
    return q, k, v


# ---------------------------------------------------------------------------
# flash forward + grad parity (fp32-accumulation tolerance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,h,kh,d,dv,window,chunk", [
    (96, 4, 2, 16, 16, None, 32),    # GQA causal
    (96, 4, 2, 16, 16, 48, 32),      # sliding window
    (100, 4, 4, 8, 12, None, 32),    # unaligned length, MLA-style dv != d
    (64, 8, 2, 32, 32, 16, 16),      # tight window, wide grouping
])
def test_flash_pallas_matches_reference(sq, h, kh, d, dv, window, chunk):
    q, k, v = _qkv(sq, h, kh, d, dv)
    out_ref = flash_attention(q, k, v, window=window, q_chunk=chunk,
                              kv_chunk=chunk, impl="jnp")
    out_pal = flash_attention(q, k, v, window=window, q_chunk=chunk,
                              kv_chunk=chunk, impl="pallas")
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               atol=2e-5)

    def loss(impl):
        return lambda q, k, v: (flash_attention(
            q, k, v, window=window, q_chunk=chunk, kv_chunk=chunk,
            impl=impl) ** 2).sum()

    g_pal = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_pallas_bf16():
    q, k, v = _qkv(64, 4, 2, 16, 16, dtype=jnp.bfloat16, seed=1)
    out_pal = flash_attention(q, k, v, q_chunk=32, kv_chunk=32,
                              impl="pallas")
    assert out_pal.dtype == jnp.bfloat16
    out_ref = flash_attention(q, k, v, q_chunk=32, kv_chunk=32, impl="jnp")
    np.testing.assert_allclose(np.asarray(out_pal, np.float32),
                               np.asarray(out_ref, np.float32), atol=5e-2)


def test_flash_pallas_kv_valid_len_masks_padding():
    """Ragged KV: positions beyond kv_valid_len must be invisible."""
    q, k, v = _qkv(32, 2, 2, 8, 8, seed=2)
    out_full = flash_attention(q[:, :16], k[:, :16], v[:, :16], q_chunk=16,
                               kv_chunk=16, impl="pallas")
    out_lim = flash_attention(q[:, :16], k, v, kv_valid_len=16, q_chunk=16,
                              kv_chunk=16, impl="pallas")
    np.testing.assert_allclose(np.asarray(out_lim), np.asarray(out_full),
                               atol=1e-5)


def test_flash_pallas_inside_jit_and_runtime_positions():
    q, k, v = _qkv(64, 4, 2, 16, 16, seed=3)
    positions = jnp.arange(64, dtype=jnp.int32)

    @jax.jit
    def f(q, k, v, positions):
        return flash_attention(q, k, v, positions=positions, q_chunk=32,
                               kv_chunk=32, impl="pallas")

    out = f(q, k, v, positions)
    ref = flash_attention(q, k, v, positions=positions, q_chunk=32,
                          kv_chunk=32, impl="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# decode kernels
# ---------------------------------------------------------------------------

def _ring_cache(b, length, kh, d, n_filled, seed=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    k_cache = jax.random.normal(ks[0], (b, length, kh, d))
    v_cache = jax.random.normal(ks[1], (b, length, kh, d))
    kpos = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32),
                            (b, length))
    kpos = jnp.where(kpos < n_filled, kpos, -1)  # unwritten slots
    return k_cache, v_cache, kpos


@pytest.mark.parametrize("b,length,kh,g,d,window", [
    (2, 24, 2, 2, 16, None),
    (2, 24, 2, 2, 16, 8),
    (1, 13, 1, 4, 8, None),   # odd ring length -> single-block fallback
    (3, 64, 2, 1, 32, 16),
])
def test_decode_pallas_matches_reference(b, length, kh, g, d, window):
    h = kh * g
    q = jax.random.normal(KEY, (b, 1, h, d))
    k_cache, v_cache, kpos = _ring_cache(b, length, kh, d, length - 3)
    qpos = jnp.full((b,), length - 4, jnp.int32)
    out_ref = decode_attention(q, k_cache, v_cache, kpos, qpos,
                               window=window, impl="jnp")
    out_pal = decode_attention(q, k_cache, v_cache, kpos, qpos,
                               window=window, impl="pallas")
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               atol=1e-5)


@pytest.mark.parametrize("window", [None, 8])
def test_decode_q8_pallas_vs_dequantize_then_attend(window):
    """Fused int8 decode == dequantize the cache, then bf16-path attend."""
    b, length, kh, g, d = 2, 32, 2, 2, 16
    h = kh * g
    q = jax.random.normal(KEY, (b, 1, h, d))
    k_cache, v_cache, kpos = _ring_cache(b, length, kh, d, length - 5)
    qpos = jnp.full((b,), length - 6, jnp.int32)
    k_codes, k_scale = quantize_kv_token(k_cache)
    v_codes, v_scale = quantize_kv_token(v_cache)

    out_pal = decode_attention_q8(q, k_codes, v_codes, k_scale, v_scale,
                                  kpos, qpos, window=window, impl="pallas")
    # oracle: materialize the dequantized cache, run the plain jnp path
    k_deq = k_codes.astype(jnp.float32) * \
        k_scale.astype(jnp.float32)[..., None]
    v_deq = v_codes.astype(jnp.float32) * \
        v_scale.astype(jnp.float32)[..., None]
    out_deq = decode_attention(q, k_deq, v_deq, kpos, qpos, window=window,
                               impl="jnp")
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_deq),
                               atol=1e-4)
    # and against the fused jnp reference (same wire math)
    out_ref = decode_attention_q8(q, k_codes, v_codes, k_scale, v_scale,
                                  kpos, qpos, window=window, impl="jnp")
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# dispatch + end-to-end
# ---------------------------------------------------------------------------

def test_pick_block_vmem_safe():
    from repro.kernels.decode_kernel import MAX_BLOCK, pick_block
    assert pick_block(1024) == 512           # largest aligned divisor
    assert pick_block(24) == 24
    assert pick_block(13) == 13              # odd-but-small: one block
    assert pick_block(3000) == 200           # aligned beats tiny pow2
    assert pick_block(5 * 499) == 499        # no aligned divisor <= cap
    assert pick_block(100003) is None        # big prime: no block
    for n in (13, 24, 1024, 3000, 32768):
        blk = pick_block(n)
        assert blk is not None and blk <= MAX_BLOCK and n % blk == 0


def test_decode_prime_length_falls_back_to_reference():
    """Cache lengths with no VMEM-safe block still run in interpret mode
    (one block spans the cache); on TPU they are refused, see
    test_compiled_kernels_refuse_instead_of_falling_back."""
    b, length, kh, g, d = 1, 2053, 1, 2, 8  # 2053 is prime > MAX_BLOCK
    q = jax.random.normal(KEY, (b, 1, kh * g, d))
    k_cache, v_cache, kpos = _ring_cache(b, length, kh, d, 10)
    qpos = jnp.full((b,), 9, jnp.int32)
    out_pal = decode_attention(q, k_cache, v_cache, kpos, qpos,
                               impl="pallas")
    out_ref = decode_attention(q, k_cache, v_cache, kpos, qpos, impl="jnp")
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               atol=1e-5)


def test_serve_step_cache_keyed_by_attn_impl(monkeypatch):
    """Flipping REPRO_ATTN_IMPL between generate() calls must not reuse
    the other backend's compiled step."""
    from repro.serve import decode as sd
    monkeypatch.setenv("REPRO_ATTN_IMPL", "jnp")
    impl_a = attention_ops.resolve_impl(None)
    monkeypatch.setenv("REPRO_ATTN_IMPL", "pallas")
    impl_b = attention_ops.resolve_impl(None)
    from repro.configs import get_config
    cfg = get_config("llama3_2_3b").reduced()
    step_a = sd._compiled_serve_step(cfg, None, impl_a)
    step_b = sd._compiled_serve_step(cfg, None, impl_b)
    assert step_a is not step_b
    assert sd._compiled_serve_step(cfg, None, impl_a) is step_a


def test_resolve_impl_env_and_kwarg(monkeypatch):
    monkeypatch.delenv("REPRO_ATTN_IMPL", raising=False)
    default = attention_ops.resolve_impl(None)
    assert default == ("pallas" if jax.default_backend() == "tpu"
                       else "jnp")
    monkeypatch.setenv("REPRO_ATTN_IMPL", "pallas")
    assert attention_ops.resolve_impl(None) == "pallas"
    assert attention_ops.resolve_impl("jnp") == "jnp"  # kwarg wins
    monkeypatch.setenv("REPRO_ATTN_IMPL", "nope")
    with pytest.raises(ValueError):
        attention_ops.resolve_impl(None)


@pytest.mark.parametrize("bits", [16, 8])
def test_env_forced_pallas_decode_matches_full_attention(monkeypatch, bits):
    """Ring-buffer decode through the kernels == full-sequence attention
    (the exact zero-call-site-churn path gqa_decode/serve take)."""
    d_model, h, kh, hd, s = 32, 4, 2, 8, 12
    params = init_attention_params(jax.random.PRNGKey(0), d_model, h, kh, hd)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, s, d_model))
    positions = jnp.arange(s)
    full = gqa_forward(params, x, n_heads=h, n_kv_heads=kh, head_dim=hd,
                       rope_theta=1e4, positions=positions)

    monkeypatch.setenv("REPRO_ATTN_IMPL", "pallas")
    cache = init_kv_cache(2, s, kh, hd,
                          jnp.float32 if bits == 16 else jnp.bfloat16,
                          bits=bits)
    outs = []
    for t in range(s):
        qpos = jnp.full((2,), t, jnp.int32)
        y, cache = gqa_decode(params, x[:, t:t + 1], cache, n_heads=h,
                              n_kv_heads=kh, head_dim=hd, rope_theta=1e4,
                              qpos=qpos)
        outs.append(y)
    step = jnp.concatenate(outs, axis=1)
    atol = 2e-4 if bits == 16 else 0.15  # int8 cache is lossy
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               atol=atol)


def _refusal(case):
    if case == "flash_chunk":  # chunk neither 128-aligned nor the whole seq
        q, k, v = _qkv(96, 4, 2, 16, 16)
        return lambda: flash_attention(q, k, v, q_chunk=32, kv_chunk=32,
                                       impl="pallas")
    if case == "decode_length":  # 1000 slots: no 128-aligned block divides
        q = jnp.ones((1, 1, 4, 8), jnp.float32)
        cache = jnp.ones((1, 1000, 2, 8), jnp.float32)
        kpos = jnp.zeros((1, 1000), jnp.int32)
        return lambda: decode_attention(q, cache, cache, kpos,
                                        jnp.zeros((1,), jnp.int32),
                                        impl="pallas")
    from repro.configs import get_config
    from repro.serve.engine import ServeEngine
    return lambda: ServeEngine(None, get_config("llama3_2_3b").reduced(),
                               n_slots=1, page_size=1, n_pages=5)


@pytest.mark.parametrize("case", ["flash_chunk", "decode_length",
                                  "engine_page_size"])
def test_compiled_kernels_refuse_instead_of_falling_back(monkeypatch, case):
    """On TPU a shape the compiled kernel cannot take raises; it never
    quietly runs the jnp reference."""
    monkeypatch.setenv("REPRO_ATTN_IMPL", "pallas")
    run = _refusal(case)
    monkeypatch.setattr(attention_ops, "_interpret", lambda: False)
    with pytest.raises(ValueError, match="impl='jnp'"):
        run()
