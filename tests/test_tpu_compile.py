"""Compile guards: every Pallas kernel of the main path, compiled for a
described (not attached) TPU v5e chip at tinyllava's published widths
(the paged decode kernel at llava-next-34b's serving widths too).

Interpret-mode parity tests cannot see the TPU tiling rules (block
shapes, dtype casts, vector layouts); the v5e compiler can, without a
chip.  Each case lowers one kernel with ``interpret=False`` against
shapes placed on a described device and asserts the compiled module
holds the Mosaic kernel (``tpu_custom_call``).  The topology is described
inside a fixture only: describing it loads the TPU library, which one
process may hold at a time.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.packing import packed_size
from repro.kernels import (decode_kernel, flash_kernel, nf_kernel,
                           rdfsq_kernel, wq_kernel)

CFG = get_config("tinyllava")
H, KH, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
G = H // KH
B, S = 8, 1024          # train/prefill batch and sequence of the smoke run
PAGE, N_PAGES, NPP = 16, 512, 64
# llava-next-34b as its offline serving cell runs it: 4 slots, a
# 256-page table bucket, pages of 16
BIG = get_config("llava-next-34b")
BIG_SLOTS, BIG_N_PAGES, BIG_NPP = 4, 1024, 256


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 topology, with the persistent
    compilation cache off (a TPU compile written here could not be read
    back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


def _flash(which):
    fn = {"fwd": flash_kernel.forward, "dq": flash_kernel.backward_dq,
          "dkv": flash_kernel.backward_dkv}[which]
    f = functools.partial(fn, window=None, block=128, interpret=False)
    q = ((B, H, S, D), jnp.bfloat16)
    k = ((B, KH, S, D), jnp.bfloat16)
    pos = [((S, 1), jnp.int32), ((1, S), jnp.int32)]
    if which == "fwd":
        return f, [q, k, k] + pos
    row = ((B, H, S, 1), jnp.float32)
    return f, [q, k, k, q, row, row, row] + pos


def _ring(bits):
    q = ((B, KH, G, D), jnp.bfloat16)
    pos = [((B, S), jnp.int32), ((B,), jnp.int32)]
    if bits == 16:
        cache = ((B, S, KH, D), jnp.bfloat16)
        return (functools.partial(decode_kernel.decode, window=None,
                                  block=512, interpret=False),
                [q, cache, cache] + pos)
    codes = ((B, S, KH, D), jnp.int8)
    scale = ((B, KH, S), jnp.float32)
    return (functools.partial(decode_kernel.decode_q8, window=None,
                              block=512, interpret=False),
            [q, codes, codes, scale, scale] + pos)


def _paged(bits, page=PAGE, cfg=CFG, b=B, n_pages=N_PAGES, npp=NPP):
    kh, d = cfg.n_kv_heads, cfg.head_dim
    q = ((b, kh, cfg.n_heads // kh, d), jnp.bfloat16)
    tail = [((n_pages, page), jnp.int32), ((b, npp), jnp.int32),
            ((b,), jnp.int32)]
    if bits == 16:
        pool = ((n_pages, page, kh, d), jnp.bfloat16)
        return (functools.partial(decode_kernel.decode_paged, window=None,
                                  interpret=False),
                [q, pool, pool] + tail)
    codes = ((n_pages, page, kh, d), jnp.int8)
    scale = ((n_pages, kh, page), jnp.float32)
    return (functools.partial(decode_kernel.decode_paged_q8, window=None,
                              interpret=False),
            [q, codes, codes, scale, scale] + tail)


def _rdfsq(which):
    # one connector activation per row, padded to the kernel's tile
    cols = -(-CFG.n_image_tokens * CFG.d_model // rdfsq_kernel.COLS) \
        * rdfsq_kernel.COLS
    stats = ((B, 2), jnp.float32)
    if which == "encode":
        return (functools.partial(rdfsq_kernel.quantize_pallas, bits=2,
                                  interpret=False),
                [((B, cols), jnp.float32), stats])
    return (functools.partial(rdfsq_kernel.dequantize_pallas, bits=2,
                              interpret=False),
            [((B, cols // 4), jnp.uint8), stats])


def _nf(which):
    nb, g = 64 * nf_kernel.BLOCKS_PER_TILE, 64
    book = ((16,), jnp.float32)
    if which == "encode":
        return (functools.partial(nf_kernel.quantize_pallas, bits=4,
                                  interpret=False),
                [((nb, g), jnp.float32), book])
    side = ((nb, 1), jnp.float32)
    return (functools.partial(nf_kernel.dequantize_pallas, bits=4, g=g,
                              interpret=False),
            [((nb, g // 2), jnp.uint8), side, side, book])


def _wq(m):
    d_in, d_out, group = CFG.d_model, CFG.d_ff, 128
    side = ((-(-d_in // group), d_out), jnp.float16)
    return (functools.partial(wq_kernel.matmul_pallas, bits=4, group=group,
                              d_in=d_in, interpret=False),
            [((m, d_in), jnp.bfloat16),
             ((packed_size(d_in, 4), d_out), jnp.uint8), side, side])


CASES = {
    "flash_fwd": lambda: _flash("fwd"),
    "flash_dq": lambda: _flash("dq"),
    "flash_dkv": lambda: _flash("dkv"),
    "decode_ring_bf16": lambda: _ring(16),
    "decode_ring_int8": lambda: _ring(8),
    "decode_paged_bf16": lambda: _paged(16),
    "decode_paged_int8": lambda: _paged(8),
    # odd pages compile too (decode_kernel.paged_compiles)
    "decode_paged_bf16_page5": lambda: _paged(16, 5),
    "decode_paged_int8_page5": lambda: _paged(8, 5),
    "decode_paged_bf16_llava34b": lambda: _paged(
        16, cfg=BIG, b=BIG_SLOTS, n_pages=BIG_N_PAGES, npp=BIG_NPP),
    "decode_paged_int8_llava34b": lambda: _paged(
        8, cfg=BIG, b=BIG_SLOTS, n_pages=BIG_N_PAGES, npp=BIG_NPP),
    "rdfsq2_encode": lambda: _rdfsq("encode"),
    "rdfsq2_decode": lambda: _rdfsq("decode"),
    "nf4_encode": lambda: _nf("encode"),
    "nf4_decode": lambda: _nf("decode"),
    "wq_int4_m8": lambda: _wq(8),
    "wq_int4_m256": lambda: _wq(256),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, case
