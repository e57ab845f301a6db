"""Wire codec coverage: pallas-vs-jnp dispatch (mirroring
test_attention_pallas.py), quantized_ship-vs-roundtrip parity for every
registered method, and pack/unpack properties for odd bit widths."""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import packing
from repro.core import quantizers as Q
from repro.core.quantizers import QuantConfig
from repro.core.split import SplitConfig, compressor_roundtrip, \
    quantized_ship, wire_payload
from repro.launch.mesh import make_mesh

KEY = jax.random.PRNGKey(0)


def _x(shape, dtype=jnp.float32, seed=0, scale=3.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape) * scale
            ).astype(dtype)


# ---------------------------------------------------------------------------
# packing: odd widths ride in their storage slots
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [1, 2, 3, 4, 5, 6, 7, 8])
@pytest.mark.parametrize("n", [1, 7, 64, 257])
def test_pack_unpack_roundtrip_all_widths(bits, n):
    codes = jax.random.randint(jax.random.PRNGKey(bits * 131 + n), (n,), 0,
                               2 ** bits).astype(jnp.uint8)
    words = packing.pack_bits(codes, bits)
    assert words.shape == (packing.packed_size(n, bits),)
    assert words.dtype == jnp.uint8
    out = packing.unpack_bits(words, bits, n)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(codes))


@pytest.mark.parametrize("bits,slot", [(3, 4), (5, 8), (6, 8), (7, 8)])
def test_odd_widths_pack_exactly(bits, slot):
    """Odd widths cost exactly ceil(n*b/8) on the wire — the pow2 slot
    only survives as the fused kernels' storage geometry."""
    assert packing.storage_bits(bits) == slot
    n = 123
    exact = -(-(n * bits) // 8)
    slotted = -(-n // (8 // slot))
    assert packing.packed_size(n, bits) == exact
    assert exact < slotted  # the bitstream strictly beats slot padding


@pytest.mark.parametrize("method", ["rdfsq", "nf", "fsq"])
@pytest.mark.parametrize("bits", [3, 5, 6, 7])
def test_quantizer_odd_widths_decode_encode(method, bits):
    """Odd widths flow through encode/decode/roundtrip end to end."""
    cfg = QuantConfig(method=method, bits=bits)
    x = _x((3, 129))
    x_hat = Q.decode(cfg, Q.encode(cfg, x))
    rt, _ = Q.roundtrip(cfg, x)
    np.testing.assert_allclose(np.asarray(x_hat), np.asarray(rt),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# pallas codec backend vs the jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 700), (8, 1024), (3, 257), (2, 16, 64)])
@pytest.mark.parametrize("bits", [2, 4])
def test_rdfsq_pallas_decode_matches_roundtrip(shape, bits):
    """decode(encode(x)) == roundtrip(x)[0] must hold per backend."""
    cfg = QuantConfig(method="rdfsq", bits=bits)
    x = _x(shape)
    payload = Q.encode(cfg, x, impl="pallas")
    assert payload.meta["impl"] == "pallas"
    x_hat = Q.decode(cfg, payload)
    rt, _ = Q.roundtrip(cfg, x)
    np.testing.assert_allclose(np.asarray(x_hat), np.asarray(rt),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("double_quant", [False, True])
def test_nf_pallas_decode_matches_roundtrip(bits, double_quant):
    cfg = QuantConfig(method="nf", bits=bits, double_quant=double_quant)
    x = _x((4, 700))
    payload = Q.encode(cfg, x, impl="pallas")
    assert payload.meta["impl"] == "pallas"
    x_hat = Q.decode(cfg, payload)
    rt, _ = Q.roundtrip(cfg, x)
    # the kernel emits fp16 block ranges before double-quant; same
    # tolerance class as test_kernels.test_nf_kernel_matches_core_quantizer
    np.testing.assert_allclose(np.asarray(x_hat), np.asarray(rt),
                               atol=0.1, rtol=5e-2)


def test_pallas_payload_bytes_match_jnp():
    """Same wire cost when rows pack cleanly (shape divisible)."""
    x = _x((4, 1024))
    for method, atol in (("rdfsq", 0), ("nf", 0)):
        cfg = QuantConfig(method=method, bits=2)
        bj = Q.encode(cfg, x, impl="jnp").wire_bytes()
        bp = Q.encode(cfg, x, impl="pallas").wire_bytes()
        assert bj == bp, (method, bj, bp)


def test_quant_env_dispatch(monkeypatch):
    """REPRO_QUANT_IMPL flips the backend with zero call-site churn."""
    cfg = QuantConfig(method="rdfsq", bits=2)
    x = _x((2, 256))
    monkeypatch.setenv("REPRO_QUANT_IMPL", "pallas")
    assert Q.resolve_impl(None) == "pallas"
    p = Q.encode(cfg, x)
    assert p.meta["impl"] == "pallas"
    # wire_payload (the Table-4 accounting entry point) picks it up too
    split = SplitConfig(quant=cfg, learnable_codec=False)
    assert wire_payload(split, None, x).meta["impl"] == "pallas"
    monkeypatch.setenv("REPRO_QUANT_IMPL", "jnp")
    assert Q.encode(cfg, x).meta["impl"] == "jnp"
    # a pallas payload still decodes with the pallas backend (the tag
    # travels with the payload, not the environment)
    x_hat = Q.decode(cfg, p)
    rt, _ = Q.roundtrip(cfg, x)
    np.testing.assert_allclose(np.asarray(x_hat), np.asarray(rt),
                               atol=1e-5, rtol=1e-5)
    monkeypatch.setenv("REPRO_QUANT_IMPL", "tpu-magic")
    with pytest.raises(ValueError):
        Q.resolve_impl(None)
    with pytest.raises(ValueError):
        Q.resolve_impl("cuda")


def test_stage_quants_length_validated():
    ok = SplitConfig(n_stages=4,
                     stage_quants=(QuantConfig(), QuantConfig(),
                                   QuantConfig(method="nf")))
    assert len(ok.resolve_stage_quants()) == 3
    assert SplitConfig(n_stages=3).resolve_stage_quants() == \
        (SplitConfig().quant,) * 2
    with pytest.raises(ValueError):
        SplitConfig(n_stages=4, stage_quants=(QuantConfig(),)
                    ).resolve_stage_quants()


def test_unsupported_configs_fall_back_to_jnp():
    x = _x((2, 64, 8))
    p = Q.encode(QuantConfig(method="rdfsq", bits=2, stats_axis="tensor"),
                 x, impl="pallas")
    assert p.meta["impl"] == "jnp"  # kernel stats are per sample row
    p = Q.encode(QuantConfig(method="nf", bits=2, block_size=3), x,
                 impl="pallas")
    assert p.meta["impl"] == "jnp"  # rows would straddle packed words


# ---------------------------------------------------------------------------
# the wire itself: quantized_ship == compressor_roundtrip numerics
# ---------------------------------------------------------------------------

def _ship_self(qcfg, x):
    """quantized_ship under the identity permutation on a 1-pod mesh."""
    mesh = make_mesh((1,), ("pod",))

    @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
             check_vma=False)
    def ship(x):
        return quantized_ship(qcfg, x, "pod", ((0, 0),))

    with mesh:
        return jax.jit(ship)(x)


@pytest.mark.parametrize("method", sorted(Q.methods()))
def test_quantized_ship_matches_compressor_roundtrip(method):
    """The real wire (encode -> ppermute -> decode) reproduces the
    in-graph STE roundtrip for every registered method."""
    qcfg = QuantConfig(method=method, bits=2)
    split = SplitConfig(quant=qcfg, learnable_codec=False)
    x = _x((4, 8, 64))
    y_wire = _ship_self(qcfg, x)
    y_graph, _ = compressor_roundtrip(None, split, x)
    np.testing.assert_allclose(np.asarray(y_wire), np.asarray(y_graph),
                               atol=1e-6, rtol=1e-6)


def test_quantized_ship_pallas_backend(monkeypatch):
    """The ship picks the pallas codecs up through the env var."""
    monkeypatch.setenv("REPRO_QUANT_IMPL", "pallas")
    qcfg = QuantConfig(method="rdfsq", bits=2)
    x = _x((4, 8, 64))
    y_wire = _ship_self(qcfg, x)
    rt, _ = Q.roundtrip(qcfg, x)
    np.testing.assert_allclose(np.asarray(y_wire), np.asarray(rt),
                               atol=1e-5, rtol=1e-5)


def test_ship_wire_dtype_pinned():
    """The lowered ship must permute the packed uint8/uint16 words, not a
    widened float — XLA likes to reorder converts across collectives."""
    import re
    qcfg = QuantConfig(method="identity")
    mesh = make_mesh((1,), ("pod",))

    @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
             check_vma=False)
    def ship(x):
        return quantized_ship(qcfg, x, "pod", ((0, 0),))

    x = _x((4, 64))  # f32 -> bf16 on the wire -> f32 back
    with mesh:
        hlo = jax.jit(ship).lower(x).compile().as_text()
    cps = re.findall(r"(\S+\[[0-9,]*\])\S*\s+collective-permute\(", hlo)
    assert cps, hlo
    for shape in cps:
        assert shape.startswith(("u16", "bf16")), cps