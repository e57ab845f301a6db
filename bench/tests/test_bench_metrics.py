"""Per-layer metric readers on synthetic traces: each reads its number
where there is something to read and returns nothing where there is
not (never 0 for a share of a roofline)."""
import types

import pytest

from conftest import ROOT, tiny_config

from bench.harness import serve_cell, spec, train_cell
from bench.harness import trace as T

PEAKS = spec.peaks("TPU v5 lite", ROOT)
MS = 1_000_000  # ns


def _req(plen, n_out):
    return types.SimpleNamespace(tokens=[1] * plen, out=[2] * n_out)


def _ctx(ops=(), modules=(), kernels=None, served=(), stats=None):
    run = types.SimpleNamespace(sizes=spec.sizes(tiny_config()),
                                model=spec.model_module(tiny_config()),
                                driver=serve_cell, peaks=PEAKS,
                                devices=[None],
                                cell=types.SimpleNamespace(
                                    params={"batch": 4},
                                    traffic={"seq_len": 24}))
    s = T.Summary(window=(0, 100 * MS), n_devices=1, ops=list(ops),
                  modules=list(modules), spans=[], kernels=kernels or {})
    counters = dict(served=list(served), stats=stats or {}, steps=10,
                    window_s=0.1)
    return T.Context(run=run, counters=counters, summary=s)


def _read(name, ctx):
    return spec.metric_module(name, ROOT).read(ctx)


@pytest.mark.parametrize("name", ["flash_attention_roofline",
                                  "paged_decode_roofline", "decode_tick_ms",
                                  "decode_mfu", "prefill_ms_per_request"])
def test_nothing_to_read_returns_nothing(name):
    assert _read(name, _ctx(served=[_req(5, 4)],
                            stats={"admitted": 1})) is None


def test_programs_by_name():
    mods = [T.Op(0, "jit_paged_step(3)", "jit_paged_step(3)", 0, 2 * MS),
            T.Op(0, "jit_paged_step(3)", "jit_paged_step(3)", 5 * MS,
                 9 * MS),
            T.Op(0, "jit__prefill(1)", "jit__prefill(1)", 10 * MS, 30 * MS),
            T.Op(0, "jit__insert_prefill_impl(2)",
                 "jit__insert_prefill_impl(2)", 30 * MS, 34 * MS)]
    ctx = _ctx(modules=mods, served=[_req(5, 4)], stats={"admitted": 2})
    assert _read("decode_tick_ms", ctx) == pytest.approx(3.0)
    assert _read("prefill_ms_per_request", ctx) == pytest.approx(12.0)
    mfu = _read("decode_mfu", ctx)
    assert 0.0 < mfu < 100.0


def test_kernel_rooflines_stay_under_peak():
    k = T.Kernel("_fwd_kernel",
                 [(256, 1), (1, 256), (2, 4, 256, 64), (2, 2, 256, 64)],
                 [(2, 4, 256, 64)])
    ops = [T.Op(0, "k.1", "jit_step", 0, 1 * MS)]
    share = _read("flash_attention_roofline", _ctx(ops=ops,
                                                   kernels={("jit_step", "k.1"): k}))
    assert 0.0 < share < 100.0
    pk = T.Kernel("_paged_kernel", [], [])
    ops = [T.Op(0, "p.1", "jit_paged_step", 0, 1 * MS)]
    share = _read("paged_decode_roofline", _ctx(
        ops=ops, kernels={("jit_paged_step", "p.1"): pk}, served=[_req(5, 4)]))
    assert 0.0 < share < 100.0


def test_idle_shares_and_mfu():
    ops = [T.Op(0, "fusion.1", "jit_step", 0, 25 * MS)]
    ctx = _ctx(ops=ops, served=[_req(5, 4)])
    assert _read("train.device_idle_share", ctx) == pytest.approx(75.0)
    assert _read("serve.device_idle_share", ctx) == pytest.approx(75.0)
    assert 0.0 < _read("serve_mfu", ctx) < 100.0
    ctx.run.driver = train_cell
    assert 0.0 < _read("train_mfu", ctx) < 100.0
