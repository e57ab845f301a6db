"""Operation and byte counts against hand counts at tinyllava's widths."""
import os

import pytest

from conftest import ROOT

from bench.harness import flops as F
from bench.harness import spec

C = spec.sizes(spec.load_json(os.path.join(ROOT, "bench", "configs",
                                           "tinyllava.json")))
PEAKS = spec.peaks("TPU v5 lite", ROOT)


def test_weights_by_hand():
    # attention 1280*64*(2*20 + 2*5) = 4,096,000; SwiGLU 3*1280*3456
    assert F.layer_weights(C) == 4_096_000 + 13_271_040
    assert F.token_weights(C) == 16 * 17_367_040 + 2 * 1280 ** 2 \
        + 1280 * 32000
    assert F.connector_weights(C) == 1152 * 1280 + 1280 * 1280


def test_train_step_by_hand():
    per_row = (6 * 1024 * F.token_weights(C)
               + 6 * 729 * F.connector_weights(C)
               + 3 * 2 * 1024 ** 2 * 64 * 20 * 16)
    assert F.train_step_flops(C, 32, 1024) == 32 * per_row
    # about 68 TFLOP a step of 32 x 1024 tokens
    assert 6.5e13 < F.train_step_flops(C, 32, 1024) < 7.0e13


def test_serve_by_hand():
    t = 729 + 48
    assert F.prefill_flops(C, 48) == (2 * t * F.token_weights(C)
                                      + 2 * 729 * F.connector_weights(C)
                                      + 2 * t * t * 64 * 20 * 16)
    assert F.decode_flops(C, 800) == 2 * F.token_weights(C) \
        + 4 * 800 * 64 * 20 * 16


def test_flash_counts_by_hand():
    fwd = F.flash_fwd(8, 20, 5, 1024, 64)
    assert fwd["flops"] == 2 * 8 * 20 * 1024 ** 2 * 64
    assert fwd["bytes"] == 2 * 8 * 1024 * 64 * (40 + 10) + 8 * 8 * 20 * 1024
    assert F.flash_dq(8, 20, 5, 1024, 64)["flops"] == 1.5 * fwd["flops"]
    assert F.flash_dkv(8, 20, 5, 1024, 64)["flops"] == 2 * fwd["flops"]


def test_paged_decode_by_hand():
    w = F.paged_decode(C, 1000)
    assert w["bytes"] == 2 * 1000 * 5 * 64 * 2 + 2 * 2 * 20 * 64 + 4 * 1000
    assert w["flops"] == 4 * 1000 * 64 * 20


@pytest.mark.parametrize("flops,byts,bound", [(197e12, 1.0, "compute"),
                                              (1.0, 819e9, "memory")])
def test_roofline_share(flops, byts, bound):
    r = F.roofline_share(flops, byts, 2.0, PEAKS)
    assert r["bound"] == bound and r["share"] == pytest.approx(50.0)
