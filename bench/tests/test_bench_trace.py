"""The trace reduction: idle share, kernel time by the kernel name the
compiled HLO records, idle gaps named by host spans, roofline share and
its binding bound."""
import base64
import os

import pytest

from conftest import DATA, ROOT

from bench.harness import flops as F
from bench.harness import spec
from bench.harness import trace as T


class Ev:
    def __init__(self, name, start, end, **stats):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start
        self.stats = list(stats.items())


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def _body(fn):
    """A Mosaic body's strings as the compiler lays them out: attribute
    names, source files, helper functions, then the kernel function."""
    raw = (b"MLIR\x00stable_mosaic\x00sym_name\x00src/repro/models/"
           b"transformer.py\x00_attend_heads\x00value\x00" + fn.encode()
           + b"\x00main\x00")
    return base64.b64encode(raw).decode()


HLO = "\n".join([
    "HloModule jit_step",
    '  %k.1 = (bf16[2,4,256,64]{3,2,1,0}) custom-call(%a, %b, %c), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{s32[256,1]{1,0}, s32[1,256]{1,0}, bf16[2,4,256,64]{3,2,1,0}, '
    'bf16[2,2,256,64]{3,2,1,0}, bf16[2,2,256,64]{3,2,1,0}}, '
    'backend_config={"custom_call_config":{"body":"'
    + _body("_fwd_kernel") + '"}}',
    "  %fusion.3 = bf16[2,256]{1,0} fusion(%x), kind=kLoop",
])

US = 1000  # ns


def _profile():
    host = Plane("/host:CPU", [Line("python", [
        Ev(T.WINDOW_SPAN, 0, 100 * US),
        Ev("bench.engine_step", 10 * US, 40 * US),
        Ev("bench.prefill", 12 * US, 20 * US),
        Ev("bench.engine_step", 65 * US, 99 * US),
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Ops", [
            Ev("fusion.3", -5 * US, 5 * US, hlo_module="jit_step"),
            Ev("k.1", 20 * US, 30 * US, hlo_module="jit_step"),
            Ev("fusion.3", 25 * US, 50 * US, hlo_module="jit_step"),
            Ev("fusion.3", 70 * US, 80 * US, hlo_module="jit_step"),
            # the same instruction name in another program is not the kernel
            Ev("k.1", 72 * US, 75 * US, hlo_module="jit_paged_step"),
        ]),
        Line("XLA Modules", [Ev("jit_step(1)", 20 * US, 50 * US),
                             Ev("jit_paged_step(2)", 70 * US, 80 * US)]),
    ])
    other = Plane("/device:TPU:1", [Line("XLA Ops", [
        Ev("fusion.3", 0, 100 * US, hlo_module="jit_step")])])
    return Profile([host, dev, other])


@pytest.fixture
def summary():
    return T.reduce_profile(_profile(), [HLO], n_devices=1)


def test_window_and_idle_share(summary):
    assert summary.window_s == pytest.approx(100e-6)
    # busy: [0,5] + [20,50] + [70,80] = 45 of 100 us; device 1 is not used
    assert summary.busy_s == pytest.approx(45e-6)
    assert summary.idle_share() == pytest.approx(0.55)


def test_kernels_from_hlo():
    k = T.kernels_in_hlo(HLO)
    assert list(k) == [("jit_step", "k.1")]
    fwd = k[("jit_step", "k.1")]
    assert fwd.kernel == "_fwd_kernel"
    assert fwd.operands[2:] == [(2, 4, 256, 64), (2, 2, 256, 64),
                                (2, 2, 256, 64)]
    assert fwd.results == [(2, 4, 256, 64)]


def test_kernels_from_compiled_hlo():
    """The kernels of the train step and the decode tick as the v5e
    compiler emits them (``data/compile_kernels_hlo.py``)."""
    with open(os.path.join(DATA, "v5e_kernels.hlo.txt")) as f:
        text = f.read()
    found = {}
    for part in text.split("HloModule ")[1:]:
        found.update(T.kernels_in_hlo("HloModule " + part))
    names = sorted((m, k.kernel) for (m, _), k in found.items())
    assert names == [("jit_paged_step", "_paged_kernel"),
                     ("jit_train_step", "_dkv_kernel"),
                     ("jit_train_step", "_dq_kernel"),
                     ("jit_train_step", "_fwd_kernel"),
                     ("jit_train_step", "_fwd_kernel")]
    for (m, _), k in found.items():
        if m == "jit_train_step":
            # (q positions, k positions, q, k, ...) at batch 2 x 1024
            assert k.operands[2] == (2, 20, 1024, 64)
            assert k.operands[3] == (2, 5, 1024, 64)


def test_ops_without_a_program_take_the_enclosing_one():
    prof = _profile()
    for ev in prof.planes[1].lines[0].events:
        ev.stats = []
    s = T.reduce_profile(prof, [HLO], n_devices=1)
    calls = s.kernel_ops("_fwd_kernel")
    assert [(op.start, op.module) for op, _ in calls] == [(20 * US,
                                                           "jit_step")]
    # the op at 72-75 us lies in the paged step's run, not the kernel's
    assert [o.module for o in s.ops if o.name == "k.1"] == [
        "jit_step", "jit_paged_step"]


def test_kernel_time_by_name(summary):
    calls = summary.kernel_ops("_fwd_kernel")
    assert [(op.start, op.end) for op, _ in calls] == [(20 * US, 30 * US)]
    assert summary.kernel_ops("_paged_kernel") == []
    runs = summary.module_runs("paged_step")
    assert [(m.start, m.end) for m in runs] == [(70 * US, 80 * US)]


def test_idle_gaps_named_by_host_spans(summary):
    gaps = summary.idle_gaps()
    assert gaps == [(5 * US, 20 * US), (50 * US, 70 * US),
                    (80 * US, 100 * US)]
    names = {summary.span_at((s + e) // 2) for s, e in gaps}
    # (5+20)/2 = 12.5 us lies in the prefill span inside the engine step
    assert names == {"bench.prefill", "no host span", "bench.engine_step"}
    b = summary.breakdown()
    assert b["idle_gaps"][0] == ["no host span", pytest.approx(20e-6)]
    assert b["device_ops"][0][0] == "jit_step/fusion.3"
    assert ["_fwd_kernel", pytest.approx(10e-6)] in \
        b["device_ops"]


def test_roofline_share_and_bound(summary):
    peaks = spec.peaks("TPU v5 lite", ROOT)
    (op, k), = summary.kernel_ops("_fwd_kernel")
    b, h, s, d = k.operands[2]
    w = F.flash_fwd(b, h, k.operands[3][1], s, d)
    r = F.roofline_share(w["flops"], w["bytes"], (op.end - op.start) / 1e9,
                         peaks)
    t_c = w["flops"] / 197e12
    t_m = w["bytes"] / 819e9
    assert r["bound"] == ("compute" if t_c > t_m else "memory")
    assert r["share"] == pytest.approx(100 * max(t_c, t_m) / 10e-6)



@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on one TPU v5e chip by ``data/record_trace.py``:
    five runs of a jitted step holding the flash forward kernel, each in
    a ``bench.step`` span, with 2 ms host sleeps (``bench.host_wait``)
    between them, all inside ``bench.window``."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "v5e_trace.hlo.txt")) as f:
        hlo = f.read()
    pd = ProfileData.from_file(os.path.join(DATA, "v5e_trace.xplane.pb"))
    return T.reduce_profile(pd, [hlo], n_devices=1)


def test_recorded_trace_kernels_and_programs(recorded):
    # the device's clock runs about 1.05 ms ahead of the host's in this
    # trace, so the first of the five runs ends before the window opens
    calls = recorded.kernel_ops("_fwd_kernel")
    assert len(calls) == 4
    assert {op.module for op, _ in calls} == {"jit_step"}
    assert all(k.operands[2] == (2, 4, 256, 64) for _, k in calls)
    assert len(recorded.module_runs("jit_step")) == 4
    assert recorded.breakdown()["device_ops"][0][0] == "_fwd_kernel"


def test_recorded_trace_idle_share_and_gaps(recorded):
    # five steps of about 26 us of device work in a window of some ms
    assert 0 < recorded.busy_s < 0.01 * recorded.window_s
    assert recorded.idle_share() > 0.99
    names = [n for n, _ in recorded.breakdown()["idle_gaps"][:4]]
    assert set(names) <= {"bench.host_wait", "bench.step"}
    assert "bench.host_wait" in names


def test_recorded_trace_roofline_share(recorded):
    peaks = spec.peaks("TPU v5 lite", ROOT)
    for op, k in recorded.kernel_ops("_fwd_kernel"):
        b, h, s, d = k.operands[2]
        w = F.flash_fwd(b, h, k.operands[3][1], s, d)
        r = F.roofline_share(w["flops"], w["bytes"],
                             (op.end - op.start) / 1e9, peaks)
        assert 0 < r["share"] <= 100
