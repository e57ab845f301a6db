"""The serving engine's spans read against the device trace: idle time
split by overlap between the innermost engine spans, host time per step,
the host-device offset, and the readers of the engine's counters and
request times.  Each reader returns nothing where the program wrote
nothing to read (a program without the spans, counters or times)."""
import os
import types

import pytest

from conftest import DATA, ROOT

from bench.harness import engine_spans as S
from bench.harness import trace as T
from bench.harness import spec

MS = 1_000_000  # ns


def _summary(spans=(), ops=(), modules=()):
    return T.Summary(window=(0, 100 * MS), n_devices=1,
                     ops=[T.Op(0, "fusion.1", "jit_paged_step", s * MS,
                               e * MS) for s, e in ops],
                     modules=list(modules),
                     spans=[(n, int(s * MS), int(e * MS))
                            for n, s, e in spans],
                     kernels={})


def _ctx(summary=None, served=(), stats=None, closed=1.0):
    counters = dict(served=list(served), stats=stats or {}, closed=closed)
    return T.Context(run=None, counters=counters,
                     summary=summary or _summary())


def _read(name, ctx):
    return spec.metric_module(name, ROOT).read(ctx)


# Device busy 0-10 and 40-70 ms, then idle to the close at 100 ms.  One
# engine step 5-60 ms: inputs 5-20, fetch 20-35, its own time 35-40
# (idle 10-40 is split 10 inputs, 15 fetch, 5 step).  The benchmark waits
# for an arrival 75-95 ms: idle there, and 70-75, 95-100, goes to neither.
STEP = [("bench.window", 0, 100), ("bench.engine_step", 4, 61),
        ("engine.step", 5, 60), ("engine.tick.inputs", 5, 20),
        ("engine.tick.fetch", 20, 35), ("bench.wait_arrival", 75, 95)]
BUSY = [(0, 10), (40, 70)]


def test_gap_split_by_overlap_and_outside_engine_spans():
    s = _summary(STEP, BUSY)
    assert s.idle_share() == pytest.approx(0.6)
    idle = S.idle_by_span(s)
    assert idle == {"engine.tick.inputs": pytest.approx(0.010),
                    "engine.tick.fetch": pytest.approx(0.015),
                    "engine.step": pytest.approx(0.005)}
    ctx = _ctx(s)
    assert _read("serve.idle_fetch_share", ctx) == pytest.approx(15.0)
    assert _read("serve.idle_host_share", ctx) == pytest.approx(15.0)


def test_innermost_names_each_piece():
    spans = [("engine.step", 0, 10), ("engine.admit", 1, 2),
             ("engine.tick.fetch", 4, 8), ("engine.step", 12, 14)]
    assert S.innermost(spans) == [
        (0, 1, "engine.step"), (1, 2, "engine.admit"), (2, 4, "engine.step"),
        (4, 8, "engine.tick.fetch"), (8, 10, "engine.step"),
        (12, 14, "engine.step")]


def _run(start, end):
    return T.Op(0, "jit_paged_step(4)", "jit_paged_step(4)", int(start * MS),
                int(end * MS))


def test_host_ms_per_step():
    spans = STEP + [("engine.step", 62, 80), ("engine.prefill.fetch", 70, 78),
                    ("engine.step", 90, 120)]  # the last ends after the close
    s = _summary(spans, BUSY)
    # (55 - 15) and (18 - 8) ms
    assert S.host_ms_per_step(s) == pytest.approx(25.0)
    assert _read("engine.host_ms_per_step", _ctx(s)) == pytest.approx(25.0)


def test_clock_offset_bounds():
    """Four ticks (launch, fetch), the device's stamps 0.5 ms early: its
    runs truly at 18.3-35.6, 50.6-58 and 85.1-94.5 ms read 0.5 ms less.
    The third tick's run is missing from the trace and is skipped."""
    spans = [("engine.tick.launch", 18, 19), ("engine.tick.fetch", 19, 36),
             ("engine.tick.launch", 50, 51), ("engine.tick.fetch", 51, 58.2),
             ("engine.tick.launch", 70, 71), ("engine.tick.fetch", 71, 80),
             ("engine.tick.launch", 85, 86), ("engine.tick.fetch", 86, 95)]
    runs = [_run(17.8, 35.1), _run(50.1, 57.5), _run(84.6, 94.0)]
    # (run end - fetch end, run start - launch start) for each tick
    got = S.clock_offsets_ms(_summary(spans, modules=runs))
    assert got == [pytest.approx((-0.9, -0.2)), pytest.approx((-0.7, 0.1)),
                   pytest.approx((-1.0, -0.4))]
    assert S.clock_offsets_ms(_summary(spans)) == []


@pytest.mark.parametrize("name", ["serve.idle_fetch_share",
                                  "serve.idle_host_share",
                                  "engine.host_ms_per_step"])
def test_no_engine_spans_reads_nothing(name):
    spans = [n for n in STEP if not n[0].startswith("engine.")]
    assert _read(name, _ctx(_summary(spans, BUSY))) is None


def _req(arrival, submit, admit):
    return types.SimpleNamespace(arrival_time=arrival, submit_time=submit,
                                 admit_time=admit)


def test_request_time_percentiles():
    # ten requests: late by 0..9 ms, admitted 10 x that later; the last
    # never admitted, so its wait runs to the close
    served = [_req(1.0, 1.0 + i / 1e3, 1.0 + i / 1e2) for i in range(9)]
    served.append(_req(1.0, 1.009, None))
    ctx = _ctx(served=served, closed=3.0)
    assert _read("engine.submit_late_p90_ms", ctx) == pytest.approx(8.1)
    assert _read("engine.queue_wait_p90_ms", ctx) == pytest.approx(
        80 + 0.1 * (2000 - 80))


def test_counters():
    ctx = _ctx(stats=dict(prefill_positions=4096, prefill_real_positions=3072,
                          compiles=0))
    assert _read("prefill_pad_share", ctx) == pytest.approx(25.0)
    assert _read("engine.compiles_in_window", ctx) == 0


@pytest.mark.parametrize("name,ctx", [
    ("engine.submit_late_p90_ms", _ctx()),
    ("engine.queue_wait_p90_ms", _ctx()),
    # a program whose requests carry no submit or admit time
    ("engine.submit_late_p90_ms", _ctx(served=[types.SimpleNamespace(
        arrival_time=1.0)])),
    ("engine.queue_wait_p90_ms", _ctx(served=[types.SimpleNamespace(
        arrival_time=1.0)])),
    # and whose stats have no prefill or compile counters
    ("prefill_pad_share", _ctx(stats=dict(admitted=3))),
    ("prefill_pad_share", _ctx(stats=dict(prefill_positions=0,
                                          prefill_real_positions=0))),
    ("engine.compiles_in_window", _ctx(stats=dict(admitted=3))),
])
def test_nothing_to_read_returns_nothing(name, ctx):
    assert _read(name, ctx) is None


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on one TPU v5e chip by
    ``data/record_engine_trace.py``: a few engine steps of the steady
    serving cell (one prefill, then decode ticks) inside
    ``bench.window``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(os.path.join(DATA,
                                            "v5e_engine_trace.xplane.pb"))
    return T.reduce_profile(pd, [], n_devices=1)


def test_recorded_engine_trace(recorded):
    names = {n for n, _, _ in S.engine_spans(recorded)}
    assert names == {"engine.step", "engine.admit", "engine.prefill.inputs",
                     "engine.wire", "engine.prefill.launch",
                     "engine.prefill.fetch", "engine.tick.inputs",
                     "engine.tick.launch", "engine.tick.fetch",
                     "engine.pick", "engine.emit"}
    idle = S.idle_by_span(recorded)
    shares = sum(idle.values()) / recorded.window_s
    assert 0 < shares <= recorded.idle_share() + 1e-9
    # the host waits in the fetch for each tick program it launched
    assert idle["engine.tick.fetch"] > 0
    assert len(recorded.module_runs("paged_step")) >= 2
    # one offset of a few ms between the clocks fits every tick
    offsets = S.clock_offsets_ms(recorded)
    assert len(offsets) >= 2
    assert -5.0 < max(lo for lo, _ in offsets) <= min(
        hi for _, hi in offsets) < 1.0
    assert 0 < S.host_ms_per_step(recorded) < 1000
