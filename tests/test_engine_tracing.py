"""The serving engine's host spans, prefill counters, request times and
compile count: a tiny split-serve engine runs one wave of three requests
(padded to four prefill rows) under the JAX profiler on the CPU, and the
recorded ``.xplane.pb`` is read back with ``ProfileData``."""
import glob
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tf
from repro.serve.engine import ServeEngine

PROMPTS = (5, 7, 9)
MAX_NEW = (2, 3, 4)
SLOTS, PAGE = 4, 8

# span -> the stats it carries; every span but engine.step lies inside one
SPANS = {
    "engine.step": (),
    "engine.admit": ("admitted",),
    "engine.prefill.inputs": ("rows", "positions"),
    "engine.wire": ("wire_bytes",),
    "engine.prefill.launch": (),
    "engine.prefill.fetch": ("bytes",),
    "engine.tick.inputs": ("active", "npp"),
    "engine.tick.launch": (),
    "engine.tick.fetch": (),
    "engine.pick": (),
    "engine.emit": (),
}


def _engine():
    cfg = get_config("tinyllava").reduced()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    longest = cfg.n_image_tokens + max(PROMPTS) + max(MAX_NEW) + 2 * PAGE
    n_pages = 1 + SLOTS * -(-longest // PAGE)
    eng = ServeEngine(params, cfg, n_slots=SLOTS, page_size=PAGE,
                      n_pages=n_pages, split_wire=cfg.split.quant)
    return eng, cfg


def _submit_wave(eng, cfg, seed=0, longer=0):
    """Three requests; the last decodes ``longer`` tokens more."""
    rng = np.random.default_rng(seed)
    img = rng.normal(size=(cfg.n_image_tokens, cfg.d_vision)).astype(
        np.float32)
    new = MAX_NEW[:-1] + (MAX_NEW[-1] + longer,)
    return [eng.submit(list(rng.integers(1, cfg.vocab_size, p)), max_new=n,
                       image_embeds=img, arrival_time=time.perf_counter())
            for p, n in zip(PROMPTS, new)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The engine, its stats after one wave, and the host events of that
    wave's trace as {name: [(start_ns, end_ns, {stat: value})]}."""
    from jax.profiler import ProfileData

    eng, cfg = _engine()
    rids = _submit_wave(eng, cfg)
    out = str(tmp_path_factory.mktemp("engine_trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    events.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.end_ns), dict(e.stats)))
    return dict(engine=eng, cfg=cfg, rids=rids, events=events,
                stats=dict(eng.stats))


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_nested_with_stats(traced, name):
    events = traced["events"]
    assert name in events
    steps = events["engine.step"]
    for s, e, stats in events[name]:
        assert set(SPANS[name]) <= set(stats), (name, stats)
        if name != "engine.step":
            assert sum(1 for a, b, _ in steps if a <= s and e <= b) == 1


def test_phases_do_not_overlap(traced):
    """The phases of a step follow each other: none opens inside
    another."""
    phases = sorted((s, e) for name, evs in traced["events"].items()
                    if name != "engine.step" for s, e, _ in evs)
    for (_, e0), (s1, _) in zip(phases, phases[1:]):
        assert e0 <= s1


def test_span_stats_and_counts(traced):
    st, events = traced["stats"], traced["events"]
    assert st["prefill_batches"] == 1 and st["decode_ticks"] == max(
        MAX_NEW) - 1
    assert len(events["engine.prefill.fetch"]) == st["prefill_batches"]
    assert len(events["engine.tick.fetch"]) == st["decode_ticks"]
    assert len(events["engine.pick"]) == st["prefill_batches"] + \
        st["decode_ticks"]
    assert sum(d["admitted"] for _, _, d in events["engine.admit"]) == 3
    (_, _, inputs), = events["engine.prefill.inputs"]
    assert (inputs["rows"], inputs["positions"]) == (
        st["prefill_rows"], st["prefill_positions"])
    (_, _, wire), = events["engine.wire"]
    assert wire["wire_bytes"] == st["wire_bytes"] > 0
    (_, _, fetch), = events["engine.prefill.fetch"]
    assert fetch["bytes"] == st["prefill_fetch_bytes"] > 0
    actives = [d["active"] for _, _, d in events["engine.tick.inputs"]]
    assert actives == sorted(actives, reverse=True) and actives[0] == 3


def test_prefill_position_counters_by_hand(traced):
    """Three requests are padded to four rows; the longest, 16 image
    tokens and a prompt of 9, takes 25 positions, ceil(25 / 8) = 4
    pages, a power of two already, so each row is 32 positions."""
    st, n_img = traced["stats"], traced["cfg"].n_image_tokens
    assert n_img == 16
    assert st["prefill_rows"] == 4
    assert st["prefill_positions"] == 4 * 32
    assert st["prefill_real_positions"] == 3 * 16 + 5 + 7 + 9


def test_prefill_fetch_bytes_by_hand(traced):
    """The prefill copies back one vocabulary row per prefill row: four
    rows of 512 logits, not four rows of 32 positions of them."""
    st, cfg = traced["stats"], traced["cfg"]
    itemsize = np.dtype(tf.cdtype(cfg)).itemsize
    assert cfg.vocab_size == 512
    assert st["prefill_fetch_bytes"] == 4 * 512 * itemsize


def test_request_times_in_order(traced):
    eng = traced["engine"]
    for rid in traced["rids"]:
        r = eng.request(rid)
        assert r.arrival_time <= r.submit_time <= r.admit_time \
            <= r.emit_times[0]


def test_compiles_count_new_shapes_only(traced):
    """A second wave of the same shapes lowers nothing; the same wave
    with one request decoding into a wider page table lowers the tick
    for it."""
    eng, cfg = traced["engine"], traced["cfg"]
    before = eng.stats["compiles"]
    assert before > 0
    _submit_wave(eng, cfg, seed=1)
    eng.run()
    assert eng.stats["compiles"] == before
    buckets = set(eng.stats["page_table_buckets"])
    _submit_wave(eng, cfg, seed=2, longer=2 * PAGE)
    eng.run()
    assert eng.stats["page_table_buckets"] > buckets
    assert eng.stats["compiles"] > before
