"""Continuous-batching serving engine: paged pool invariants, paged
kernel parity, engine-vs-generate token parity, donation, early stop."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import QuantConfig, SplitConfig
from repro.core.split import WireLink
from repro.kernels import attention_ops, attention_ref
from repro.models import transformer as tf
from repro.models.layers import attention as attn_mod
from repro.serve import decode as sd
from repro.serve.engine import ServeEngine
from repro.serve.pool import PagePool
from repro.serve.scheduler import Request, SlotScheduler
from repro.utils import compile_cache


def _params(cfg, seed=0):
    return tf.init_params(jax.random.PRNGKey(seed), cfg)


# ---------------------------------------------------------------------------
# page pool invariants
# ---------------------------------------------------------------------------

def test_page_pool_random_admit_retire_trace():
    rng = np.random.default_rng(0)
    pool = PagePool(33)
    live = {}
    next_rid = 0
    for _ in range(300):
        if live and rng.random() < 0.4:
            rid = int(rng.choice(list(live)))
            n = pool.free_owner(rid)
            assert n == len(live.pop(rid))
        else:
            n = int(rng.integers(1, 5))
            if pool.can_alloc(n):
                pages = pool.alloc(n, next_rid)
                assert len(set(pages)) == n
                # no page aliased by two live requests, trash never out
                for p in pages:
                    assert p != 0
                    for other in live.values():
                        assert p not in other
                live[next_rid] = pages
                next_rid += 1
        pool.check_invariants()
    for rid in list(live):
        pool.free_owner(rid)
    pool.check_invariants()
    assert pool.n_free == 32 and pool.n_live == 0


def test_page_pool_retired_pages_reusable_and_double_free_raises():
    pool = PagePool(5)
    a = pool.alloc(4, 1)
    pool.free_owner(1)
    b = pool.alloc(4, 2)
    assert set(a) == set(b)  # the whole pool cycles through
    with pytest.raises(RuntimeError):
        pool.alloc(1, 3)
    pool.free(b)
    with pytest.raises(RuntimeError):
        pool.free(b)


def test_scheduler_head_of_line_blocks_until_pages_free():
    pool = PagePool(5)  # 4 usable pages
    sched = SlotScheduler(2, pool, page_size=4)
    sched.submit(Request(rid=0, tokens=[1] * 10, max_new=6))   # 4 pages
    sched.submit(Request(rid=1, tokens=[1] * 2, max_new=2))    # 1 page
    admitted = sched.admit()
    assert [r.rid for r in admitted] == [0]
    # a free slot exists but the FIFO head (nothing) — rid 1 must wait for
    # pages, not jump past a fuller pool
    assert sched.admit() == []
    sched.retire(admitted[0], "length")
    assert [r.rid for r in sched.admit()] == [1]


# ---------------------------------------------------------------------------
# paged decode kernels vs refs
# ---------------------------------------------------------------------------

def _paged_fixture():
    rng = np.random.default_rng(0)
    p, pg, kh, g, d = 7, 8, 2, 2, 16
    pt = jnp.array([[1, 2, -1], [3, 4, 5], [-1, -1, -1]], jnp.int32)
    qpos = jnp.array([12, 21, -1], jnp.int32)
    pos = np.full((p, pg), -1, np.int32)
    pos[1] = np.arange(pg)
    pos[2] = np.arange(pg, 2 * pg)
    pos[2, 5:] = -1  # slot 0 holds 13 tokens
    for j in range(3):
        pos[3 + j] = np.arange(j * pg, (j + 1) * pg)
    qf = jnp.asarray(rng.normal(size=(3, kh, g, d)), jnp.float32) / np.sqrt(d)
    return rng, p, pg, kh, d, pt, qpos, jnp.asarray(pos), qf


@pytest.mark.parametrize("window", [None, 6])
def test_decode_paged_pallas_matches_ref(window):
    rng, p, pg, kh, d, pt, qpos, pos, qf = _paged_fixture()
    k = jnp.asarray(rng.normal(size=(p, pg, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(p, pg, kh, d)), jnp.float32)
    ref = attention_ref.decode_attention_paged_ref(qf, k, v, pos, pt, qpos,
                                                   window=window)
    out = attention_ops.decode_paged_pallas(qf, k, v, pos, pt, qpos,
                                            window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # the inactive slot must be exact zero, not a softmax of garbage
    assert np.all(np.asarray(out)[2] == 0.0)


@pytest.mark.parametrize("window", [None, 6])
def test_decode_paged_q8_pallas_matches_ref(window):
    rng, p, pg, kh, d, pt, qpos, pos, qf = _paged_fixture()
    kc = jnp.asarray(rng.integers(-127, 128, (p, pg, kh, d)), jnp.int8)
    vc = jnp.asarray(rng.integers(-127, 128, (p, pg, kh, d)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.1, (p, pg, kh)), jnp.float16)
    vs = jnp.asarray(rng.uniform(0.01, 0.1, (p, pg, kh)), jnp.float16)
    ref = attention_ref.decode_attention_paged_q8_ref(
        qf, kc, vc, ks, vs, pos, pt, qpos, window=window)
    out = attention_ops.decode_paged_q8_pallas(
        qf, kc, vc, ks, vs, pos, pt, qpos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert np.all(np.asarray(out)[2] == 0.0)


def test_paged_ref_equals_contiguous_ref_on_gathered_cache():
    rng, p, pg, kh, d, pt, qpos, pos, qf = _paged_fixture()
    k = jnp.asarray(rng.normal(size=(p, pg, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(p, pg, kh, d)), jnp.float32)
    kg = attention_ref.gather_pages(k, pt)
    vg = attention_ref.gather_pages(v, pt)
    kpos = attention_ref.paged_kpos(pos, pt)
    dense = attention_ref.decode_attention_ref(qf, kg, vg, kpos, qpos)
    paged = attention_ref.decode_attention_paged_ref(qf, k, v, pos, pt, qpos)
    act = np.asarray(qpos) >= 0
    np.testing.assert_array_equal(np.asarray(dense)[act],
                                  np.asarray(paged)[act])


@pytest.mark.parametrize("bits", [16, 8])
def test_gqa_decode_paged_matches_ring_cache(bits):
    rng = jax.random.PRNGKey(0)
    s, h, kh, d, dm, pg, npp = 2, 4, 2, 16, 32, 4, 4
    params = attn_mod.init_attention_params(rng, dm, h, kh, d,
                                            dtype=jnp.float32)
    ring = attn_mod.init_kv_cache(s, pg * npp, kh, d, dtype=jnp.float32,
                                  bits=bits)
    pool = attn_mod.init_paged_kv_pool(1 + s * npp, pg, kh, d,
                                       dtype=jnp.float32, bits=bits)
    pt = jnp.asarray(1 + np.arange(s * npp).reshape(s, npp), jnp.int32)
    for t in range(6):
        x = jax.random.normal(jax.random.fold_in(rng, t), (s, 1, dm),
                              jnp.float32)
        qpos = jnp.full((s,), t, jnp.int32)
        yr, ring = attn_mod.gqa_decode(params, x, ring, n_heads=h,
                                       n_kv_heads=kh, head_dim=d,
                                       rope_theta=1e4, qpos=qpos)
        yp, pool = attn_mod.gqa_decode_paged(params, x, pool, n_heads=h,
                                             n_kv_heads=kh, head_dim=d,
                                             rope_theta=1e4, qpos=qpos,
                                             page_table=pt)
        np.testing.assert_array_equal(np.asarray(yr), np.asarray(yp))


def test_gqa_decode_paged_inactive_writes_hit_trash_page():
    rng = jax.random.PRNGKey(0)
    h, kh, d, dm, pg, npp = 4, 2, 16, 32, 4, 2
    params = attn_mod.init_attention_params(rng, dm, h, kh, d,
                                            dtype=jnp.float32)
    pool = attn_mod.init_paged_kv_pool(1 + npp, pg, kh, d,
                                       dtype=jnp.float32)
    pt = jnp.asarray(np.vstack([1 + np.arange(npp), -np.ones(npp)]),
                     jnp.int32)
    x = jax.random.normal(rng, (2, 1, dm), jnp.float32)
    _, pool = attn_mod.gqa_decode_paged(
        params, x, pool, n_heads=h, n_kv_heads=kh, head_dim=d,
        rope_theta=1e4, qpos=jnp.array([0, -1], jnp.int32), page_table=pt)
    assert np.all(np.asarray(pool["pos"])[0] == -1)  # trash stays empty
    assert np.asarray(pool["pos"])[1, 0] == 0        # active write landed


# ---------------------------------------------------------------------------
# engine vs generate
# ---------------------------------------------------------------------------

def _lockstep_case(cfg):
    params = _params(cfg)
    b, p, n_new, pg = 4, 8, 8, 4
    toks = np.random.default_rng(1).integers(
        1, cfg.vocab_size, size=(b, p)).astype(np.int32)
    ref = np.asarray(sd.generate(params, cfg, dict(tokens=jnp.asarray(toks)),
                                 n_new=n_new, cache_len=16))
    eng = ServeEngine(params, cfg, n_slots=b, page_size=pg,
                      n_pages=1 + b * ((p + n_new) // pg))
    rids = [eng.submit(list(toks[i]), max_new=n_new) for i in range(b)]
    res = eng.run()
    np.testing.assert_array_equal(np.stack([res[r] for r in rids]), ref)
    assert eng.page_pool.n_live == 0


def test_engine_lockstep_token_exact_vs_generate():
    _lockstep_case(get_config("llama3_2_3b").reduced())


def test_engine_lockstep_token_exact_vs_generate_int8_cache():
    _lockstep_case(dataclasses.replace(get_config("llama3_2_3b").reduced(),
                                       kv_cache_bits=8))


def test_engine_churn_mixed_lengths_invariants():
    cfg = get_config("llama3_2_3b").reduced()
    eng = ServeEngine(_params(cfg), cfg, n_slots=2, page_size=4,
                      n_pages=1 + 10)
    rng = np.random.default_rng(7)
    rids = [eng.submit(list(rng.integers(1, cfg.vocab_size,
                                         int(rng.integers(3, 12)))),
                       max_new=int(rng.integers(1, 9)))
            for _ in range(6)]
    steps = 0
    while not eng.idle:
        eng.step()
        eng.page_pool.check_invariants()
        steps += 1
        assert steps < 500
    for rid in rids:
        r = eng.request(rid)
        assert r.state == "done" and len(r.out) == r.max_new
    assert eng.page_pool.n_live == 0
    assert eng.stats["prefill_batches"] >= 2  # mid-flight admissions ran


def test_engine_eos_retires_midflight_and_backfills_slot():
    cfg = get_config("llama3_2_3b").reduced()
    params = _params(cfg)
    toks = np.random.default_rng(3).integers(
        1, cfg.vocab_size, size=(2, 4)).astype(np.int32)
    # discover a token row 0 will emit mid-stream, then replay with it as EOS
    probe = ServeEngine(params, cfg, n_slots=1, page_size=4, n_pages=1 + 4)
    rid = probe.submit(list(toks[0]), max_new=6)
    stream = probe.run()[rid]
    eos = stream[2]
    eng = ServeEngine(params, cfg, n_slots=1, page_size=4, n_pages=1 + 4,
                      eos_id=eos)
    r0 = eng.submit(list(toks[0]), max_new=6)
    r1 = eng.submit(list(toks[1]), max_new=2)  # waits for the only slot
    while not eng.idle:
        eng.step()
        eng.page_pool.check_invariants()
    req0, req1 = eng.request(r0), eng.request(r1)
    assert req0.finish_reason == "eos"
    assert req0.out == stream[:3]          # eos emitted, then retired
    assert len(req0.out) < 6               # early, not max_new
    assert req1.state == "done" and len(req1.out) == 2  # backfilled slot


def test_engine_vlm_lockstep_and_split_serve_wire_bytes():
    cfg = get_config("tinyllava").reduced()
    params = _params(cfg)
    b, p, n_new, pg = 2, 16, 4, 8
    n_img = cfg.n_image_tokens
    rng = np.random.default_rng(5)
    toks = rng.integers(1, cfg.vocab_size, size=(b, p)).astype(np.int32)
    imgs = rng.normal(size=(b, n_img, cfg.d_vision)).astype(np.float32)
    ref = np.asarray(sd.generate(
        params, cfg, dict(tokens=jnp.asarray(toks),
                          image_embeds=jnp.asarray(imgs)),
        n_new=n_new, cache_len=64))
    n_pages = 1 + b * (-(-(n_img + p + n_new) // pg))
    eng = ServeEngine(params, cfg, n_slots=b, page_size=pg, n_pages=n_pages)
    rids = [eng.submit(list(toks[i]), max_new=n_new, image_embeds=imgs[i])
            for i in range(b)]
    res = eng.run()
    np.testing.assert_array_equal(np.stack([res[r] for r in rids]), ref)
    assert eng.stats["wire_bytes"] == 0  # co-located mode ships nothing

    eng = ServeEngine(params, cfg, n_slots=b, page_size=pg, n_pages=n_pages,
                      split_wire=cfg.split.quant)
    rids = [eng.submit(list(toks[i]), max_new=n_new, image_embeds=imgs[i])
            for i in range(b)]
    res = eng.run()
    assert all(len(res[r]) == n_new for r in rids)
    # byte accounting matches the WireLink static contract for the shipped
    # connector activations (B, n_img, d_model in the compute dtype)
    link = WireLink(src=0, dst=1, quant=cfg.split.quant)
    sds = jax.ShapeDtypeStruct((b, n_img, cfg.d_model), tf.cdtype(cfg))
    assert eng.stats["wire_bytes"] == link.fwd_wire_bytes(sds)


# ---------------------------------------------------------------------------
# prefill at each row's last real position
# ---------------------------------------------------------------------------

def _ragged_prefill_batch(cfg, plens, rows, lp, seed):
    """Right-padded prompts of lengths ``plens`` in ``rows`` rows of
    ``lp`` tokens; rows past ``plens`` are dummies (zeros)."""
    rng = np.random.default_rng(seed)
    tokens = np.zeros((rows, lp), np.int32)
    for i, p in enumerate(plens):
        tokens[i, :p] = rng.integers(1, cfg.vocab_size, p)
    batch = dict(tokens=jnp.asarray(tokens))
    if cfg.modality == "vlm":
        img = rng.normal(size=(rows, cfg.n_image_tokens, cfg.d_vision))
        img[len(plens):] = 0.0
        batch["image_embeds"] = jnp.asarray(img.astype(np.float32))
    return batch


@pytest.mark.parametrize("arch", ["llama3_2_3b", "tinyllava", "zamba2_2_7b"])
def test_prefill_last_positions_gathers_full_logits(arch):
    """``last_positions`` returns the full prefill's logits at those
    positions, (B, V), and the same caches."""
    cfg = get_config(arch).reduced()
    params = _params(cfg)
    n_img = cfg.n_image_tokens if cfg.modality == "vlm" else 0
    plens, rows, lp, cache_len = [5, 12, 8], 4, 12, n_img + 12
    batch = _ragged_prefill_batch(cfg, plens, rows, lp, seed=11)
    last = np.array([n_img + p - 1 for p in plens] + [0], np.int32)
    rng = jax.random.PRNGKey(3)
    full, caches = sd.prefill(params, cfg, batch, cache_len, rng=rng)
    got, got_caches = sd.prefill(params, cfg, batch, cache_len, rng=rng,
                                 last_positions=jnp.asarray(last))
    assert full.shape == (rows, n_img + lp, cfg.vocab_size)
    assert got.shape == (rows, cfg.vocab_size)
    # the head over B rows and over B x S rows may sum in another order
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(full)[np.arange(rows), last],
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(caches),
                    jax.tree_util.tree_leaves(got_caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefill_last_positions_are_data_not_a_new_program():
    """Other positions of the same shape reuse the traced program."""
    cfg = get_config("llama3_2_3b").reduced()
    params = _params(cfg)
    batch = _ragged_prefill_batch(cfg, [3, 7], 2, 8, seed=2)
    sd.prefill(params, cfg, batch, 8,
               last_positions=jnp.asarray([2, 6], jnp.int32))
    before = compile_cache.lowerings()
    sd.prefill(params, cfg, batch, 8,
               last_positions=jnp.asarray([0, 7], jnp.int32))
    assert compile_cache.lowerings() == before


def _wave_features(params, cfg, wire, imgs, rows):
    """The engine's split-serve wave: connector over the admitted images
    and zero dummy rows, one encode/decode round trip for the wave."""
    from repro.core import quantizers
    from repro.models.layers.mlp import mlp_forward

    pad = np.zeros((rows - len(imgs),) + imgs.shape[1:], imgs.dtype)
    feats = mlp_forward(params["connector"],
                        jnp.asarray(np.concatenate([imgs, pad]))
                        .astype(tf.cdtype(cfg)))
    return quantizers.decode(wire, quantizers.encode(wire, feats))


def _no_cut(cfg):
    """No compressor at the cut: its per-sample statistics would see a
    row's right padding, which an unpadded ``generate`` never does."""
    return dataclasses.replace(
        cfg, split=SplitConfig(quant=QuantConfig(method="identity"),
                               learnable_codec=False, enabled=False))


@pytest.mark.parametrize("arch,plens,split", [
    ("tinyllava", [16, 16, 16], True),
    ("tinyllava", [5, 17, 9], False),
    ("llama3_2_3b", [5, 17, 9], False),
])
def test_engine_prefill_fetches_rows_by_vocab(arch, plens, split):
    """Three requests in a wave of four rows (one dummy row): the prefill
    copies back rows x V logits, and every request's tokens equal greedy
    ``generate`` on its own prompt.  Split-serve ships the images over
    the wire (``generate`` gets the reconstruction of the same wave) and
    keeps the cut's compressor, so its prompts fill the bucket; prompts
    of different lengths are right-padded, and run without the cut."""
    cfg = get_config(arch).reduced()
    if not split:
        cfg = _no_cut(cfg)
    params = _params(cfg)
    n_img = cfg.n_image_tokens if cfg.modality == "vlm" else 0
    pg, n_new = 8, 4
    rng = np.random.default_rng(9)
    toks = [rng.integers(1, cfg.vocab_size, p).astype(np.int32)
            for p in plens]
    imgs = rng.normal(size=(len(plens), n_img, cfg.d_vision)
                      ).astype(np.float32)
    wire = cfg.split.quant if split else None
    n_pages = 1 + 4 * (-(-(n_img + max(plens) + n_new) // pg))
    eng = ServeEngine(params, cfg, n_slots=4, page_size=pg, n_pages=n_pages,
                      split_wire=wire)
    rids = [eng.submit(list(t), max_new=n_new,
                       image_embeds=imgs[i] if n_img else None)
            for i, t in enumerate(toks)]
    res = eng.run()

    rows = eng.stats["prefill_rows"]
    assert eng.stats["prefill_batches"] == 1 and rows == 4
    itemsize = jnp.dtype(tf.cdtype(cfg)).itemsize
    assert eng.stats["prefill_fetch_bytes"] == rows * cfg.vocab_size * itemsize
    feats = _wave_features(params, cfg, wire, imgs, rows) if split else None
    for i, t in enumerate(toks):
        batch = dict(tokens=jnp.asarray(t[None]))
        if split:
            batch["image_features"] = feats[i:i + 1]
        elif n_img:
            batch["image_embeds"] = jnp.asarray(imgs[i:i + 1])
        ref = np.asarray(sd.generate(params, cfg, batch, n_new=n_new,
                                     cache_len=n_img + len(t) + n_new))
        assert res[rids[i]] == list(ref[0]), i


def test_engine_prefill_fetch_bytes_sum_over_waves():
    """Each prefill wave adds its rows x V x itemsize."""
    cfg = get_config("llama3_2_3b").reduced()
    eng = ServeEngine(_params(cfg), cfg, n_slots=2, page_size=4,
                      n_pages=1 + 10)
    rng = np.random.default_rng(4)
    for _ in range(5):
        eng.submit(list(rng.integers(1, cfg.vocab_size,
                                     int(rng.integers(2, 10)))),
                   max_new=int(rng.integers(1, 5)))
    waves = []
    while not eng.idle:
        rows = eng.stats["prefill_rows"]
        eng.step()
        if eng.stats["prefill_rows"] != rows:
            waves.append(eng.stats["prefill_rows"] - rows)
    itemsize = jnp.dtype(tf.cdtype(cfg)).itemsize
    assert len(waves) >= 2
    assert eng.stats["prefill_fetch_bytes"] == sum(
        r * cfg.vocab_size * itemsize for r in waves)


# ---------------------------------------------------------------------------
# donation + generate early stop
# ---------------------------------------------------------------------------

def test_serve_step_donates_caches_no_copy():
    cfg = get_config("llama3_2_3b").reduced()
    params = _params(cfg)
    caches = tf.init_caches(cfg, 2, 16, dtype=tf.cdtype(cfg))
    step = sd.compiled_serve_step(cfg)
    low = step.lower(params, caches, dict(tokens=jnp.zeros((2, 1),
                                                           jnp.int32)),
                     jnp.zeros((2,), jnp.int32))
    assert "tf.aliasing_output" in low.as_text()
    assert "input_output_alias" in low.compile().as_text()


def test_paged_step_donates_pools():
    from repro.serve import paged
    cfg = get_config("llama3_2_3b").reduced()
    params = _params(cfg)
    pools = paged.init_pools(cfg, 5, 4)
    step = paged.compiled_paged_step(cfg)
    low = step.lower(params, pools, dict(tokens=jnp.zeros((2, 1),
                                                          jnp.int32)),
                     jnp.zeros((2,), jnp.int32),
                     jnp.full((2, 2), -1, jnp.int32))
    assert "tf.aliasing_output" in low.as_text()
    assert "input_output_alias" in low.compile().as_text()


def test_generate_eos_freezes_finished_rows():
    cfg = get_config("llama3_2_3b").reduced()
    params = _params(cfg)
    toks = np.random.default_rng(0).integers(
        1, cfg.vocab_size, (2, 8)).astype(np.int32)
    batch = dict(tokens=jnp.asarray(toks))
    base = np.asarray(sd.generate(params, cfg, batch, n_new=8,
                                  cache_len=16))
    eos = int(base[0][2])
    out = np.asarray(sd.generate(params, cfg, batch, n_new=8, cache_len=16,
                                 eos_id=eos, pad_id=0))
    i0 = list(base[0]).index(eos)
    # regression: the finished row's tokens are unchanged by continued
    # stepping — eos kept, everything after is pad
    np.testing.assert_array_equal(out[0][:i0 + 1], base[0][:i0 + 1])
    assert np.all(out[0][i0 + 1:] == 0)
    if eos not in base[1]:
        np.testing.assert_array_equal(out[1], base[1])
