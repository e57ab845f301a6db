"""The traffic generator: the same seed gives the same inputs, every
seed the same sizes in another order, all inside the mix's ranges."""
import numpy as np
import pytest

from conftest import ROOT, tiny_config

from bench.harness import spec, traffic

SEEDS = [0, 7, 2 ** 31 + 5, 2 ** 33 + 1]
SERVE = [w["name"] for w in spec.benchmark(ROOT)["workloads"]
         if spec.load_cell(w["name"], ROOT).kind == "serve"]


def _sizes(reqs):
    return (sorted(len(r.prompt) for r in reqs),
            sorted(r.max_new for r in reqs),
            sorted(round(b - a, 9) for a, b in
                   zip([0.0] + [r.due for r in reqs], [r.due for r in reqs])))


@pytest.mark.parametrize("workload", SERVE)
def test_serve_requests_deterministic_and_in_range(workload):
    cell = spec.load_cell(workload, ROOT)
    c = spec.sizes(cell.config)
    mix = cell.traffic
    a = traffic.serve_requests(mix, cell.params, c, SEEDS[2], 20.0)
    b = traffic.serve_requests(mix, cell.params, c, SEEDS[2], 20.0)
    assert [(r.due, r.prompt, r.max_new, r.image) for r in a] == \
        [(r.due, r.prompt, r.max_new, r.image) for r in b]
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for r in a:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new <= o["max"]
        assert all(1 <= t < c.vocab_size for t in r.prompt)
        assert 0 <= r.image < mix["image_pool"]
        assert r.due >= 0.0
    if mix["arrivals"] == "poisson":
        assert a[-1].due <= 20.0
        assert all(x.due <= y.due for x, y in zip(a, a[1:]))


@pytest.mark.parametrize("workload", SERVE)
def test_every_seed_gets_the_same_work(workload):
    cell = spec.load_cell(workload, ROOT)
    c = spec.sizes(cell.config)
    runs = [traffic.serve_requests(cell.traffic, cell.params, c, s, 20.0)
            for s in SEEDS]
    assert all(_sizes(r) == _sizes(runs[0]) for r in runs)
    assert [r.prompt for r in runs[0]] != [r.prompt for r in runs[1]]
    # the same work at the same times, to within one group of neighbours
    g = cell.traffic["order_group"]
    for r in runs[1:]:
        for i in range(g - 1, len(r), g):
            assert r[i].due == pytest.approx(runs[0][i].due, abs=1e-9)
            assert sum(len(q.prompt) for q in r[i + 1 - g:i + 1]) == \
                sum(len(q.prompt) for q in runs[0][i + 1 - g:i + 1])
            assert sum(q.max_new for q in r[i + 1 - g:i + 1]) == \
                sum(q.max_new for q in runs[0][i + 1 - g:i + 1])


def test_lognormal_sizes_median_and_clip():
    dist = {"median": 48, "sigma": 0.6, "min": 16, "max": 256}
    v = traffic.quantile_set(dist, 1001)
    assert np.median(v) == 48
    assert v.min() >= 16 and v.max() <= 256
    gaps = traffic.exponential_gaps(2.0, 1000)
    assert abs(gaps.mean() - 0.5) < 0.02


def test_train_batches_deterministic_and_labelled():
    import jax

    from bench.harness import weights as W

    c = spec.sizes(tiny_config())
    mix = {"seq_len": 24, "image_std": 1.0, "distinct_batches": 3}
    key = W.base_key(SEEDS[3])
    a = traffic.train_batches(mix, {"batch": 4}, c, key)
    b = traffic.train_batches(mix, {"batch": 4}, c, key)
    assert len(a) == 3
    for x, y in zip(a, b):
        for k in x:
            assert np.array_equal(np.asarray(x[k]), np.asarray(y[k]))
    n_img = c.n_image_tokens
    tok, lab = np.asarray(a[0]["tokens"]), np.asarray(a[0]["labels"])
    assert tok.shape == (4, 24 - n_img)
    assert (lab[:, :n_img] == traffic.IGNORE).all()
    assert np.array_equal(lab[:, n_img:-1], tok[:, 1:])
    assert (lab[:, -1] == traffic.IGNORE).all()
    assert ((tok >= 0) & (tok < c.vocab_size)).all()
    rows = np.concatenate([np.asarray(x["tokens"]) for x in a])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert a[0]["image_embeds"].shape == (4, n_img, c.d_vision)
    del jax


def test_large_seeds_give_distinct_keys():
    import jax

    from bench.harness import weights as W

    keys = {tuple(np.asarray(jax.random.key_data(W.base_key(s))).tolist())
            for s in SEEDS + [2 ** 32 + 7, 7 + 2 ** 32 * 0]}
    assert len(keys) == len(SEEDS) + 1
