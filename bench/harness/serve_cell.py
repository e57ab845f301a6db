"""A serving cell: the program's ``ServeEngine`` in split-serve mode.

Set-up builds the engine on the seeded weights and warms every shape the
cell's traffic will use (one admission wave of each power-of-two size up
to ``n_slots``, decoding far enough to cross into every page-table
bucket).  The window offers the cell's requests open-loop at their due
times (or all at once for a backlog) and calls the engine's ``step``;
each request's first token is timed from its due time.  A request due
in the window is waited for after the close, up to ``drain_s``.

The check runs the plain reference once over a sample of the finished
requests, drawn from the seed with the longest among them, and reads the
gap by which each served token's logit lies below the reference's best
at its position: the widest (``served_logit_gap``) and the mean over the
sample's served tokens (``served_logit_gap_mean``).  A cell's limits
say which of the two it compares.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench.harness import traffic, weights as W


def _pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def _pages(n: int, page: int) -> int:
    return -(-n // page)


def build(run) -> Dict:
    from repro.serve.engine import ServeEngine

    c, cfg, cp, mix = run.sizes, run.arch, run.cell.params, run.cell.traffic
    key = W.base_key(run.seed)
    params = run.model.init_params(c, key)
    images = traffic.image_pool(mix, c, W.sub_key(key, 4))
    page, slots = int(cp["page_size"]), int(cp["n_slots"])
    longest = c.n_image_tokens + mix["prompt_tokens"]["max"] \
        + mix["output_tokens"]["max"]
    n_pages = 1 + slots * _pages(longest, page)
    engine = ServeEngine(params, cfg, n_slots=slots, page_size=page,
                         n_pages=n_pages, split_wire=cfg.split.quant)
    _warm(engine, c, mix, slots, page, images)
    return dict(engine=engine, images=images)


def _warm(engine, c, mix, slots: int, page: int, images) -> None:
    """Every prefill shape (each power-of-two wave size up to ``slots``,
    each padded prompt length) and every page-table bucket that the
    mix's requests can reach, each once."""
    n_img = c.n_image_tokens
    p_lo, p_hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    o_hi = mix["output_tokens"]["max"]
    by_len = {}
    for p in range(p_lo, p_hi + 1):
        by_len.setdefault(_pow2(_pages(n_img + p, page)), p)
    for p in by_len.values():
        rows = 1
        while rows <= slots:
            for _ in range(rows):
                engine.submit([1] * p, max_new=2, image_embeds=images[0])
            engine.run()
            rows *= 2
    seen = {_pow2((n_img + p) // page + 1) for p in by_len.values()}
    for q in range(n_img + p_lo, n_img + p_hi + o_hi):
        b = _pow2(q // page + 1)
        if b in seen:
            continue
        seen.add(b)
        p = min(max(q - n_img, p_lo), p_hi)
        engine.submit([1] * p, max_new=q - (n_img + p) + 2,
                      image_embeds=images[0])
        engine.run()


def window(run, built: Dict, seconds: float) -> Dict:
    from jax.profiler import TraceAnnotation

    engine, images = built["engine"], built["images"]
    mix, cp = run.cell.traffic, run.cell.params
    reqs = traffic.serve_requests(mix, cp, run.sizes, run.seed, seconds)
    backlog = mix["arrivals"] == "backlog"
    drain = 0.0 if backlog else float(cp["drain_s"])
    before = {k: v for k, v in engine.stats.items()
              if isinstance(v, (int, float))}
    rids: List[int] = []
    t0 = time.perf_counter()
    due = [t0 + r.due for r in reqs]
    end, i, n = t0 + seconds, 0, len(reqs)
    while True:
        now = time.perf_counter()
        while i < n and due[i] <= now:
            r = reqs[i]
            rids.append(engine.submit(r.prompt, max_new=r.max_new,
                                      image_embeds=images[r.image],
                                      arrival_time=due[i]))
            i += 1
        if backlog and now >= end:
            break
        if not engine.idle:
            with TraceAnnotation("bench.engine_step"):
                engine.step()
        elif i < n:
            with TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(due[i], end + drain) - now))
        else:
            break
        if now >= end + drain:
            break
    closed = time.perf_counter()
    stats = {k: engine.stats[k] - before.get(k, 0) for k in before}
    served = [engine.request(r) for r in rids]
    return dict(t0=t0, end=end, closed=closed, due=due[:len(rids)],
                served=served, stats=stats, window_s=seconds,
                backlog=backlog,
                open_at_close=sum(1 for r in served if r.state != "done"))


def program_texts(run, built: Dict) -> List[str]:
    """The compiled HLO of the decode tick, once for each page-table
    bucket the engine used (read back from the compilation cache)."""
    import jax
    import jax.numpy as jnp

    from repro.serve import paged

    eng = built["engine"]
    step = paged.compiled_paged_step(run.arch)
    s = eng.scheduler.n_slots

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    i32 = jnp.int32
    return [step.lower(shapes(eng.params), shapes(eng.pools),
                       dict(tokens=jax.ShapeDtypeStruct((s, 1), i32)),
                       jax.ShapeDtypeStruct((s,), i32),
                       jax.ShapeDtypeStruct((s, npp), i32)
                       ).compile().as_text()
            for npp in sorted(eng.stats["page_table_buckets"])]


def end_to_end(counters: Dict) -> Dict[str, float]:
    t0, end = counters["t0"], counters["end"]
    tokens = sum(sum(1 for t in r.emit_times if t0 <= t <= end)
                 for r in counters["served"])
    return dict(serve_tokens_per_s=tokens / (end - t0))


def window_flops(run, counters: Dict) -> float:
    """Each admitted request's prefill over its image and prompt and each
    token it decoded at its context, attention included."""
    c, model = run.sizes, run.model
    n = 0.0
    for r in counters["served"]:
        if not r.out:
            continue
        n += model.prefill_flops(c, len(r.tokens))
        start = c.n_image_tokens + len(r.tokens)
        n += sum(model.decode_flops(c, start + j + 1)
                 for j in range(len(r.out) - 1))
    return n


def latencies(counters: Dict) -> Dict[str, np.ndarray]:
    """Milliseconds from each request's due time to its first token
    (``ttft``; one never answered waited until the close at least) and
    between consecutive tokens of a request (``gaps``)."""
    ttft, gaps = [], []
    for r, due in zip(counters["served"], counters["due"]):
        first = r.emit_times[0] if r.emit_times else counters["closed"]
        ttft.append((first - due) * 1e3)
        gaps.extend(np.diff(r.emit_times) * 1e3)
    return dict(ttft=np.asarray(ttft), gaps=np.asarray(gaps))


def attempted(counters: Dict) -> int:
    return len(counters["served"])


def failed(counters: Dict) -> int:
    """Requests due in the window and never finished; a backlog's
    requests still in flight at the close are not failures."""
    if counters["backlog"]:
        return 0
    return sum(1 for r in counters["served"] if r.state != "done")


def sample(run, counters: Dict) -> List:
    """Finished requests, drawn from the seed, the longest among them,
    until ``check_tokens`` served tokens are in the sample."""
    done = [r for r in counters["served"] if r.state == "done"]
    if not done:
        return []
    rng = np.random.default_rng([run.seed, 2])
    longest = max(range(len(done)), key=lambda j: len(done[j].out))
    order = [longest] + [j for j in rng.permutation(len(done))
                         if j != longest]
    want = int(run.cell.params["check_tokens"])
    picked, tokens = [], 0
    for j in order:
        if tokens >= want or len(picked) >= int(
                run.cell.params["check_requests_max"]):
            break
        picked.append(done[j])
        tokens += len(done[j].out)
    return picked


def _arrays(run, picked: List, images) -> Dict:
    c = run.sizes
    page = int(run.cell.params["page_size"])
    n_img = c.n_image_tokens
    lens = [len(r.tokens) for r in picked]
    lb = _pow2(_pages(n_img + max(lens), page)) * page
    r, n_out = len(picked), max(len(q.out) for q in picked)
    prompt = np.zeros((r, lb - n_img), np.int32)
    served = np.zeros((r, n_out), np.int32)
    for i, q in enumerate(picked):
        prompt[i, :lens[i]] = q.tokens
        served[i, :len(q.out)] = q.out
    img = np.stack([np.asarray(q.image_embeds, np.float32) for q in picked])
    return dict(img=img, prompt=prompt, plen=np.asarray(lens, np.int32),
                served=served, n_out=[len(q.out) for q in picked], lb=lb)


def _gaps(logits: np.ndarray, tokens: np.ndarray,
          n_out: List[int]) -> np.ndarray:
    """The gap below the best logit of the token at each served
    position, request after request."""
    out = []
    for i, n in enumerate(n_out):
        lg = logits[i, :n]
        out.append(lg.max(axis=-1) - lg[np.arange(n), tokens[i, :n]])
    return np.concatenate(out)


def _reference(run, a: Dict, lp: bool) -> np.ndarray:
    import jax.numpy as jnp

    key = W.base_key(run.seed)
    return np.asarray(run.model.serve_logits(
        run.sizes, key, jnp.asarray(a["img"]), jnp.asarray(a["prompt"]),
        jnp.asarray(a["plen"]), jnp.asarray(a["served"]), a["lb"], lp=lp))


def check(run, built: Dict, counters: Dict) -> Dict[str, float]:
    picked = sample(run, counters)
    built.pop("engine", None)
    if not picked:
        return dict(served_logit_gap=float("inf"),
                    served_logit_gap_mean=float("inf"))
    a = _arrays(run, picked, built["images"])
    ref = _reference(run, a, lp=False)
    gaps = _gaps(ref, a["served"], a["n_out"])
    built["readings"] = dict(gaps=gaps.tolist())
    return _numbers(gaps)


def _numbers(gaps: np.ndarray) -> Dict[str, float]:
    return dict(served_logit_gap=float(gaps.max()),
                served_logit_gap_mean=float(gaps.mean()))


def control(run, built: Dict, counters: Dict) -> Dict[str, float]:
    """At each served position, the gap in the reference of the token
    that the float8 reference puts first."""
    picked = sample(run, counters)
    a = _arrays(run, picked, built["images"])
    ref = _reference(run, a, lp=False)
    low = _reference(run, a, lp=True)
    gaps = _gaps(ref, low.argmax(axis=-1), a["n_out"])
    built["control_readings"] = dict(gaps=gaps.tolist())
    return _numbers(gaps)
