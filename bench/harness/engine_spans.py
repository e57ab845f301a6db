"""The serving engine's own host spans read against the device trace.

``ServeEngine.step`` runs each of its phases inside a host span named
``engine.*`` (``engine.step`` around ``engine.admit``,
``engine.prefill.inputs``, ``engine.wire``, ``engine.prefill.launch``,
``engine.prefill.fetch``, ``engine.tick.inputs``, ``engine.tick.launch``,
``engine.tick.fetch``, ``engine.pick``, ``engine.emit``).  The profiler
writes them on its host clock beside the device planes, and
``trace.reduce_profile`` keeps them in ``Summary.spans``, so they share
the clock of the device's idle gaps.

An idle stretch of the device is split between the innermost engine
spans open over it by overlap, so an offset between the host's and the
device's clocks (``clock_offsets_ms`` bounds it) moves at most that much
of each gap from one span to the next.  Idle time
outside every engine span (the benchmark's own loop, waiting for an
arrival) is left out.  A program that writes no engine span gives
nothing here.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

PREFIX = "engine."
STEP = "engine.step"
FETCHES = ("engine.prefill.fetch", "engine.tick.fetch")
TICK_LAUNCH = "engine.tick.launch"
TICK_FETCH = "engine.tick.fetch"
# how far a tick's run may start from its launch span on the trace's
# clocks; on a TPU v5e they were seen up to 7 ms apart
NEAR_NS = 10_000_000
TICK_PROGRAM = "paged_step"

Span = Tuple[str, int, int]


def engine_spans(summary) -> List[Span]:
    return sorted((s for s in summary.spans if s[0].startswith(PREFIX)),
                  key=lambda s: (s[1], -s[2]))


def innermost(spans: List[Span]) -> List[Tuple[int, int, str]]:
    """Time covered by ``spans`` (sorted by start, longest first), cut
    into pieces each named by the innermost span open over it; spans of
    one thread nest, so the innermost is the last one opened."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[str, int]] = []
    t = None

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][1] <= limit:
            name, end = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for name, s, e in spans:
        close_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][0]))
        t = s if t is None else max(t, s)
        stack.append((name, e))
    close_until(float("inf"))
    return out


def _overlap(gaps: List[Tuple[int, int]],
             pieces: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Nanoseconds of ``gaps`` under each name of ``pieces``; both are
    sorted and disjoint."""
    out: Dict[str, int] = {}
    i = j = 0
    while i < len(gaps) and j < len(pieces):
        lo = max(gaps[i][0], pieces[j][0])
        hi = min(gaps[i][1], pieces[j][1])
        if hi > lo:
            name = pieces[j][2]
            out[name] = out.get(name, 0) + hi - lo
        if gaps[i][1] <= pieces[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_by_span(summary) -> Optional[Dict[str, float]]:
    """Seconds of the window in which no operation runs on the device,
    by the innermost engine span open over them, averaged over the
    devices; None when no engine span overlaps the window."""
    lo, hi = summary.window
    spans = [s for s in engine_spans(summary) if s[2] > lo and s[1] < hi]
    if not spans:
        return None
    pieces = innermost(spans)
    out: Dict[str, float] = {}
    for dev in range(summary.n_devices):
        for name, ns in _overlap(summary.idle_gaps(dev), pieces).items():
            out[name] = out.get(name, 0.0) + ns / 1e9 / summary.n_devices
    return out


def host_ms_per_step(summary) -> Optional[float]:
    """Mean over the ``engine.step`` spans inside the window of their
    length less that of their ``*.fetch`` spans, in ms."""
    lo, hi = summary.window
    spans = engine_spans(summary)
    steps = [s for s in spans if s[0] == STEP and lo <= s[1] and s[2] <= hi]
    if not steps:
        return None
    fetches = [s for s in spans if s[0] in FETCHES]
    starts = [s[1] for s in fetches]
    total = 0
    for _, s, e in steps:
        total += e - s
        for _, fs, fe in fetches[bisect.bisect_left(starts, s):]:
            if fs >= e:
                break
            total -= min(fe, e) - fs
    return total / 1e6 / len(steps)


def clock_offsets_ms(summary) -> List[Tuple[float, float]]:
    """For each decode tick in the window whose program run the trace
    holds, bounds in ms on the device's timestamp less the host's for
    one instant: the run cannot start before its ``engine.tick.launch``
    span opens, nor end after the ``engine.tick.fetch`` span that waits
    for it closes, so the offset lies in (run end - fetch end, run start
    - launch start).  A tick's run is the one that starts nearest to its
    launch, no further than ``NEAR_NS``: ticks lie farther apart."""
    lo, hi = summary.window
    runs = sorted((m.start, m.end) for m in summary.module_runs(TICK_PROGRAM))
    starts = [r[0] for r in runs]
    spans = engine_spans(summary)
    fetches = [s for s in spans if s[0] == TICK_FETCH]
    fetch_starts = [s[1] for s in fetches]
    out = []
    for name, ls, le in spans:
        if name != TICK_LAUNCH or not (lo <= ls and le <= hi):
            continue
        j = bisect.bisect_left(fetch_starts, le)
        i = bisect.bisect_left(starts, ls)
        near = [k for k in (i - 1, i) if 0 <= k < len(runs)
                and abs(starts[k] - ls) <= NEAR_NS]
        if j == len(fetches) or not near:
            continue
        rs, re_ = runs[min(near, key=lambda k: abs(starts[k] - ls))]
        out.append(((re_ - fetches[j][2]) / 1e6, (rs - ls) / 1e6))
    return out
