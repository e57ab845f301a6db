"""One run of one cell: set-up, window, check, result line.

The kind of the cell's traffic mix picks the driver (``train_cell`` or
``serve_cell``); everything else is data.  With ``trace`` the window
runs under the JAX profiler, the trace is reduced (``trace.py``) and the
cell's per-layer metric readers (``bench/metrics/<name>.py``) read it.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import time
from typing import Dict, List

from bench.harness import checks as C
from bench.harness import device, serve_cell, spec, train_cell

DRIVERS = {"train": train_cell, "serve": serve_cell}
TRACE_DIR = os.path.join(spec.BENCH_DIR, ".trace")


@dataclasses.dataclass
class Run:
    cell: spec.Cell
    seed: int
    sizes: spec.Sizes
    arch: object                       # the program's ArchConfig
    peaks: Dict
    devices: List


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             devices: List, peaks: Dict, t_start: float,
             control: bool = False) -> Dict:
    run = Run(cell=cell, seed=seed, sizes=spec.sizes(cell.config),
              arch=spec.arch_config(cell.config), peaks=peaks,
              devices=devices)
    drv = DRIVERS[cell.kind]
    built = drv.build(run)
    setup_s = time.perf_counter() - t_start
    _log(f"set-up {setup_s:.3f} s")

    if trace:
        counters, summary = _traced_window(run, drv, built, seconds)
    else:
        counters, summary = drv.window(run, built, seconds), None
    dev = dict(device.describe(devices),
               memory_peak_bytes=device.memory_peak_bytes(devices))
    _log(f"window done: {_brief(counters)}")

    t_check = time.perf_counter()
    values = drv.check(run, built, counters)
    _log(f"check done in {time.perf_counter() - t_check:.3f} s")
    checks = C.judge(values, cell.params["limits"])
    out = dict(correct=C.passed(checks), attempted=drv.attempted(counters),
               failed=drv.failed(counters))
    if trace:
        from bench.harness import trace as T

        ctx = T.Context(run=run, counters=counters, summary=summary)
        out["metrics"] = _per_layer(cell, ctx)
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["device"] = dev
        out["breakdown"] = summary.breakdown()
    else:
        e2e = dict(drv.end_to_end(counters), setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = dev
    if control:
        out["control"] = C.judge(drv.control(run, built, counters),
                                 cell.params["limits"])
    out["checks"] = checks
    return out


def _traced_window(run, drv, built, seconds):
    import jax

    from bench.harness import trace as T

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    # host spans and device ops; no Python function tracer, whose cost
    # would land on the host loop being measured
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            counters = drv.window(run, built, seconds)
    finally:
        jax.profiler.stop_trace()
    summary = T.reduce(TRACE_DIR, drv.program_texts(run, built),
                       len(run.devices))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return counters, summary


def _per_layer(cell: spec.Cell, ctx) -> Dict:
    out = {}
    for m in cell.per_layer:
        value = spec.metric_module(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _brief(counters: Dict) -> str:
    keep = {k: v for k, v in counters.items()
            if isinstance(v, (int, float, str))}
    return str(keep)


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def check_lines(result: Dict) -> str:
    text = C.lines(result["checks"])
    if "control" in result:
        text = "\n".join([f"control {line}" for line in
                          C.lines(result["control"]).splitlines()] + [text])
    return text
