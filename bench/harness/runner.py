"""One run of one cell: set-up, window, check, result line.

The kind of the cell's traffic mix picks the driver, the module
``bench/harness/<kind>_cell.py``; the cell's configuration picks its model
module (``spec.model_module``); everything else is data.  With ``trace``
the window runs under the JAX profiler, the trace is reduced
(``trace.py``) and the cell's per-layer metric readers
(``bench/metrics/<name>.py``) read it.

A cell whose trace would hold too many device ops to reduce within a
run's time limit sets ``trace_seconds``: its traced run's window is
that long, or ``seconds`` where that is shorter.

A driver provides, each taking the ``Run`` first where it needs it:

* ``build(run) -> built``: everything up to the window (weights from the
  seed, compiled programs, warm-up, the checked steps); counted as set-up;
* ``window(run, built, seconds) -> counters``: the measured loop;
* ``program_texts(run, built)``: the compiled HLO of the programs the
  window ran, for the trace reduction;
* ``end_to_end(counters)``: the cell's end-to-end metrics but ``setup_s``;
* ``check(run, built, counters)`` and ``control(run, built, counters)``:
  the numbers compared with the plain reference, of the program and of
  the lower-precision control;
* ``attempted(counters)`` and ``failed(counters)``;
* ``window_flops(run, counters)``: the model operations of the work the
  window completed, for the whole-step share of peak (``*_mfu``);
* optionally ``FAULTS``: faults of its own timed path by name, which
  ``calibrate.py --faults`` takes before those of ``faults.py``.
"""
from __future__ import annotations

import dataclasses
import importlib
import os
import shutil
import sys
import time
from typing import Dict, List

from bench.harness import checks as C
from bench.harness import device, spec

TRACE_DIR = os.path.join(spec.BENCH_DIR, ".trace")


def driver(kind: str):
    """The driver of a traffic mix's ``kind``."""
    return importlib.import_module(f"bench.harness.{kind}_cell")


@dataclasses.dataclass
class Run:
    cell: spec.Cell
    seed: int
    sizes: object                      # the model module's sizes
    arch: object                       # the program's ArchConfig
    peaks: Dict
    devices: List
    model: object                      # the cell's model module
    driver: object                     # the cell's driver module


def make_run(cell: spec.Cell, seed: int, devices: List, peaks: Dict) -> Run:
    model = spec.model_module(cell.config)
    arch = getattr(model, "arch_config", spec.arch_config)(cell.config)
    return Run(cell=cell, seed=seed, sizes=model.sizes(cell.config),
               arch=arch, peaks=peaks, devices=devices, model=model,
               driver=driver(cell.kind))


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             devices: List, peaks: Dict, t_start: float,
             control: bool = False) -> Dict:
    run = make_run(cell, seed, devices, peaks)
    drv = run.driver
    built = drv.build(run)
    setup_s = time.perf_counter() - t_start
    _log(f"set-up {setup_s:.3f} s")

    if trace:
        traced = min(seconds, float(cell.params.get("trace_seconds", seconds)))
        counters, summary = _traced_window(run, drv, built, traced)
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
