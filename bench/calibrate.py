#!/usr/bin/env python3
"""Read a cell's correctness numbers over many seeds in one process: the
program's, and for some seeds the float8 control's.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 8 [--control-seeds 1,2] [--faults half_batch,...] \
        [--fault-seeds 1,2] [--dump readings.jsonl]

Each seed builds the cell anew (weights, inputs, engine or state), runs a
short window at the cell's own load through the same driver as
``run.py``, and prints one JSON line with the numbers compared; for the
seeds in ``--control-seeds`` also the float8 control's.  ``--dump``
appends each seed's raw readings (a training cell's losses and leaf
norms, the program's and the reference's; a serving cell's gap at each
checked position) to a JSON-lines file.  For each seed in
``--fault-seeds``, each fault of ``--faults`` (the driver's own
``FAULTS``, else ``bench/harness/faults.py``'s) is then planted under the
timed path in turn for one more run of that seed, whose numbers are read
against the seed's reference (a training driver keeps it; a serving one
computes it anew), and the fault is taken out again.  The
limits in ``cells/<cell>.json`` are set from these readings (see
``PERF.md``); scored runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="",
                    help="faults to plant, one run each, for --fault-seeds")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--dump", default="",
                    help="append each seed's raw readings to this file")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import device, faults, runner, spec

    cell = spec.load_cell(args.workload, ROOT)
    devices = device.accelerators(cell.chips)
    peaks = spec.peaks(devices[0].device_kind, ROOT)
    device.enable_compile_cache()
    drv = runner.driver(cell.kind)
    plant = {name: getattr(drv, "FAULTS", {}).get(name) or faults.FAULTS[name]
             for name in args.faults.split(",") if name}
    control = {int(s) for s in args.control_seeds.split(",") if s}
    fault_seeds = {int(s) for s in args.fault_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        run = runner.make_run(cell, seed, devices, peaks)
        built = drv.build(run)
        counters = drv.window(run, built, args.seconds)
        line = dict(seed=seed, program=drv.check(run, built, counters),
                    attempted=drv.attempted(counters),
                    failed=drv.failed(counters))
        if seed in control:
            line["control"] = drv.control(run, built, counters)
        if seed in fault_seeds:
            line["faults"] = {name: _fault_run(drv, run, built, fault,
                                               args.seconds)
                              for name, fault in plant.items()}
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        if args.dump:
            raw = {k: built[k] for k in ("readings", "reference",
                                         "control_readings") if k in built}
            with open(args.dump, "a") as f:
                f.write(json.dumps(dict(seed=seed, **raw)) + "\n")
        del built, counters
    return 0


def _fault_run(drv, run, sound, fault, seconds):
    """The numbers of one run with ``fault`` planted, against the sound
    run's reference; the fault is taken out again after."""
    undo = []

    def patch(obj, name, value):
        undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    fault(patch)
    try:
        built = drv.build(run)
        counters = drv.window(run, built, seconds)
        if "reference" in sound:
            built["reference"] = sound["reference"]
        return drv.check(run, built, counters)
    finally:
        for obj, name, value in reversed(undo):
            setattr(obj, name, value)


if __name__ == "__main__":
    sys.exit(main())
