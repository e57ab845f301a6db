#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic
mix, sizes and per-layer metric readers are found by name under
``bench/``.  One run is one process: it makes the weights and inputs
from ``--seed`` on the device, warms every shape the cell's traffic uses
(set-up), measures for ``--seconds``, checks what the timed path
produced against the plain reference in ``bench/reference/``, and prints
one JSON object as the last line of standard output.  With ``--trace 1``
the window runs under the JAX profiler and the line carries the cell's
per-layer metrics instead of its end-to-end ones.

It exits non-zero, and prints no result, when JAX finds no TPU, fewer
chips than the cell asks for, or a device kind missing from
``bench/peaks.json``.  ``--control 1`` (never used by a scored run)
also reads the numbers of the float8 control in the same process.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import device, runner, spec

    cell = spec.load_cell(args.workload, ROOT)
    try:
        devices = device.accelerators(cell.chips)
        peaks = spec.peaks(devices[0].device_kind, ROOT)
    except (device.NoAccelerator, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    device.enable_compile_cache()
    result = runner.run_cell(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), devices=devices,
                             peaks=peaks, t_start=T_START,
                             control=bool(args.control))
    print(runner.check_lines(result), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
