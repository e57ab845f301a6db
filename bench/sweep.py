#!/usr/bin/env python3
"""Find the knee of a serving cell: offer its traffic open-loop at a
series of rates to one engine and report what it sustained.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 20 \
        --rates 2,4,8,16

One process builds the cell once (weights, engine, warm-up) and runs one
window per rate, each drained for at most ``--drain`` seconds.  One JSON
line a rate: offered and completed requests a second, output tokens a
second, TTFT p50/p90 and ITL p99 in ms, and the requests left unfinished.
The knee is the highest rate whose completions keep up with the offer
and whose TTFT stays flat; a cell's ``rate_per_s`` is set below it from
this output (see ``PERF.md``).  Scored runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=10.0)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import device, runner, serve_cell, spec

    cell = spec.load_cell(args.workload, ROOT)
    devices = device.accelerators(cell.chips)
    device.enable_compile_cache()
    cell.params["drain_s"] = args.drain
    run = runner.Run(cell=cell, seed=args.seed, sizes=spec.sizes(cell.config),
                     arch=spec.arch_config(cell.config),
                     peaks=spec.peaks(devices[0].device_kind, ROOT),
                     devices=devices)
    built = serve_cell.build(run)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.params["rate_per_s"] = rate
        c = serve_cell.window(run, built, args.seconds)
        e2e = serve_cell.end_to_end(c)
        lat = serve_cell.latencies(c)
        served, t0 = c["served"], c["t0"]
        done_in = sum(1 for r in served if r.state == "done"
                      and r.emit_times[-1] <= c["end"])
        print(json.dumps(dict(
            rate=rate, slots=int(cell.params["n_slots"]),
            offered_per_s=len(served) / (c["end"] - t0),
            completed_per_s=done_in / (c["end"] - t0),
            unfinished=serve_cell.failed(c),
            ttft_p50_ms=float(np.percentile(lat["ttft"], 50)),
            ttft_p90_ms=float(np.percentile(lat["ttft"], 90)),
            itl_p99_ms=float(np.percentile(lat["gaps"], 99)), **e2e,
            ticks=c["stats"].get("decode_ticks"),
            prefills=c["stats"].get("prefill_batches"),
            drained_s=c["closed"] - c["end"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
