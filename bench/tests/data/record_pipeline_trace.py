#!/usr/bin/env python3
"""Record a short TPU trace of the split-pipeline cell, to check the
reading of its stage boundary's collective-permutes against a real
device trace (needs four chips).

    python3 bench/tests/data/record_pipeline_trace.py <out_dir> \
        [--seconds S] [--seed N]

The cell ``tinyllava-pipeline2-4chip`` is built as a run builds it (its
configuration, mesh, traffic and seeded state), and its window runs for
``--seconds`` inside a ``bench.window`` host span under the profiler.
Writes to ``out_dir``: ``v5e_pipeline_trace.xplane.pb``, the trace pruned
as ``record_engine_trace.prune`` prunes (device op and program lines,
names and times only; the ``bench.*`` host spans) and cut to the first
``--keep`` seconds of the window (``clip``), and
``v5e_pipeline_trace.hlo.txt``, the step's compiled HLO cut to its
header, the instructions that ``wire_exposed_share`` sorts (the
collective-permutes and the ops that enclose others) and the Pallas
kernels' custom calls (their operand shapes feed
``flash_attention_roofline``), and
``full_step.hlo.txt``, the whole of it.
"""
import argparse
import glob
import importlib.util
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

CELL = "tinyllava-pipeline2-4chip"


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cut_hlo(text: str, keep) -> str:
    """The header line and the instruction lines whose opcode is in
    ``keep``."""
    reader = _load("wire_exposed_share", os.path.join(
        ROOT, "bench", "metrics", "wire_exposed_share.py"))
    lines = text.splitlines()
    out = [lines[0]]
    for line in lines[1:]:
        m = reader._LINE.match(line)
        if m:
            op = reader._OPCODE.search(" " + m.group(2))
            if op and op.group(1) in keep:
                out.append(line)
    return "\n".join(out) + "\n"


def clip(raw: bytes, seconds: float) -> bytes:
    """The trace's first ``seconds`` of its ``bench.window`` span: the
    span cut to that length, and only the device events that start in
    it kept."""
    from bench.harness.trace import WINDOW_SPAN

    pb = _load("record_engine_trace", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "record_engine_trace.py"))._xplane_pb2()
    space = pb.XSpace.FromString(raw)
    cut_ps = int(seconds * 1e12)
    lo = hi = None
    for plane in space.planes:
        names = {k: m.name for k, m in plane.event_metadata.items()}
        for line in plane.lines:
            for e in line.events:
                if names.get(e.metadata_id) == WINDOW_SPAN:
                    lo = line.timestamp_ns * 1000 + e.offset_ps
                    hi = lo + cut_ps
                    e.duration_ps = min(e.duration_ps, cut_ps)
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            keep = [e for e in line.events
                    if lo <= base + e.offset_ps < hi]
            del line.events[:]
            line.events.extend(keep)
    return space.SerializeToString()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out_dir")
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keep", type=float, default=0.4,
                    help="seconds of the traced window kept in the file")
    args = ap.parse_args(argv)

    import jax

    from bench.harness import device, runner, spec
    from bench.harness.trace import WINDOW_SPAN

    cell = spec.load_cell(CELL, ROOT)
    try:
        devices = device.accelerators(cell.chips)
    except device.NoAccelerator as e:
        print(f"record_pipeline_trace: {e}", file=sys.stderr)
        return 3
    device.enable_compile_cache()
    run = runner.make_run(cell, args.seed, devices,
                          spec.peaks(devices[0].device_kind, ROOT))
    built = run.driver.build(run)
    run.driver.window(run, built, 0.2)
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        counters = run.driver.window(run, built, args.seconds)
    jax.profiler.stop_trace()
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    with open(src, "rb") as f:
        raw = f.read()
    prune = _load("record_engine_trace", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "record_engine_trace.py")).prune
    os.makedirs(args.out_dir, exist_ok=True)
    out = os.path.join(args.out_dir, "v5e_pipeline_trace.xplane.pb")
    with open(out, "wb") as f:
        f.write(clip(prune(raw), args.keep))
    reader = _load("wire_exposed_share", os.path.join(
        ROOT, "bench", "metrics", "wire_exposed_share.py"))
    hlo = run.driver.program_texts(run, built)[0]
    with open(os.path.join(args.out_dir, "v5e_pipeline_trace.hlo.txt"),
              "w") as f:
        f.write(cut_hlo(hlo, reader.WIRE + reader.ENCLOSING
                        + ("custom-call",)))
    with open(os.path.join(args.out_dir, "full_step.hlo.txt"), "w") as f:
        f.write(hlo)
    print(f"record_pipeline_trace: {counters['steps']} steps, {len(raw)} "
          f"bytes recorded, {os.path.getsize(out)} kept in {out}")
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
