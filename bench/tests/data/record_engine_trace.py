#!/usr/bin/env python3
"""Record a small TPU trace of the serving engine's own spans, to check
their reading against a real device trace (needs a chip).

    python3 bench/tests/data/record_engine_trace.py <out_dir> [--ticks N]

On one TPU chip: the engine of the steady serving cell
(``tinyllava-serve-split2b-steady``: its configuration, slots and page
size, seeded weights, split-serve wire) warms one wave of two requests,
then serves a second such wave inside a ``bench.window`` host span: one
prefill and ``--ticks`` decode ticks.  The trace keeps, of the host, the
``engine.*`` and ``bench.*`` spans and, of the device, the names and
times of its ops and program runs (``trace.OP_LINES``,
``trace.MODULE_LINES``); the rest is pruned to keep the file small.  Writes ``v5e_engine_trace.xplane.pb``
to ``out_dir``.
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

CELL = "tinyllava-serve-split2b-steady"
PROMPT = 48
KEEP_SPANS = ("engine.", "bench.")


def _xplane_pb2():
    """The XPlane protobuf module, loaded from its file so that the
    package that ships it is not initialised."""
    found = importlib.util.find_spec("tensorflow")
    path = os.path.join(os.path.dirname(found.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prune(raw: bytes) -> bytes:
    """Keep the host planes' spans named ``engine.*`` or ``bench.*`` and
    the device planes' op and program lines, of which only names and
    times (a device event's stats, such as its source stack, go)."""
    from bench.harness import trace as T

    pb = _xplane_pb2()
    space = pb.XSpace.FromString(raw)
    for plane in space.planes:
        host = plane.name.startswith("/host:")
        device = T._device_index(plane.name) is not None
        names = {k: m.name for k, m in plane.event_metadata.items()}
        lines = []
        for line in plane.lines:
            if not (host or (device and line.name in T.OP_LINES
                             + T.MODULE_LINES)):
                continue
            new = pb.XLine()
            new.CopyFrom(line)
            if host:
                del new.events[:]
                new.events.extend(e for e in line.events
                                  if names[e.metadata_id].startswith(
                                      KEEP_SPANS))
            else:
                for e in new.events:
                    del e.stats[:]
            if new.events:
                lines.append(new)
        del plane.lines[:]
        plane.lines.extend(lines)
        used = {e.metadata_id for line in plane.lines for e in line.events}
        for mid in [m for m in plane.event_metadata if m not in used]:
            del plane.event_metadata[mid]
        if device:
            for m in plane.event_metadata.values():
                del m.stats[:]
    return space.SerializeToString()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out_dir")
    ap.add_argument("--ticks", type=int, default=3)
    args = ap.parse_args(argv)

    import jax

    from bench.harness import spec, traffic, weights as W
    from bench.harness.trace import WINDOW_SPAN
    from repro.serve.engine import ServeEngine

    if jax.devices()[0].platform != "tpu":
        print("record_engine_trace: needs a TPU", file=sys.stderr)
        return 3
    cell = spec.load_cell(CELL, ROOT)
    c, cfg = spec.sizes(cell.config), spec.arch_config(cell.config)
    key = W.base_key(0)
    images = traffic.image_pool(cell.traffic, c, W.sub_key(key, 4))
    page, slots = int(cell.params["page_size"]), int(cell.params["n_slots"])
    longest = c.n_image_tokens + PROMPT + args.ticks + 1
    eng = ServeEngine(W.init_params(c, key), cfg, n_slots=slots,
                      page_size=page,
                      n_pages=1 + 2 * -(-longest // page),
                      split_wire=cfg.split.quant)

    def wave():
        for i in range(2):
            eng.submit([1 + i] * PROMPT, max_new=args.ticks + 1,
                       image_embeds=images[i])
        eng.run()

    wave()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        wave()
    jax.profiler.stop_trace()
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    with open(src, "rb") as f:
        raw = f.read()
    os.makedirs(args.out_dir, exist_ok=True)
    out = os.path.join(args.out_dir, "v5e_engine_trace.xplane.pb")
    with open(out, "wb") as f:
        f.write(prune(raw))
    print(f"record_engine_trace: {len(raw)} bytes recorded, "
          f"{os.path.getsize(out)} kept in {out}")
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
