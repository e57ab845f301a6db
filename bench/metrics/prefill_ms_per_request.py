"""prefill_ms_per_request: device time of the prefill programs (the
prefill forward and the scatter into the paged pool) in the traced
window, over the requests admitted in it.  Moves ``serve_tokens_per_s``."""

LAYER = "prefill"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "device_trace"
PROGRAMS = ("_prefill",)


def read(ctx):
    runs = [m for p in PROGRAMS for m in ctx.summary.module_runs(p)]
    admitted = ctx.counters["stats"].get("admitted", 0)
    if not runs or not admitted:
        return None
    return sum(m.end - m.start for m in runs) / 1e6 / admitted
