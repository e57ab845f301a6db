"""decode_tick_ms: mean device time of one decode tick program in the
traced window.  Moves ``serve_tokens_per_s``."""

LAYER = "decode tick"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "device_trace"
PROGRAM = "paged_step"


def read(ctx):
    runs = ctx.summary.module_runs(PROGRAM)
    if not runs:
        return None
    return sum(m.end - m.start for m in runs) / 1e6 / len(runs)
