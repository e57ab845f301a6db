"""serve_mfu: the serving engine's share of the chip's bf16 peak.

Model operations of the work done in the traced window (each admitted
request's prefill over its image and prompt, each decoded token at its
context, attention included), over the window, over peak.  Moves
``serve_tokens_per_s``.
"""
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    n = ctx.run.driver.window_flops(ctx.run, ctx.counters)
    peak = ctx.run.peaks["bf16_flops_per_s"] * len(ctx.run.devices)
    return 100.0 * n / ctx.summary.window_s / peak
