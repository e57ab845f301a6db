"""serve_mfu: the serving engine's share of the chip's bf16 peak.

Model operations of the work done in the traced window (each admitted
request's prefill over its image and prompt, each decoded token at its
context, attention included), over the window, over peak.  Moves
``serve_tokens_per_s``.
"""
from bench.harness import flops as F

LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    c = ctx.run.sizes
    n = 0.0
    for r in ctx.counters["served"]:
        if not r.out:
            continue
        n += F.prefill_flops(c, len(r.tokens))
        start = c.n_image_tokens + len(r.tokens)
        n += sum(F.decode_flops(c, start + j + 1)
                 for j in range(len(r.out) - 1))
    peak = ctx.run.peaks["bf16_flops_per_s"] * len(ctx.run.devices)
    return 100.0 * n / ctx.summary.window_s / peak
