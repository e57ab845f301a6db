"""decode_mfu: the decode tick's share of the chip's bf16 peak: model
operations of every token decoded in the traced window (weights and
attention at its context) over the device time of the tick programs.
The whole-step share that bounds a claim on the tick's kernels.  Moves
``serve_tokens_per_s``."""
LAYER = "decode tick"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"
PROGRAM = "paged_step"


def read(ctx):
    c, model = ctx.run.sizes, ctx.run.model
    runs = ctx.summary.module_runs(PROGRAM)
    if not runs:
        return None
    n = 0.0
    for r in ctx.counters["served"]:
        start = c.n_image_tokens + len(r.tokens)
        n += sum(model.decode_flops(c, start + j + 1)
                 for j in range(max(len(r.out) - 1, 0)))
    busy = sum(m.end - m.start for m in runs) / 1e9
    return 100.0 * n / busy / ctx.run.peaks["bf16_flops_per_s"]
