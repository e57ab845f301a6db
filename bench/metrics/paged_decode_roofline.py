"""paged_decode_roofline: the paged decode kernel's least time at the
chip's peaks over its device time.  Its bytes are the KV positions the
decoded tokens actually attend (K, V and their positions, every layer)
plus q and out; the operations are the scores and values.  Moves
``serve_tokens_per_s``.  Nothing to read, nothing returned."""
import sys

from bench.harness import flops as F

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"
KERNEL = "_paged_kernel"


def read(ctx):
    c, model = ctx.run.sizes, ctx.run.model
    calls = ctx.summary.kernel_ops(KERNEL)
    spent = sum(op.end - op.start for op, _ in calls) / 1e9
    if spent <= 0:
        return None
    fl = by = 0.0
    for r in ctx.counters["served"]:
        start = c.n_image_tokens + len(r.tokens)
        for j in range(max(len(r.out) - 1, 0)):
            w = model.paged_decode(c, start + j + 1)
            fl += w["flops"]
            by += w["bytes"]
    r = F.roofline_share(fl, by, spent, ctx.run.peaks)
    print(f"bench: paged_decode_roofline bound by {r['bound']}",
          file=sys.stderr)
    return r["share"]
