"""flash_attention_roofline: the flash attention kernels' (forward, dq,
dkv) least time at the chip's peaks, from each call's own operations and
bytes (``bench/harness/flops.py``), over their summed device time.
Moves ``train_tokens_per_s``.  Nothing to read, nothing returned."""
import sys

from bench.harness import flops as F

LAYER = "kernels"
MOVES = "train_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"
KERNELS = {"_fwd_kernel": F.flash_fwd,
           "_dq_kernel": F.flash_dq,
           "_dkv_kernel": F.flash_dkv}


def read(ctx):
    peaks = ctx.run.peaks
    least = spent = 0.0
    bound = {}
    for kernel, count in KERNELS.items():
        for op, k in ctx.summary.kernel_ops(kernel):
            q, kv = [s for s in k.operands if len(s) == 4][:2]
            b, h, s, d = q
            w = count(b, h, kv[1], s, d)
            r = F.roofline_share(w["flops"], w["bytes"], 1.0, peaks)
            least += r["share"] / 100.0
            spent += (op.end - op.start) / 1e9
            bound[r["bound"]] = bound.get(r["bound"], 0) + 1
    if spent <= 0:
        return None
    print(f"bench: flash_attention_roofline bound by {bound}",
          file=sys.stderr)
    return 100.0 * least / spent
