"""train_mfu: the whole train step's share of the chips' bf16 peak.

Model operations of every step completed in the traced window (forward
and backward, attention included, recomputation not counted), over the
window's host-clock length, over chips times peak.  Moves
``train_tokens_per_s``.
"""
from bench.harness import flops as F

LAYER = "train step"
MOVES = "train_tokens_per_s"
UNIT = "%"
SOURCE = "host_clock"


def read(ctx):
    c, cell = ctx.run.sizes, ctx.run.cell
    n = ctx.counters["steps"] * F.train_step_flops(
        c, int(cell.params["batch"]), int(cell.traffic["seq_len"]))
    peak = ctx.run.peaks["bf16_flops_per_s"] * len(ctx.run.devices)
    return 100.0 * n / ctx.counters["window_s"] / peak
