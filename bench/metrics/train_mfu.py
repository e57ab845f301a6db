"""train_mfu: the whole train step's share of the chips' bf16 peak.

Model operations of every step completed in the traced window, as the
cell's driver counts them (``window_flops``: forward and backward,
attention included, recomputation not counted; a pipeline cell's text
alone), over the window's host-clock length, over chips times peak.
Moves ``train_tokens_per_s``.
"""
LAYER = "train step"
MOVES = "train_tokens_per_s"
UNIT = "%"
SOURCE = "host_clock"


def read(ctx):
    n = ctx.run.driver.window_flops(ctx.run, ctx.counters)
    peak = ctx.run.peaks["bf16_flops_per_s"] * len(ctx.run.devices)
    return 100.0 * n / ctx.counters["window_s"] / peak
