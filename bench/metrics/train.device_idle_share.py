"""train.device_idle_share: share of the traced window in which no
operation runs on the device, averaged over the chips.  Moves
``train_tokens_per_s``."""

LAYER = "device"
MOVES = "train_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    return 100.0 * ctx.summary.idle_share()
