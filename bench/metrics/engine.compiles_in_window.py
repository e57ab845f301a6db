"""engine.compiles_in_window: programs the process lowered in the window
(a jit cache miss, whether or not the persistent cache then held the
program; ``ServeEngine.stats["compiles"]``).  Set-up warms every shape,
so it should read 0.  Moves ``serve_tokens_per_s``."""

LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
UNIT = "count"
SOURCE = "program_counter"


def read(ctx):
    return ctx.counters["stats"].get("compiles")
