"""serve.idle_host_share: share of the traced window in which no
operation runs on the device while the innermost engine span open is
any other than a ``*.fetch``: the host admits, builds inputs, runs the
wire's eager code, dispatches, picks or emits (``engine.step``'s own
time included).  Split by overlap (``bench/harness/engine_spans.py``).
Moves ``serve_tokens_per_s``."""
from bench.harness import engine_spans as S

LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    idle = S.idle_by_span(ctx.summary)
    if idle is None:
        return None
    host = sum(v for k, v in idle.items() if k not in S.FETCHES)
    return 100.0 * host / ctx.summary.window_s
