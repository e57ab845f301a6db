"""engine.ttft_p90_ms: from each request's due time to its first token,
the 90th percentile over every request due in the traced window (one
never answered counts its wait to the close), on the host's clock.  The
tail users feel; too unsteady from run to run to bound end to end (see
``PERF.md``).  Moves ``serve_tokens_per_s``."""
import numpy as np

from bench.harness import serve_cell

LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(ctx):
    ttft = serve_cell.latencies(ctx.counters)["ttft"]
    return float(np.percentile(ttft, 90)) if len(ttft) else None
