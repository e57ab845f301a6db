"""engine.itl_p99_ms: the gap between consecutive tokens of a request,
the 99th percentile over every gap of the requests due in the traced
window, on the host's clock.  Too unsteady from run to run to bound end
to end (see ``PERF.md``).  Moves ``serve_tokens_per_s``."""
import numpy as np

from bench.harness import serve_cell

LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(ctx):
    gaps = serve_cell.latencies(ctx.counters)["gaps"]
    return float(np.percentile(gaps, 99)) if len(gaps) else None
