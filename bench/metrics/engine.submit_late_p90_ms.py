"""engine.submit_late_p90_ms: how late the open-loop generator handed
each request to the engine after its due time, the 90th percentile over
the requests due in the window, on the host's clock.  A high reading
means the generator, not the server, set the load.  Moves
``serve_tokens_per_s``."""
import numpy as np

LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(ctx):
    served = ctx.counters["served"]
    if not served or any(getattr(r, "submit_time", None) is None
                         for r in served):
        return None
    late = [(r.submit_time - r.arrival_time) * 1e3 for r in served]
    return float(np.percentile(late, 90))
