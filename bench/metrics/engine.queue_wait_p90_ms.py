"""engine.queue_wait_p90_ms: from each request's due time to its
admission into a decode slot, the 90th percentile over the requests due
in the window (one never admitted counts its wait to the close), on the
host's clock.  Moves ``serve_tokens_per_s``."""
import numpy as np

LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(ctx):
    served, closed = ctx.counters["served"], ctx.counters["closed"]
    if not served or not all(hasattr(r, "admit_time") for r in served):
        return None
    waits = [((closed if r.admit_time is None else r.admit_time)
              - r.arrival_time) * 1e3 for r in served]
    return float(np.percentile(waits, 90))
