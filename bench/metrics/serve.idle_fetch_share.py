"""serve.idle_fetch_share: share of the traced window in which no
operation runs on the device while the innermost engine span open is a
``*.fetch`` (``engine.prefill.fetch``, ``engine.tick.fetch``): the host
waits for a program and copies its logits back.  Split by overlap
(``bench/harness/engine_spans.py``).  Moves ``serve_tokens_per_s``."""
import sys

from bench.harness import engine_spans as S

LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    idle = S.idle_by_span(ctx.summary)
    if idle is None:
        return None
    offsets = S.clock_offsets_ms(ctx.summary)
    if offsets:
        print(f"bench: device less host clock, over {len(offsets)} ticks: "
              f"at least {max(lo for lo, _ in offsets):.4f} ms at one, at "
              f"most {min(hi for _, hi in offsets):.4f} ms at one",
              file=sys.stderr)
    fetch = sum(v for k, v in idle.items() if k in S.FETCHES)
    return 100.0 * fetch / ctx.summary.window_s
