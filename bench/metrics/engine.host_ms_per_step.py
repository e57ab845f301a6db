"""engine.host_ms_per_step: the mean over the ``engine.step`` spans in
the traced window of their length less that of their ``*.fetch`` spans:
the host's own time in one engine step.  Moves ``serve_tokens_per_s``."""
from bench.harness import engine_spans as S

LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
UNIT = "ms"
SOURCE = "program_span"


def read(ctx):
    return S.host_ms_per_step(ctx.summary)
