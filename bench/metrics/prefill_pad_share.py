"""prefill_pad_share: share of the positions the prefill computed in the
window that hold no image or prompt token: padding to a power of two in
pages and in rows (``ServeEngine.stats``: 1 - prefill_real_positions /
prefill_positions).  Moves ``serve_tokens_per_s``."""

LAYER = "prefill"
MOVES = "serve_tokens_per_s"
UNIT = "%"
SOURCE = "program_counter"


def read(ctx):
    st = ctx.counters["stats"]
    pos, real = st.get("prefill_positions"), st.get("prefill_real_positions")
    if not pos or real is None:
        return None
    return 100.0 * (1.0 - real / pos)
