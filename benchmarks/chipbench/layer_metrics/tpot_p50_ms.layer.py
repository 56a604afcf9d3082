"""The median over the window's requests of a request's own time per output
token ((last token - first token) / (tokens - 1), host clock of the traced
run). A request's value depends on where its last, partial decode chunk ends
and on how many prefills of the other caller fell into its stream, so the
median over a window's fifty to two hundred requests moves by some percent
with the order the seed gives them; the end-to-end ``tpot_mean_ms`` sums over
all of them instead."""

NAME = "tpot_p50_ms.layer"
UNIT = "ms"
LAYER = "serve scheduler"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    if ctx.rehearse:
        return None
    return ctx.result.end_to_end.get("tpot_p50_ms")
