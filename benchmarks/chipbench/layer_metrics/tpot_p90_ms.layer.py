"""The 90th percentile over the window's requests of a request's own time per
output token (host clock of the traced run): the short answers, whose one
partial decode chunk is spread over few tokens, and the streams most held up
by the other caller's prefills. Five to twenty requests lie beyond it, so it
is recorded and not held to a bound; ``delivery_gap_p90_ms`` is the tail that
is."""

NAME = "tpot_p90_ms.layer"
UNIT = "ms"
LAYER = "serve scheduler"
MOVES = "delivery_gap_p90_ms"
KINDS = ("serve_closed",)


def read(ctx):
    if ctx.rehearse:
        return None
    return ctx.result.end_to_end.get("tpot_p90_ms")
