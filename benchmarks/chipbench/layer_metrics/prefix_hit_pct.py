"""Prompt tokens whose prefill the prefix cache spared, as a share of all
prompt tokens of the requests finished in the window (from the handles)."""

NAME = "prefix_hit_pct"
UNIT = "%"
LAYER = "serve scheduler"
MOVES = "ttft_p50_ms"
KINDS = ("serve_closed",)


def read(ctx):
    c = ctx.result.counters
    if not c.get("prompt_tokens"):
        return None
    return 100.0 * c["hit_tokens"] / c["prompt_tokens"]
