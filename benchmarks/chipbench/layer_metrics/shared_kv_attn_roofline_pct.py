"""The shared-cache attention's share of its roofline in one-token decode
steps, its bytes and operations taken from the functions the CONFIGURATION
names (``shapes.shared_attn_bytes(live tokens, slots, model)`` and
``shapes.shared_attn_flops(live tokens, slots, model)``, resolved as
``model_builder`` is): the least time the chip could take to read the rows that
are LIVE (the window's mean of the tokens in the slots' caches, the driver's
own count) once a reading layer, the larger of bytes over peak bytes/s and
operations over peak FLOP/s, over the device time a step spends under the
scope ``attn.shared``. What the program reads beyond the live rows (whole
blocks of keys, a padded lane) is time it spends and no work it has to do, so
it lowers the share. ``None`` for a configuration that names no such functions
or a program without the scope."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench import registry
from benchmarks.chipbench.harness import say

NAME = "shared_kv_attn_roofline_pct"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPE = "attn.shared"


def read(ctx):
    names = ctx.config.get("shapes") or {}
    if not ctx.on_tpu or "shared_attn_bytes" not in names \
            or "shared_attn_flops" not in names:
        return None
    t = ds.table(ctx, "decode_chunk")
    if t is None or not t.seconds(SCOPE):
        return None
    spent = ds.ms_per_step(t, SCOPE) * 1e-3
    c, model = ctx.result.counters, ctx.config["model"]
    slots, peaks = int(ctx.config["serve"]["slots"]), ctx.peaks()
    live = float(c["live_tokens_mean"])
    by_bytes = registry.resolve(names["shared_attn_bytes"])(live, slots, model) \
        / peaks["hbm_bytes_per_s"]
    by_flops = registry.resolve(names["shared_attn_flops"])(live, slots, model) \
        / peaks["bf16_flops_per_s"]
    say(f"{SCOPE} ({names['shared_attn_bytes']}): {spent * 1e3:.3f} ms a step over "
        f"{live:.0f} live rows a reading layer; least {by_bytes * 1e3:.3f} ms by "
        f"bytes, {by_flops * 1e3:.3f} ms by operations")
    return 100.0 * max(by_bytes, by_flops) / spent
