"""The absorbed latent attention's share of its roofline in one-token decode
steps, its bytes and operations taken from the functions the CONFIGURATION
names (``shapes.latent_attn_bytes(live tokens, slots, model)`` and
``shapes.latent_attn_flops(live tokens, slots, model)``, resolved as
``model_builder`` is): the least time the chip could take for a step's
attention over the rows that are LIVE (the window's mean of the tokens in the
slots' caches, the driver's own count; the published 576-wide mathematics for
the operations, the rows as stored for the bytes), the larger of bytes over
peak bytes/s and operations over peak FLOP/s, over the device time a step
spends under the scope ``attn.latent``. What the program walks beyond the live
rows (whole blocks up to the batch's longest sequence, for every slot) is time
it spends and no work it has to do, so it lowers the share. ``None`` for a
configuration that names no such functions or a program without the scope."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import registry
from benchmarks.chipbench.harness import say

NAME = "latent_attn_roofline_pct"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPE = "attn.latent"


def read(ctx):
    names = ctx.config.get("shapes") or {}
    if not ctx.on_tpu or "latent_attn_bytes" not in names \
            or "latent_attn_flops" not in names:
        return None
    t = ds.table(ctx, "decode_chunk")
    if t is None or not t.seconds(SCOPE):
        return None
    spent = ds.ms_per_step(t, SCOPE) * 1e-3
    c, model = ctx.result.counters, ctx.config["model"]
    slots, peaks = int(ctx.config["serve"]["slots"]), ctx.peaks()
    live = float(c["live_tokens_mean"])
    by_bytes = registry.resolve(names["latent_attn_bytes"])(live, slots, model) \
        / peaks["hbm_bytes_per_s"]
    by_flops = registry.resolve(names["latent_attn_flops"])(live, slots, model) \
        / peaks["bf16_flops_per_s"]
    chunks = [sp for sp in ps.named(ps.in_window(ctx), "serving.decode_chunk")
              if "attn_rows" in sp.stats]
    walked = slots * ps.total(chunks, "attn_rows") / len(chunks) if chunks else 0.0
    say(f"{SCOPE} ({names['latent_attn_bytes']}): {spent * 1e3:.3f} ms a step over "
        f"{live:.0f} live rows a layer (the walk reaches {walked:.0f}); least "
        f"{by_bytes * 1e3:.3f} ms by bytes, {by_flops * 1e3:.3f} ms by operations")
    return 100.0 * max(by_bytes, by_flops) / spent
