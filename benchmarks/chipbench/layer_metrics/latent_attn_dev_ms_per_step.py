"""Device milliseconds a decode step spends in the scope ``attn.latent``, over
all layers: a latent-attention layer's ABSORBED one-token attention (the
queries taken into the latent, scores and values over the cached rows, the
value expansion). The row's append and the rotations are ``kv.append`` and
``attn.heads`` (``decode_attn_dev_ms_per_step``). ``None`` for a program that
opens no such scope."""

from benchmarks.chipbench import device_scopes as ds

NAME = "latent_attn_dev_ms_per_step"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPE = "attn.latent"


def read(ctx):
    t = ds.table(ctx, "decode_chunk")
    if t is None or not t.seconds(SCOPE):
        return None
    return ds.ms_per_step(t, SCOPE)
