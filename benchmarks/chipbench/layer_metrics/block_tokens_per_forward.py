"""Tokens a slot keeps a forward, for a model that generates by diffusion
over blocks: sum ``tokens_kept`` over sum ``forwards x active_slots`` of the
traced ``serving.decode_chunk`` spans. A block of ``B`` unmasked one position
a forward and committed by one more gives ``B / (B + 1)``; a last block cut
to the tokens asked, a first block the prompt opened and a chunk that ends
inside a block bring it down, a strategy that unmasks several positions a
forward brings it up. An earlier line gives the blocks committed and the
positions unmasked a forward. A program without these attributes (a commit
before generation by blocks) gives ``None``."""

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench.harness import say

NAME = "block_tokens_per_forward"
UNIT = "tokens"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    chunks = [sp for sp in ps.named(ps.in_window(ctx), "serving.decode_chunk")
              if "forwards" in sp.stats]
    if not chunks:
        return None
    slot_forwards = sum(float(sp.stats["forwards"]) * float(sp.stats["active_slots"])
                        for sp in chunks)
    if not slot_forwards:
        return None
    forwards = ps.total(chunks, "forwards")
    say(f"generation by blocks of {int(float(chunks[0].stats['block_length']))}, "
        f"traced window: {len(chunks)} chunks, {forwards:.0f} forwards, "
        f"{ps.total(chunks, 'blocks_committed') / forwards:.2f} blocks committed and "
        f"{ps.total(chunks, 'positions_unmasked') / forwards:.2f} positions unmasked a "
        f"forward over all slots, {ps.total(chunks, 'tokens_kept') / forwards:.2f} tokens "
        "kept a forward over all slots")
    return ps.total(chunks, "tokens_kept") / slot_forwards
