"""The height of the expert kernel's tiles follows the rows an expert is
expected to get (PR 60): ``tile_rows(A, E)`` over the shapes the benchmark's
serving cells run, and the layer at 128-row tiles against the ``jax.numpy``
form and against itself at 32-row tiles (interpret mode here)."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.moe import grouped_ffn as g


def _former_height(assignments: int) -> int:
    """``tile_rows`` as it was up to PR 59: keyed on the assignments alone."""
    return 16 if assignments <= 2048 else 32


def _expert_cells():
    """``{cell: (model configuration, slots, prompt buckets, buckets the
    cell's traffic reaches)}`` for the serving cells of ``BENCHMARK.json``
    whose model has expert layers; the count of serving cells beside it. A
    bucket is reached by the warm-up's whole-bucket prompts and by the cycle
    of the traffic's fixed requests."""
    from benchmarks.chipbench import registry
    from benchmarks.chipbench.lengths import fixed_requests
    from deepspeed_tpu.inference.serving.executor import prompt_buckets
    bench = registry.load_benchmark()
    dirs = registry.search_dirs(bench)
    cells, serving = {}, 0
    for cell in bench["workloads"]:
        with open(registry.config_file_of(bench, cell["config"])) as fh:
            config = json.load(fh)
        if "serve" not in config:
            continue
        serving += 1
        cap = int(config["serve"]["max_seq_len"])
        cfg = registry.resolve(config["model_builder"])(max_seq_len=cap,
                                                        **config["model"])
        if "E" not in cfg.layer_kinds:
            continue
        traffic = registry.load_json("traffic", cell["traffic"], dirs)
        buckets = prompt_buckets(cap - 1)
        prompts = [int(n) for n in traffic["parity_prompts"]] \
            + [p for p, _ in fixed_requests(traffic, cap)]
        reached = {min(b for b in buckets if b >= p) for p in prompts}
        cells[cell["name"]] = (cfg, int(config["serve"]["slots"]), buckets, reached)
    return cells, serving


_CELLS, _SERVING = _expert_cells()
_SHAPES = [(cell, "decode", 0) for cell in _CELLS] + [
    (cell, "reached" if b in reached else "unreached", b)
    for cell, (_, _, buckets, reached) in _CELLS.items() for b in buckets]
# the programs whose tiles are higher than the parent's: the claimed cell's one
# prefill, and buckets that no cell's prompts reach
_TALLER = {("sarvam-105b.doc4k32", "reached", 4096),
           ("sarvam-105b.doc4k32", "unreached", 2048),
           ("sarvam-105b.doc4k32", "unreached", 6143),
           ("granite-4.0-h-small.conv32", "unreached", 1024),
           ("granite-4.0-h-small.conv32", "unreached", 2047),
           ("lfm2-8b-a1b.conv32", "unreached", 1024),
           ("lfm2-8b-a1b.conv32", "unreached", 2047)}


def test_five_of_the_nine_serving_cells_have_expert_layers():
    assert _SERVING == 9 and sorted(c.rsplit(".", 1)[0] for c in _CELLS) == [
        "granite-4.0-h-small", "lfm2-8b-a1b", "nemotron-3-super-120b-a12b",
        "sarvam-105b", "sdar-30b-a3b-chat"]
    assert {c: sorted(r) for c, (_, _, _, r) in _CELLS.items()} == {
        "nemotron-3-super-120b-a12b.conv32": [64, 128, 256, 512],
        "sdar-30b-a3b-chat.conv32": [64, 128, 256, 512],
        "lfm2-8b-a1b.conv32": [64, 128, 256, 512],
        "granite-4.0-h-small.conv32": [64, 128, 256, 512],
        "sarvam-105b.doc4k32": [512, 4096]}


@pytest.mark.parametrize("cell,what,tokens", _SHAPES,
                         ids=[f"{c}-{w}-{t}" for c, w, t in _SHAPES])
def test_only_the_long_prefill_gets_taller_tiles(cell, what, tokens):
    """``tile_rows`` / ``plan_rows`` at the ``(A, E)`` of every program a
    serving cell with experts can build: the parent's height everywhere but
    sarvam's 4,096-token prefill (256 rows an expert), and buckets no cell's
    prompts reach; a decode step keeps 16 rows whatever the router's width."""
    cfg, slots, _, _ = _CELLS[cell]
    if what == "decode":
        tokens = slots * max(1, 2 * int(cfg.gen_block_length))
    A, E, held = tokens * cfg.experts_per_token, cfg.n_routed_experts, \
        cfg.held_experts[1]
    tm = g.tile_rows(A, E)
    assert g.plan_rows(A, held, E) == (A // tm + min(held, A)) * tm
    if (cell, what, tokens) in _TALLER:
        assert tm == 128 > _former_height(A) and A / E >= g.TALL_TILES_FROM
    else:
        assert tm == _former_height(A), (A, E, A / E)
        assert what != "decode" or tm == 16
    # no cell's reached program is changed but the claimed one
    if what != "unreached" and tm != _former_height(A):
        assert (cell, tokens) == ("sarvam-105b.doc4k32", 4096)


def test_the_height_is_decided_by_the_assignments_and_the_routers_width_alone():
    assert g.TALL_TILES_FROM > 71                   # granite-small's 512 bucket
    for A in (128, 704, 2048):
        assert {g.tile_rows(A, E) for E in (1, 8, 128, 512)} == {16}
    assert g.tile_rows(32768, 128) == 128 and g.tile_rows(32768, 512) == 32
    assert g.tile_rows(11264, 512) == 32 and g.tile_rows(4096, 128) == 32
    assert g.tile_rows(g.TALL_TILES_FROM * 72, 72) == 128
    assert g.tile_rows(g.TALL_TILES_FROM * 72 - 1, 72) == 32


# ------------------------------------------------------- the layer at 128-row tiles
ROWS = (0, 1, 127, 128, 129, 300)       # what each held expert gets


def _layer(dtype=jnp.float32, l=128, f=256):
    """Six held experts of ten, given exactly ``ROWS`` rows; every token's
    other three choices fall on the four experts held elsewhere."""
    rng = np.random.RandomState(5)
    first = np.repeat(np.arange(len(ROWS)), ROWS)
    rng.shuffle(first)
    T = first.size
    others = np.stack([rng.permutation(4)[:3] + len(ROWS) for _ in range(T)])
    idx = np.concatenate([first[:, None], others], axis=1).astype(np.int32)
    idx = np.take_along_axis(idx, np.stack([rng.permutation(4) for _ in range(T)]), 1)
    x = jnp.asarray(rng.standard_normal((T, l)), dtype)
    w = jnp.asarray(rng.rand(T, 4), jnp.float32)
    w1, wg = (jnp.asarray(rng.standard_normal((len(ROWS), l, f)) * 0.1, dtype)
              for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((len(ROWS), f, l)) * 0.1, dtype)
    return x, jnp.asarray(idx), w, w1, w2, wg


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("blocks", [1, 2])
def test_the_layer_at_128_row_tiles_is_the_layer_at_32(blocks, gated, monkeypatch):
    """``grouped_experts`` at the height ``tile_rows`` gives 274 rows an
    expert against the ``jax.numpy`` form on the same plan and against
    itself at 32-row tiles, experts with 0, 1, 127, 128, 129 and 300 rows
    (none, a row in a tile, a row short of a tile, a whole tile, a row over,
    two tiles and a part), whole and cut in two over the experts' width:
    equal to float32 rounding (a row's result does not depend on the tile it
    rides in), the same counts."""
    x, idx, w, w1, w2, wg = _layer()
    wg = wg if gated else None
    count, experts = len(ROWS), 10
    assert g.tile_rows(idx.size, experts) == 128
    monkeypatch.setattr(g, "width_blocks", lambda *a: blocks)
    layer = functools.partial(g.grouped_experts, x, idx, w, 0, count, experts, w1, w2,
                              jax.nn.silu, None, wg)
    tall, stats = layer()
    assert [int(v) for v in stats] == [sum(ROWS), sum(r > 0 for r in ROWS)]
    scale = float(jnp.abs(tall).max())
    assert scale > 0.1
    with monkeypatch.context() as m:
        m.setattr(g, "tile_rows", lambda *a: 32)
        short, short_stats = layer()
    assert np.array_equal(stats, short_stats)
    assert float(jnp.abs(tall - short).max()) < 1e-5 * scale
    with monkeypatch.context() as m:
        m.setattr(g, "grouped_ffn", g.grouped_ffn_xla)
        want, _ = layer()
    assert float(jnp.abs(tall - want).max()) < 1e-5 * scale


@pytest.mark.parametrize("blocks", [1, 2])
def test_a_128_row_tile_past_the_last_real_one_is_not_written(blocks, monkeypatch):
    """The kernel at 128-row tiles: an expert's rows fill whole tiles and a
    part (0 -> none, 1 and 127 and 128 -> one, 129 -> two, 300 -> three), the
    live tiles hold what the ``jax.numpy`` form gives and the tiles past the
    last real one are left as they were (interpret mode leaves NaN there)."""
    x, idx, _, w1, w2, wg = _layer()
    tm = 128
    plan = g.dispatch_plan(idx, 0, len(ROWS), tm)
    te, tv = np.asarray(plan["tile_expert"]), np.asarray(plan["tile_valid"])
    assert tv.shape == (idx.size // tm + len(ROWS),) and tv.sum() == 8
    assert list(te[:8]) == [1, 2, 3, 4, 4, 5, 5, 5] and (te[8:] == 5).all()
    x_rows = x[plan["row_token"]]
    monkeypatch.setattr(g, "width_blocks", lambda *a: blocks)
    got = np.asarray(g.grouped_ffn(x_rows, plan["tile_expert"], plan["tile_valid"],
                                   w1, w2, jax.nn.silu, tm, wg))
    want = np.asarray(g.grouped_ffn_xla(x_rows, plan["tile_expert"],
                                        plan["tile_valid"], w1, w2, jax.nn.silu, tm, wg))
    live = np.repeat(tv == 1, tm)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    assert np.isnan(got[~live]).all()
