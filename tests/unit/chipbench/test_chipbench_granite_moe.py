"""granite-4.0-h-small's benchmark files: its configuration against the catalog
row it copies and the cut it states, the yardstick's arithmetic
(``granite_moe_shapes.py``) against the program's own count at tiny and at
published sizes, its three readers on a synthesised trace (and ``None`` where
the program has no such counts, kernel or scope, or the configuration names
no ``shapes``), the labelled CPU rehearsal of its cell, and the float8 probe
of its logit limit at a small width on both paths."""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks.chipbench import device_scopes as ds  # noqa: E402
from benchmarks.chipbench import granite_moe_shapes as ms  # noqa: E402
from benchmarks.chipbench import granite_shapes as gs  # noqa: E402
from benchmarks.chipbench import hybrid_trace as ht  # noqa: E402
from benchmarks.chipbench import program_spans as ps  # noqa: E402
from benchmarks.chipbench import registry  # noqa: E402
from benchmarks.chipbench import trace_reduce as tr  # noqa: E402
from test_chipbench_hybrid import _record, rounded_matrices  # noqa: E402

BENCH = registry.load_benchmark(REPO)
DIRS = registry.search_dirs(BENCH, REPO)
CONFIG = "granite-4.0-h-small"
CELL = "granite-4.0-h-small.conv32"
READERS = {      # name -> (unit, layer, moves), as each file declares itself
    "granite_moe_ffn_roofline_pct": ("%", "kernels", "tpot_mean_ms"),
    "granite_moe_decode_hbm_roofline_pct": ("%", "compiled steps", "tpot_mean_ms"),
    "moe_shared_dev_ms_per_step": ("ms", "compiled steps", "tpot_mean_ms"),
}
PEAKS = {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9}
SCOPED_DECODE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                             "decode_tiny_scoped.xplane.pb.gz")
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}


def _doc():
    with open(registry.config_file_of(BENCH, CONFIG, REPO)) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_number_and_states_its_cut():
    doc = _doc()
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == doc["reduced"] == ["num_hidden_layers", "num_local_experts"]
    assert entry["source"] == doc["source"] and entry["file"].endswith(CONFIG + ".json")
    for key, value in CATALOG.items():
        if key in doc["reduced"]:
            assert doc[key] != value and doc["published"][key] == value, key
        else:
            assert doc[key] == value, key
    assert (doc["num_hidden_layers"], doc["num_local_experts"]) == (10, 36)
    m = doc["model"]
    # the stage: one whole period, attention sixth; the router keeps its width
    assert gs.mixers(m) == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (m["num_local_experts"], m["num_experts_per_tok"], m["experts_held"]) == \
        (72, 10, [0, 36])
    for key, value in m.items():             # the builder's keywords: the published ones
        if key in CATALOG and key not in ("num_hidden_layers", "layer_types"):
            assert value == CATALOG[key], key
    assert m["layer_types"] == CATALOG["layer_types"][:m["num_hidden_layers"]]
    assert "level_random_experts" not in m and "init_std" not in m
    assert m["greedy_decode_rows"] == doc["serve"]["slots"] == 32
    s = doc["serve"]
    assert (s["dtype"], s["max_seq_len"], s["chunk_size"], s["kv_page_size"],
            s["max_queue"]) == ("bfloat16", 2048, 8, 16, 64)
    assert s["kv_total_pages"] == 32 * 128 + 1 and s["prefix_cache"] == {"enabled": False}
    for item in ("ssm_state_dtype", "init", "time_step_limit", "head_dim", "expert_width",
                 "input_linear", "greedy_decode_rows"):
        assert len(doc["assumed"][item]) > 40, item
    assert doc["chips"] == 1 and "one chip of 8" in doc["deployment"]
    assert "NOTHING stands in" in doc["deployment"]
    assert doc["routes"] == {"decode_chunk": ["decode_attention", "moe_grouped_ffn"],
                             "prefill_flash_from": 256}
    for key in ("memory_arithmetic", "routes_note", "serve_note", "shapes_note"):
        assert len(doc[key]) > 40, key
    assert doc["reference"]["module"] == "granite_moe_hybrid"
    # no width differs in the rehearsal's file from what the tiny tests use
    assert set(doc["rehearsal"]["model"]) <= set(m)
    from tests.unit import granite_moe_tiny as gm
    for key, value in doc["rehearsal"]["model"].items():
        if key not in ("vocab_size", "greedy_decode_rows", "experts_held"):
            assert gm.MODEL[key] == value, key


@pytest.mark.parametrize("size", ["published", "tiny"])
def test_the_arithmetic_is_the_programs_own_count(size):
    """Shape arithmetic only: nothing is allocated at the published size."""
    from deepspeed_tpu.models.causal_lm import granite_hybrid_cfg
    doc = _doc()
    m = doc["model"] if size == "published" else {**doc["model"], **doc["rehearsal"]["model"]}
    assert ms.params(m) == granite_hybrid_cfg(max_seq_len=2048, **m).num_params()
    whole = {k: v for k, v in m.items() if k != "experts_held"}
    assert ms.params(whole) == granite_hybrid_cfg(max_seq_len=2048, **whole).num_params()
    assert ms.params(whole) - ms.params(m) == len(gs.mixers(m)) * (
        m["num_local_experts"] - m["experts_held"][1]) * ms.expert_params(m)


def test_the_arithmetic_reproduces_the_issues_numbers():
    doc = _doc()
    m = doc["model"]
    assert gs.mamba_params(m) + 4096 == 102_291_072
    assert gs.attention_params(m) + 4096 == 41_947_136
    assert ms.expert_params(m) == 9_437_184 and ms.shared_params(m) == 18_874_368
    assert ms.router_params(m) == 294_912
    assert ms.expert_layer_params(m, 36) == 358_912_000
    assert ms.params(m) == 4_962_732_672                    # 9.93 GB of bf16
    assert ms.params({**m, "num_hidden_layers": 40, "layer_types": doc["layer_types"],
                      "experts_held": None}) == 32_207_337_984
    assert gs.ssm_state_bytes_per_slot(m) == 9 * 128 * 64 * 128 * 4 == 37_748_736
    assert gs.conv_state_bytes_per_slot(m) == 9 * 3 * 8448 * 2 == 456_192
    assert gs.kv_bytes_per_token(m) == 2 * 8 * 128 * 2 == 4_096
    assert 32 * (gs.ssm_state_bytes_per_slot(m) + gs.conv_state_bytes_per_slot(m)) \
        == 32 * 38_204_928 == 1_222_557_696
    # a step that reads every held expert of every layer: "~12.4 GB", 55 % in experts
    step = ms.decode_step_bytes(m, 32, 32 * 320, 360.0)
    assert 12.3e9 < step < 12.5e9
    assert ms.moe_step_bytes(360.0, m) == 360 * 18_874_368 == 6_794_772_480
    assert 0.54 < ms.moe_step_bytes(360.0, m) / step < 0.56
    assert step == 2 * ms.params_beside_experts(m) + ms.moe_step_bytes(360.0, m) \
        + gs.ssm_update_bytes(32, m) + 2 * 32 * 456_192 + 32 * 320 * 4_096
    assert ms.params_beside_experts(m) + 360 * ms.expert_params(m) == ms.params(m)
    # the kernel: a layer's 36 experts and its ~160 rows in and out; memory-bound
    assert ms.moe_ffn_bytes(36, 160, m) == 36 * 18_874_368 + 160 * 4096 * 6
    assert ms.moe_ffn_flops(160, m) == 160 * 2 * 9_437_184
    assert ms.moe_ffn_bytes(36, 160, m) / 819e9 > 40 * ms.moe_ffn_flops(160, m) / 197e12
    assert "4,962,732,672" in doc["memory_arithmetic"]
    assert "1,222,557,696" in doc["memory_arithmetic"]
    for name in doc["shapes"].values():
        assert registry.resolve(name).__module__.endswith("granite_moe_shapes")


# ------------------------------------------------------- a synthesised trace
def _two_chunks(TA):
    import time
    for n, (assigned, touched) in enumerate([(12800, 2870), (12760, 2858)], 1):
        with TA("chipbench.step"):
            with TA("serving.decode_chunk", chunk=n, active_slots=32,
                    request_ids="1 2", slot_steps_run=256) as chunk:
                time.sleep(0.17)
                chunk.set_metadata(tokens_kept=250, deliveries=32, stalled_deliveries=1,
                                   moe_assignments=assigned, moe_experts_touched=touched)


@pytest.fixture(scope="module")
def chunk_trace(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("granite_moe"), _two_chunks)


def _ctx(path, with_device=True):
    red = tr.reduce_trace(path)
    ops, programs = [], []
    for sp in ps.named(ps.load(path), "serving.decode_chunk"):
        a = sp.start + 0.001
        programs.append(("decode_chunk", a, a + 0.160))
        # per chunk of 8 steps: 80 ms in the expert kernel, 80 ms elsewhere
        ops += [("moe_grouped_ffn.7", a, a + 0.080), ("fusion.9", a + 0.080, a + 0.160)]
    red["devices"] = [{"id": 0, "ops": ops, "asyncs": [], "programs": programs}] \
        if with_device else []
    return types.SimpleNamespace(
        trace_path=path, trace_reduced=red, on_tpu=True, config=_doc(), dirs=DIRS,
        peaks=lambda: PEAKS,
        result=types.SimpleNamespace(counters={"chunk_size": 8,
                                               "live_tokens_mean": 10240.0}))


def _reader(name):
    return registry.load_module("layer_metrics", name, DIRS)


def test_the_readers_on_two_synthetic_chunks(chunk_trace, monkeypatch, capsys):
    ctx = _ctx(chunk_trace)
    assert len(ht.decode_chunks(ctx)) == 2
    m = ctx.config["model"]
    least = (ms.moe_ffn_bytes(2870, 12800, m) + ms.moe_ffn_bytes(2858, 12760, m)) / 819.0e9
    got = _reader("granite_moe_ffn_roofline_pct").read(ctx)
    assert got == pytest.approx(100.0 * least / 0.160, rel=1e-6) and 80.0 < got < 105.0
    touched = (2870 + 2858) / 16
    need = ms.decode_step_bytes(m, 32, 10240.0, touched)
    assert _reader("granite_moe_decode_hbm_roofline_pct").read(ctx) == pytest.approx(
        100.0 * need / 819.0e9 / 0.020, rel=1e-6)
    out = capsys.readouterr().out
    assert "bound by ['memory']" in out and "granite_moe_shapes:moe_ffn_bytes" in out
    assert "in 358.0 touched experts" in out and "granite_moe_shapes:decode_step_bytes" in out
    # the shared expert by its scope: 16 steps in two chunks, 8 ms in moe.shared
    table = ds.Table("decode_chunk", 2, 16.0, {
        ("moe.shared", "forward"): [0.008, 480, 0.0, 0.0],
        ("moe.experts", "forward"): [0.16, 160, 0.0, 0.0]}, {})
    monkeypatch.setattr(ds, "table", lambda ctx, program: table)
    assert _reader("moe_shared_dev_ms_per_step").read(ctx) == pytest.approx(0.5)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_none_on_a_program_without_what_it_reads(name, tmp_path):
    """The parent commit cannot build this configuration, and the driver lays
    these files over it: on the parent's programs and configurations each
    reader returns nothing and does not raise. A program whose chunk spans
    carry no expert counts and whose chunks hold no expert kernel; another
    configuration's file (none names ``shapes``); no device plane; a recorded
    trace of a scoped program without ``moe.shared``; untraced; on the CPU."""
    import time

    def old_program(TA):
        with TA("chipbench.step"):
            with TA("serving.decode_chunk", chunk=1, active_slots=2,
                    request_ids="1 2", slot_steps_run=16) as chunk:
                time.sleep(0.005)
                chunk.set_metadata(tokens_kept=12, deliveries=2, stalled_deliveries=0)

    path = _record(tmp_path, old_program)
    ctx = _ctx(path)
    for dev in ctx.trace_reduced["devices"]:
        dev["ops"] = [("fusion.1", s, e) for _, s, e in dev["ops"]]
    assert _reader(name).read(ctx) is None            # this file, no counts, no kernel
    assert _reader(name).read(_ctx(path, with_device=False)) is None
    others = {}
    for other in ("bloom-7b1", "nemotron-3-super-120b-a12b", "sdar-30b-a3b-chat",
                  "lfm2-8b-a1b", "granite-4.0-h-micro"):
        with open(os.path.join(REPO, "benchmarks", "chipbench", "configs",
                               other + ".json")) as f:
            others[other] = json.load(f)
        assert "shapes" not in others[other]
        ctx.config = others[other]
        assert _reader(name).read(ctx) is None, other
    recorded = types.SimpleNamespace(
        trace_path=SCOPED_DECODE, trace_reduced=tr.reduce_trace(SCOPED_DECODE),
        on_tpu=True, config=others["bloom-7b1"], dirs=DIRS, peaks=lambda: PEAKS,
        result=types.SimpleNamespace(counters={"chunk_size": 4, "live_tokens_mean": 40.0}))
    assert ds.table(recorded, "decode_chunk") is not None     # scoped, but no moe.shared
    assert _reader(name).read(recorded) is None
    untraced = types.SimpleNamespace(
        trace_path=None, trace_reduced=None, on_tpu=True, config=_doc(), dirs=DIRS,
        peaks=lambda: PEAKS, result=types.SimpleNamespace(counters={}))
    assert _reader(name).read(untraced) is None
    on_cpu = types.SimpleNamespace(**{**vars(_ctx(path)), "on_tpu": False})
    assert _reader(name).read(on_cpu) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_new_reader_declares_itself(name):
    """Each reader is a file beside the accepted ones, found by name.
    ``BENCHMARK.json`` cannot list them in this PR (an entry goes at the END
    of ``per_layer``, and ``test_chipbench_scopes.py`` pins the last eight:
    PERF.md section 7 (m)); the ``benchmark`` PR that lists one has to list it
    as the file says, on this cell."""
    mod = _reader(name)
    assert (mod.NAME, (mod.UNIT, mod.LAYER, mod.MOVES)) == (name, READERS[name])
    assert mod.KINDS == ("serve_closed",)
    for entry in BENCH["per_layer"]:
        if entry["name"] == name:
            assert (entry["unit"], entry["layer"], entry["moves"]) == READERS[name]
            assert CELL in entry["workloads"] and entry["source"] == "device_trace"


ACCEPTED_CELLS = ["bloom-7b1.chat", "gpt2-125m.seq1k", "bloom-7b1.docqa",
                  "nemotron-3-super-120b-a12b.conv32", "sdar-30b-a3b-chat.conv32",
                  "lfm2-8b-a1b.conv32", "granite-4.0-h-micro.conv64"]
MODEL_AGNOSTIC = {
    "sched_host_ms_per_step", "decode_step_dev_ms", "serve_device_idle_pct",
    "tpot_p50_ms.layer", "sched_fetch_idle_ms_per_step", "decode_wasted_step_pct",
    "decode_scoped_pct", "decode_attn_dev_ms_per_step", "decode_head_dev_ms_per_step",
    "decode_per_chunk_dev_ms", "setup_compile_s", "setup_engine_init_s"}


def test_the_accepted_cells_stand_as_they_were_and_this_one_follows_them():
    """Entries are found by NAME and held by their ORDER from the front, so
    that the next cell appended after this one outdates nothing here. The
    accepted ``test_chipbench_granite.py:
    test_the_cell_reports_the_model_agnostic_readers_and_adds_no_entry`` pins
    granite-4.0-h-micro.conv64 as the LAST cell and FAILS since this cell was
    appended after it (``PERF.md`` section 7 (m): a ``benchmark`` PR's to
    mend); everything else it held of that cell is held here too, by name."""
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[:8] == ACCEPTED_CELLS + [CELL]
    assert [c["name"] for c in BENCH["configs"]][:7] == [
        "bloom-7b1", "gpt2-125m", "nemotron-3-super-120b-a12b", "sdar-30b-a3b-chat",
        "lfm2-8b-a1b", "granite-4.0-h-micro", CONFIG]
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "conv32", 1)
    assert len(cell["why"]) <= 200 and len(BENCH["configs"][6]["why"]) <= 200
    micro = "granite-4.0-h-micro.conv64"
    for name in (micro, CELL):
        e2e = {m["name"] for m in registry.metrics_of(BENCH, "end_to_end", name)}
        assert e2e == {"tpot_mean_ms", "setup_s"}, name
        reports = {m["name"] for m in registry.metrics_of(BENCH, "per_layer", name)}
        assert reports == MODEL_AGNOSTIC, name
        assert all(m["moves"] in e2e for m in BENCH["per_layer"] if m["name"] in reports)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        listed = m.get("workloads", [])
        # every list keeps the accepted cells in their order, this cell after them
        assert [c for c in cells if c in listed] == listed, m["name"]
        if CELL in listed:
            assert micro in listed, m["name"]
    # the traffic file is the accepted conv32, as it is: 32 callers = the slots
    conv32 = registry.load_json("traffic", "conv32", DIRS)
    assert conv32["clients"] == _doc()["serve"]["slots"] == 32
    assert conv32["parity_prompts"] == [512, 128]
    conv64 = registry.load_json("traffic", "conv64", DIRS)
    assert conv64["lengths"] == conv32["lengths"] and conv64["kind"] == "serve_closed"
    with open(registry.config_file_of(BENCH, "granite-4.0-h-micro", REPO)) as f:
        assert conv64["clients"] == json.load(f)["serve"]["slots"] == 64
    assert (conv64["document_tokens"], conv64["parity_prompts"],
            conv64["parity_output_tokens"], conv64["traced_seconds"]) == \
        (0, [512, 128], 17, 3.0)
    assert conv64["rehearsal"]["clients"] == 4


def test_the_cells_rehearsal_ends_in_one_correct_line(tmp_path):
    """The cell as the driver runs it, here at the rehearsal's tiny widths:
    routes, parity with ``engine.generate`` and the reference's comparison
    all run, the stand-in's routers stay as they were seeded, nothing
    compiles inside the window."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7", PYTHONPATH="", TMPDIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3", "--trace", "1",
         "--rehearse-cpu"], env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, out.stdout[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0 and last["metrics"] == {}
    assert "reference granite_moe_hybrid" in out.stdout and "NOT compared" not in out.stdout
    assert "parity vs engine.generate" in out.stdout
    assert "levelled" not in out.stdout + out.stderr
    # 4 slots x 3 Mamba layers x (8 x 16 x 16 x 4 B of state + 3 x 160 x 2 B of window)
    assert "state_bytes=109824" in out.stdout and "heads_per_row=1" in out.stdout
    assert "programs compiled or loaded inside the window: 0" in out.stdout


@pytest.mark.parametrize("path", ["forward", "decode"])
def test_float8_weights_fail_a_logit_limit_that_the_bf16_program_passes(path):
    """The comparison ``serve_closed.check_reference`` makes of the program's
    forward (largest logit error over the last 8 positions, in spreads of the
    reference's logits) at a small width, three seeds: the bf16 program against
    the float32 reference on its own weights, and the reference on
    float8_e4m3fn matrices against itself. ``forward`` is ``engine.forward``,
    what the harness reads; ``decode`` reads the same 8 positions off the
    DECODE path (40 tokens prefilled under right padding, then one token a step
    through the cache: the state update, decode attention, the expert layer at
    one row a sequence). The configuration's limit is set between chip
    readings at the published widths (PERF.md section 6, PR 53); here the same
    comparison separates the two types around ``SMALL_LIMIT``, and the
    configuration's own limit holds every program reading too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.causal_lm import granite_hybrid_cfg, init_cache
    SMALL_LIMIT = 0.1
    doc = _doc()
    ref = registry.load_module("reference", doc["reference"]["module"], DIRS)
    limit = float(doc["reference"]["logit_tolerance_spreads"])
    model = {**doc["model"], **doc["rehearsal"]["model"], "hidden_size": 128,
             "mamba_n_heads": 16, "vocab_size": 2048}

    def decoded(eng, ids):
        module, variables = eng.module, {"params": eng.params}
        pad = np.zeros((1, 64), np.int32)
        pad[0, :40] = ids[:40]
        lens = jnp.asarray([40])
        logits, caches = jax.jit(lambda v, i, c, n: module.apply(
            v, i, caches=c, cache_lens=jnp.zeros_like(n), logits_positions=n - 1,
            seq_lens=n))(variables, jnp.asarray(pad), init_cache(eng.model_config, 1, 64),
                         lens)
        step = jax.jit(lambda v, t, c, n: module.apply(
            v, t, positions=n[:, None], caches=c, cache_lens=n))
        rows = [logits[0, 0]]
        for i in range(40, 47):
            logits, caches = step(variables, jnp.asarray(ids[None, i:i + 1]), caches, lens)
            rows.append(logits[0, 0])
            lens = lens + 1
        return np.asarray(jnp.stack(rows), np.float32)

    program, coarse = [], []
    for seed in range(3):
        eng = InferenceEngine(
            granite_hybrid_cfg(max_seq_len=64, init_std=0.05, **model),
            DeepSpeedInferenceConfig(dtype="bfloat16", max_out_tokens=64), seed=seed)
        ids = np.random.default_rng(seed).integers(1, 2000, size=48).astype(np.int32)
        at = np.arange(40, 48) if path == "forward" else np.arange(39, 47)
        want = ref.next_token_logits(eng.params, model, ids, at)
        spread = float(want.std(axis=-1).mean())
        got = (np.asarray(eng.forward(ids[None])[0, -8:], np.float32) if path == "forward"
               else decoded(eng, ids))
        program.append(float(np.abs(got - want).max()) / spread)
        low = ref.next_token_logits(rounded_matrices(eng.params, "float8_e4m3fn"),
                                    model, ids, at)       # the engine's matrices are gone
        coarse.append(float(np.abs(low - want).max()) / spread)
    assert max(program) < SMALL_LIMIT < min(coarse), (program, coarse)
    assert max(program) <= limit, (program, limit)
