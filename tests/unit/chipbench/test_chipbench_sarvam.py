"""sarvam-105b's benchmark files: its configuration against the catalog row it
copies and the cut it states, its traffic file's parameters, the yardstick's
arithmetic (``sarvam_shapes.py``) against the program's own count and the
issue's numbers, its six readers on a synthesised trace (and ``None`` where
the program has no such scope or the configuration names no ``shapes``), how
``BENCHMARK.json`` lists them (every entry found by NAME: no position is
pinned, so the next cell outdates nothing here), the labelled CPU rehearsal
of its cell, and the float8 probe of its limits at a small width."""

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
from benchmarks.chipbench import lengths  # noqa: E402
from benchmarks.chipbench import program_spans as ps  # noqa: E402
from benchmarks.chipbench import registry  # noqa: E402
from benchmarks.chipbench import sarvam_shapes as sh  # noqa: E402
from benchmarks.chipbench import trace_reduce as tr  # noqa: E402
from test_chipbench_hybrid import _record, rounded_matrices  # noqa: E402

BENCH = registry.load_benchmark(REPO)
DIRS = registry.search_dirs(BENCH, REPO)
CONFIG = "sarvam-105b"
CELL = "sarvam-105b.doc4k32"
URL = "https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json"
READERS = {      # name -> (unit, layer, moves, source), as each file declares itself
    "latent_attn_dev_ms_per_step": ("ms", "compiled steps", "tpot_mean_ms", "device_trace"),
    "latent_attn_roofline_pct": ("%", "kernels", "tpot_mean_ms", "device_trace"),
    "latent_decode_hbm_roofline_pct": ("%", "compiled steps", "tpot_mean_ms",
                                       "device_trace"),
    "prefill_stall_pct": ("%", "serve scheduler", "tpot_mean_ms", "host_clock"),
    "sarvam_moe_ffn_roofline_pct": ("%", "kernels", "tpot_mean_ms", "device_trace"),
    "latent_prefill_attn_roofline_pct": ("%", "kernels", "ttft_p50_ms", "device_trace"),
}
# read here too (a traced run printed all four) but NOT joined: accepted tests
# hold these lists to their cells (test_chipbench_chunk_cycles.py,
# test_chipbench_hybrid.py: ``entry["workloads"] == CELLS``)
NOT_JOINED = {"moe_experts_touched_per_step", "sched_chunk_gap_dev_ms",
              "sched_chunk_turnaround_host_ms", "host_pause_pct"}
MODEL_AGNOSTIC = {
    "sched_host_ms_per_step", "decode_step_dev_ms", "serve_device_idle_pct",
    "tpot_p50_ms.layer", "sched_fetch_idle_ms_per_step", "decode_wasted_step_pct",
    "decode_scoped_pct", "decode_attn_dev_ms_per_step", "decode_head_dev_ms_per_step",
    "decode_per_chunk_dev_ms", "setup_compile_s", "setup_engine_init_s"}
PEAKS = {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9}
SCOPED_DECODE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                             "decode_tiny_scoped.xplane.pb.gz")
ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "deepseek_yarn"}
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "attn_implementation": None, "default_theta": 10000, "first_k_dense_replace": 1,
    "head_dim": 576, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 16384, "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "sarvam_mla", "moe_intermediate_size": 2048,
    "moe_router_enable_expert_bias": True, "num_attention_heads": 64, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32, "num_shared_experts": 1,
    "q_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_scaling": ROPE, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "tie_word_embeddings": False, "use_qk_norm": True,
    "v_head_dim": 128, "vocab_size": 262144}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


def _doc():
    with open(registry.config_file_of(BENCH, CONFIG, REPO)) as f:
        return json.load(f)


def _entry(group, name):
    (entry,) = [e for e in BENCH[group] if e["name"] == name]
    return entry


# ------------------------------------------------------------ the configuration
def test_the_configuration_keeps_every_published_number_and_states_its_cut():
    doc = _doc()
    entry = _entry("configs", CONFIG)
    assert entry["reduced"] == doc["reduced"] == REDUCED
    assert entry["source"] == doc["source"] == URL
    assert entry["file"] == "benchmarks/chipbench/configs/sarvam-105b.json"
    assert len(entry["why"]) <= 200
    for key, value in CATALOG.items():
        if key in REDUCED:
            assert doc[key] != value and doc["published"][key] == value, key
        else:
            assert doc[key] == value, key
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["vocab_size"]) == \
        (8, 16, 32768)
    m = doc["model"]
    # the router keeps its width and its 8 a token; the chip holds experts 0-15
    assert (m["num_experts"], m["num_experts_per_tok"], m["experts_held"]) == \
        (128, 8, [0, 16])
    for key, value in m.items():             # the builder's keywords: the published ones
        if key in CATALOG and key not in ("num_hidden_layers", "vocab_size"):
            assert value == CATALOG[key], key
    assert m["num_hidden_layers"] == 8 and m["vocab_size"] == 32768
    # the stand-in's routers: homes behind a margin, no levelling, no other init
    assert m["home_random_routers"] is True and "level_random_experts" not in m
    assert "init_std" not in m
    assert m["greedy_decode_rows"] == doc["serve"]["slots"] == 32
    s = doc["serve"]
    assert (s["dtype"], s["max_seq_len"], s["chunk_size"], s["kv_pool"], s["kv_page_size"],
            s["max_queue"]) == ("bfloat16", 6144, 8, "paged", 16, 64)
    assert s["kv_total_pages"] == 32 * 384 + 1 and s["prefix_cache"] == {"enabled": False}
    for item in ("use_qk_norm", "router", "rotary_pairs", "router_bias", "router_homes", "init",
                 "out_init_std", "kv_b_proj", "greedy_decode_rows", "not_read"):
        assert len(doc["assumed"][item]) > 40, item
    assert doc["chips"] == 1 and "one chip of 32" in doc["deployment"]
    assert "NOTHING stands in" in doc["deployment"]
    assert doc["routes"] == {"decode_chunk": ["moe_grouped_ffn"],
                             "prefill_flash_from": 256}
    for key in ("memory_arithmetic", "routes_note", "serve_note", "shapes_note"):
        assert len(doc[key]) > 40, key
    assert "640" in doc["memory_arithmetic"] and "memory_peak_bytes" in doc["memory_arithmetic"]
    assert doc["reference"]["module"] == "sarvam_mla"
    assert 0 < doc["reference"]["tolerance_spreads"] <= 1
    assert doc["reference"]["tolerance_spreads"] < doc["reference"]["logit_tolerance_spreads"] <= 2
    assert set(doc["shapes"]) == {"params", "decode_step_bytes", "latent_attn_bytes",
                                  "latent_attn_flops", "moe_ffn_bytes", "moe_ffn_flops",
                                  "prefill_attn_flops"}
    for spec in doc["shapes"].values():
        assert callable(registry.resolve(spec))
    # the rehearsal's widths are the tiny tests'
    from tests.unit import sarvam_tiny as st
    assert set(doc["rehearsal"]["model"]) <= set(m) | {"q_head_dim", "head_dim"}
    for key, value in doc["rehearsal"]["model"].items():
        if key in st.MODEL and key != "vocab_size":
            assert st.MODEL[key] == value, key


def test_the_builder_takes_the_files_model_section_whole():
    from deepspeed_tpu.models.causal_lm import sarvam_mla_cfg
    doc = _doc()
    assert registry.resolve(doc["model_builder"]) is sarvam_mla_cfg
    cfg = sarvam_mla_cfg(max_seq_len=6144, **doc["model"])
    assert cfg.layer_pattern == "LF" + "LE" * 7 and cfg.max_seq_len == 6144
    assert (cfg.moe_router, cfg.moe_kind, cfg.moe_shared_width) == \
        ("sigmoid_bias", "gated", 2048)
    assert abs(cfg.attn_scale - 0.135234) < 1e-6
    tiny = sarvam_mla_cfg(max_seq_len=96, **registry.rehearsal_view(doc)["model"])
    assert tiny.layer_pattern == "LFLELE" and tiny.held_experts == (0, 4)


# ------------------------------------------------------------------ the traffic
def test_the_traffic_file_holds_the_issues_parameters():
    t = registry.load_json("traffic", "doc4k32", DIRS)
    assert (t["kind"], t["clients"], t["document_tokens"], t["asks_per_document"]) == \
        ("serve_closed", 32, 0, 1)
    ln = t["lengths"]
    assert (ln["count"], ln["pairing_seed"]) == (24, 24)
    assert ln["prompt"] == {"distribution": "lognormal", "mean": 3600, "sigma": 0.08,
                            "min": 3072, "max": 4096}
    assert ln["output"] == {"distribution": "lognormal", "mean": 512, "sigma": 0.6,
                            "min": 64, "max": 1536}
    assert (t["parity_prompts"], t["parity_output_tokens"], t["traced_seconds"]) == \
        ([4096, 512], 17, 3.0)
    for key in ("source", "assumed", "cut"):
        assert len(ln[key]) > 40, key
    pairs = lengths.fixed_requests(t, _doc()["serve"]["max_seq_len"])
    prompts, outputs = [p for p, _ in pairs], sorted(o for _, o in pairs)
    assert (min(prompts), max(prompts)) == (3072, 4096)
    assert (outputs[0], outputs[-1]) == (126, 1452)
    assert round(sum(prompts) / 24) == 3595 and round(sum(outputs) / 24) == 506
    assert max(p + o for p, o in pairs) <= 5632 < 6144
    # every prompt pads to the ONE 4,096 bucket of the executor's buckets
    from deepspeed_tpu.inference.serving.executor import prompt_buckets
    buckets = prompt_buckets(6143)
    assert {min(b for b in buckets if b >= p) for p in prompts} == {4096}
    assert t["rehearsal"]["clients"] == 4 and len(t["rehearsal"]["requests"]) == 4


# --------------------------------------------------------------- the arithmetic
@pytest.mark.parametrize("size", ["published", "tiny"])
def test_the_arithmetic_is_the_programs_own_count(size):
    """Shape arithmetic only: nothing is allocated at the published size."""
    from deepspeed_tpu.models.causal_lm import sarvam_mla_cfg
    doc = _doc()
    m = doc["model"] if size == "published" else registry.rehearsal_view(doc)["model"]
    assert sh.params(m) == sarvam_mla_cfg(max_seq_len=6144, **m).num_params()
    whole = {k: v for k, v in m.items() if k != "experts_held"}
    assert sh.params(whole) == sarvam_mla_cfg(max_seq_len=6144, **whole).num_params()
    assert sh.params(whole) - sh.params(m) == sh.expert_layers(m) * (
        m["num_experts"] - m["experts_held"][1]) * sh.expert_params(m)


def test_the_arithmetic_reproduces_the_issues_numbers():
    m = _doc()["model"]
    assert sh.attention_params(m) == 94_634_496
    assert sh.up_params(m) == 8_388_608 and sh.dense_ffn_params(m) == 201_326_592
    assert sh.expert_params(m) == sh.shared_params(m) == 25_165_824
    assert sh.router_params(m) == 524_288 + 128
    assert sh.params(m) == 4_225_311_616                       # 8.45 GB of bf16
    assert (sh.row_width(m), sh.row_lanes(m)) == (576, 640)
    # a cached token: 9,216 B as published, 10,240 B as stored, all 8 layers
    assert 8 * sh.row_width(m) * 2 == 9216 and 8 * sh.row_lanes(m) * 2 == 10240
    # a cached row a layer: 64 x (576 + 512) x 2 = 139 kFLOP against 1,280 B
    live = 32 * 3900.0
    rows_only = sh.latent_attn_flops(live, 0, m) / (8 * live)
    assert rows_only == 64 * (576 + 512) * 2 == 139_264
    assert sh.latent_attn_bytes(live, 32, m) == 8 * 2 * (live * 640 + 8_388_608)
    assert 100 < rows_only / 1280 < 120                        # 109 FLOP a byte
    # a step at a mean live context of 3.9k: ~8.7 GB, of them 1.28 GB of rows
    step = sh.decode_step_bytes(m, 32, live, 13.8 * 7)
    assert 8.5e9 < step < 8.9e9
    assert step - sh.decode_step_bytes(m, 32, 0.0, 13.8 * 7) == pytest.approx(live * 10240)
    # the 4,096-token prompt's attention as published: 2.75 TFLOP
    assert 2.74e12 < sh.prefill_attn_flops(4096, m) < 2.76e12
    assert sh.moe_ffn_flops(32, m) == 32 * 2 * 25_165_824


# ------------------------------------------------------------------ the readers
def _chunks_and_prefill(TA):
    """Two steps: an admission (a 50 ms prefill) and a chunk, then a chunk alone."""
    import time
    for n, touched in enumerate([776, 760], 1):
        with TA("chipbench.step"):
            if n == 1:
                with TA("serving.admit", request_id=9, prompt_tokens=3600, prefix_len=0,
                        slot=3):
                    with TA("serving.prefill", request_id=9, bucket=4096, tokens=3600,
                            prefix_len=0):
                        time.sleep(0.05)
            with TA("serving.decode_chunk", chunk=n, active_slots=32, request_ids="1 2",
                    slot_steps_run=256, attn_rows=5120) as chunk:
                time.sleep(0.1)
                chunk.set_metadata(tokens_kept=250, deliveries=32, stalled_deliveries=1,
                                   moe_assignments=1790, moe_experts_touched=touched)


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("sarvam"), _chunks_and_prefill)


def _ctx(path, config=None, with_device=True):
    red = tr.reduce_trace(path)
    ops, programs = [], []
    for sp in ps.named(ps.load(path), "serving.decode_chunk"):
        a = sp.start + 0.001
        programs.append(("decode_chunk", a, a + 0.096))       # 12 ms a step
        # 7 expert layers x 2 halves a step in one op here: 6.4 ms a step
        ops += [("moe_grouped_ffn.4", a, a + 0.0512), ("fusion.9", a + 0.0512, a + 0.096)]
    for sp in ps.named(ps.load(path), "serving.prefill"):
        a = sp.start + 0.001
        programs.append(("prefill", a, a + 0.045))
        ops += [("flash_fwd.1", a, a + 0.040), ("fusion.2", a + 0.040, a + 0.045)]
    red["devices"] = [{"id": 0, "ops": sorted(ops, key=lambda o: o[1]), "asyncs": [],
                       "programs": sorted(programs, key=lambda r: r[1])}] \
        if with_device else []
    # the benchmark's own step spans, here on the trace's clock, and a measured
    # window that is the traced one
    steps = [("chipbench.step", s, e) for s, e in _reader("prefill_stall_pct").traced_steps(
        types.SimpleNamespace(trace_path=path, trace_reduced=red))]
    return types.SimpleNamespace(
        trace_path=path, trace_reduced=red, on_tpu=True, config=config or _doc(),
        dirs=DIRS, peaks=lambda: PEAKS, spans=steps,
        result=types.SimpleNamespace(window=tuple(red["window"]),
                                     counters={"chunk_size": 8,
                                               "live_tokens_mean": 32 * 3900.0}))


def _reader(name):
    return registry.load_module("layer_metrics", name, DIRS)


def _table(latent_s):
    # 16 steps in two chunks; the scope's seconds as given
    rows = {("attn.core", "forward"): [0.004, 480, 0.0, 0.0]}
    if latent_s:
        rows[("attn.latent", "forward")] = [latent_s, 960, 0.0, 0.0]
    return ds.Table("decode_chunk", 2, 16.0, rows, {})


def test_the_six_readers_on_a_synthetic_trace(trace, monkeypatch, capsys):
    ctx = _ctx(trace)
    m = ctx.config["model"]
    monkeypatch.setattr(ds, "table", lambda ctx, program: _table(0.040))
    # 40 ms under attn.latent over 16 steps
    assert _reader("latent_attn_dev_ms_per_step").read(ctx) == pytest.approx(2.5)
    live = 32 * 3900.0
    least = max(sh.latent_attn_bytes(live, 32, m) / 819.0e9,
                sh.latent_attn_flops(live, 32, m) / 197.0e12)
    got = _reader("latent_attn_roofline_pct").read(ctx)
    assert got == pytest.approx(100.0 * least / 2.5e-3, rel=1e-6) and 50 < got < 105
    touched = (776 + 760) / 16
    need = sh.decode_step_bytes(m, 32, live, touched)
    assert _reader("latent_decode_hbm_roofline_pct").read(ctx) == pytest.approx(
        100.0 * need / 819.0e9 / 0.012, rel=1e-6)
    # the whole window's stall: what the step with the admission ran over the
    # step without one, which is the prefill's span to a millisecond
    stall = _reader("prefill_stall_pct").read(ctx)
    lo, hi = ctx.trace_reduced["window"]
    (pre,) = ps.named(ps.in_window(ctx), "serving.prefill")
    (long, a), (bare, b) = [(e - s, s) for _, s, e in ctx.spans]
    # over the time INSIDE the steps (the harness's own between them left out)
    assert a < b and stall == pytest.approx(100.0 * (long - bare) / (long + bare))
    # ... and near the prefill's span over the window (sleeps on a busy host)
    assert stall == pytest.approx(100.0 * (pre.end - pre.start) / (hi - lo), abs=8)
    assert 10 < stall < 40                              # 50 ms of ~250
    # the expert kernel: per chunk the touched experts' bytes over 51.2 ms
    got = _reader("sarvam_moe_ffn_roofline_pct").read(ctx)
    least = sum(sh.moe_ffn_bytes(t, 1790, m) for t in (776, 760)) / 819.0e9
    assert got == pytest.approx(100.0 * least / 0.1024, rel=1e-6) and 80 < got < 105
    # the prefill's flash kernel: 3,600 tokens' attention as published over 40 ms
    got = _reader("latent_prefill_attn_roofline_pct").read(ctx)
    assert got == pytest.approx(100.0 * sh.prefill_attn_flops(3600, m) / 197.0e12 / 0.040,
                                rel=1e-6) and 20 < got < 40
    out = capsys.readouterr().out
    assert "sarvam_shapes:latent_attn_bytes" in out and "the walk reaches 163840" in out
    assert "least 1.7" in out and "by bytes" in out     # 1.41 GB at 819 GB/s
    assert "in 96.0 touched experts" in out and "live latent rows" in out
    assert "1 prefills of" in out and "1 steps held prefills" in out
    assert "sarvam_shapes:moe_ffn_bytes" in out and "sarvam_shapes:prefill_attn_flops" in out
    assert "96.0 held experts touched a step" in out and "in 1 of 1 whole prefills" in out


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_none_on_a_program_without_what_it_reads(name, trace,
                                                                monkeypatch):
    """The parent commit cannot build this configuration, and the driver lays
    these files over it: on the parent's programs and configurations each
    reader returns nothing and does not raise. A program without the scope
    ``attn.latent`` (every one the parent has); another configuration's file;
    no device plane; a recorded trace of a scoped program; untraced; the CPU."""
    by_scope = name.startswith(("latent_attn", "latent_decode"))
    by_shapes = name != "prefill_stall_pct" and name != "latent_attn_dev_ms_per_step"
    ctx = _ctx(trace)
    if by_scope:
        monkeypatch.setattr(ds, "table", lambda ctx, program: _table(0.0))
        assert _reader(name).read(ctx) is None          # scoped, but no attn.latent
        monkeypatch.setattr(ds, "table", lambda ctx, program: None)
        assert _reader(name).read(ctx) is None          # no scoped program at all
        monkeypatch.undo()
    if name != "prefill_stall_pct":                     # it reads host spans alone
        assert _reader(name).read(_ctx(trace, with_device=False)) is None
    for other in ("bloom-7b1", "lfm2-8b-a1b"):          # no ``shapes`` of that name
        with open(os.path.join(REPO, "benchmarks", "chipbench", "configs",
                               other + ".json")) as f:
            if by_shapes:
                assert _reader(name).read(_ctx(trace, config=json.load(f))) is None, other
    recorded = types.SimpleNamespace(
        trace_path=SCOPED_DECODE, trace_reduced=tr.reduce_trace(SCOPED_DECODE),
        on_tpu=True, config=_doc(), dirs=DIRS, peaks=lambda: PEAKS,
        result=types.SimpleNamespace(counters={"chunk_size": 4, "live_tokens_mean": 40.0}))
    recorded.spans, recorded.result.window = [], (0.0, 1.0)
    assert ds.table(recorded, "decode_chunk") is not None   # scoped, no attn.latent
    assert _reader(name).read(recorded) is None             # nor prefills, nor experts
    untraced = types.SimpleNamespace(
        trace_path=None, trace_reduced=None, on_tpu=True, config=_doc(), dirs=DIRS,
        peaks=lambda: PEAKS, result=types.SimpleNamespace(counters={}))
    assert _reader(name).read(untraced) is None
    if by_shapes:
        monkeypatch.setattr(ds, "table", lambda ctx, program: _table(0.040))
        on_cpu = types.SimpleNamespace(**{**vars(_ctx(trace)), "on_tpu": False})
        assert _reader(name).read(on_cpu) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_new_reader_declares_itself_as_the_benchmark_lists_it(name):
    mod = _reader(name)
    unit, layer, moves, source = READERS[name]
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (name, unit, layer, moves)
    assert mod.KINDS == ("serve_closed",)
    entry = _entry("per_layer", name)
    assert entry == {"name": name, "unit": unit, "better": entry["better"],
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": [CELL]}
    assert entry["better"] == ("lower" if unit == "ms" or name == "prefill_stall_pct"
                               else "higher")


def test_the_cell_is_listed_by_name_and_follows_the_accepted_cells():
    """Found by NAME everywhere: where in a list an entry stands is not held,
    but that this PR's entries come after everything the benchmark had."""
    cells = [w["name"] for w in BENCH["workloads"]]
    accepted = ["bloom-7b1.chat", "gpt2-125m.seq1k", "bloom-7b1.docqa",
                "nemotron-3-super-120b-a12b.conv32", "sdar-30b-a3b-chat.conv32",
                "lfm2-8b-a1b.conv32", "granite-4.0-h-micro.conv64",
                "granite-4.0-h-small.conv32"]
    assert cells[:8] == accepted and cells.index(CELL) >= 8
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    cell = _entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "doc4k32", 1)
    assert len(cell["why"]) <= 200 and "8x" in cell["why"] and "GB" in cell["why"]
    e2e = {m["name"] for m in registry.metrics_of(BENCH, "end_to_end", CELL)}
    assert {"tpot_mean_ms", "setup_s"} <= e2e <= {"tpot_mean_ms", "setup_s", "ttft_p50_ms"}
    reports = {m["name"] for m in registry.metrics_of(BENCH, "per_layer", CELL)}
    ttft = {"prefill_dev_ms", "sched_admit_host_ms"} if "ttft_p50_ms" in e2e else set()
    assert reports == MODEL_AGNOSTIC | set(READERS) | ttft and not reports & NOT_JOINED
    assert all(m["moves"] in e2e for m in BENCH["per_layer"] if m["name"] in reports)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.index(n) > names.index("host_pause_pct") for n in READERS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        listed = m.get("workloads", [])
        assert [c for c in cells if c in listed] == listed, m["name"]
        if CELL in listed and m["name"] not in READERS:
            assert "granite-4.0-h-small.conv32" in listed or m["name"] in (
                "ttft_p50_ms", "prefill_dev_ms", "sched_admit_host_ms"), m["name"]
    # every reader of a metric this cell reports is a file found by name
    for name in reports:
        assert registry.find("layer_metrics", name + ".py", DIRS)


# ---------------------------------------------------------------- the rehearsal
def test_the_cells_rehearsal_ends_in_one_correct_line(tmp_path):
    """The cell as the driver runs it, here at the rehearsal's tiny widths:
    routes, parity with ``engine.generate`` and the reference's comparison all
    run, the pool says its latent row, the decode chunk opens ``attn.latent``
    and no prefill does, nothing compiles inside the window."""
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
    assert "reference sarvam_mla" in out.stdout and "NOT compared" not in out.stdout
    assert out.stdout.count("parity vs engine.generate") == 2
    # the stand-in's routers: homes behind a margin, made where the weights are
    assert "every token id has its home experts" in out.stdout + out.stderr
    assert "state_bytes=0" in out.stdout and "heads_per_row=1" in out.stdout
    assert "programs compiled or loaded inside the window: 0" in out.stdout


def test_the_rehearsals_chunk_opens_the_latent_scope_and_its_prefill_the_expansion():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.decode_fns import (build_paged_decode_chunk,
                                                    build_prefill, make_slot_select_fn)
    from deepspeed_tpu.models.causal_lm import CausalLM, init_cache, sarvam_mla_cfg
    from deepspeed_tpu.observability.schema import SCOPES
    assert SCOPES["attn.latent"][2] == "latent_attn_dev_ms_per_step"
    assert "attn.latent_expand" in SCOPES
    view = registry.rehearsal_view(_doc())
    cfg = sarvam_mla_cfg(max_seq_len=96, dtype=jnp.float32, **view["model"])
    module = CausalLM(cfg)
    params = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    slots, cap, pages = 4, 96, 25
    caches = jax.eval_shape(lambda: init_cache(cfg, slots, cap, kv_shape=(pages, 4, 16, 16)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
    chunk = build_paged_decode_chunk(module, lambda p: p,
                                     make_slot_select_fn(False, 1.0, 0, 1.0), 8,
                                     kv_cap=cap, with_stats=True)
    text = jax.jit(chunk).lower(
        params, i32(slots, 1), caches, i32(slots, cap // 16), i32(slots),
        jax.ShapeDtypeStruct((slots,), jnp.bool_), i32(slots), i32(slots), i32(slots),
        i32(slots), jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    assert "ds.attn.latent/" in text and "ds.attn.latent_expand" not in text
    one = jax.eval_shape(lambda: init_cache(cfg, 1, cap))
    pre = jax.jit(build_prefill(module, lambda p: p, with_stats=True)).lower(
        params, i32(1, 64), one, i32(1)).as_text(debug_info=True)
    assert "ds.attn.latent_expand" in pre and "ds.attn.latent/" not in pre


# ------------------------------------------------------------- the float8 probe
@pytest.mark.parametrize("path", ["forward", "decode"])
def test_float8_weights_fail_a_logit_limit_that_the_bf16_program_passes(path):
    """The comparison ``serve_closed.check_reference`` makes of the program's
    forward (largest logit error over the last 8 positions, in spreads of the
    reference's logits) at a small width, three seeds: the bf16 program against
    the float32 reference on its own weights, and the reference on matrices
    kept to float8's 3 mantissa bits (range kept) against itself. ``decode`` reads the same 8
    positions off the DECODE path (40 tokens prefilled expanded under right
    padding, then one token a step absorbed through the cache). The
    configuration's limits are set between chip readings at the published
    widths (PERF.md section 6, PR 57); here the same comparison separates the
    two types around ``SMALL_LIMIT``, and the configuration's own limit holds
    every program reading too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.causal_lm import init_cache, sarvam_mla_cfg
    SMALL_LIMIT = 0.12
    doc = _doc()
    ref = registry.load_module("reference", doc["reference"]["module"], DIRS)
    limit = float(doc["reference"]["logit_tolerance_spreads"])
    # the configuration's own stand-in: every token id has its home experts
    # (``home_random_routers``), the top 2 of 8 here, 4 held. With routers as seeded
    # ONE flipped choice moves a logit by spreads at this width, in the bf16
    # program and in float8 alike, and the probe would read the router's ties,
    # not the types (``test_sarvam_model.py`` counts those flips)
    model = {**registry.rehearsal_view(doc)["model"], "hidden_size": 128,
             "vocab_size": 2048}
    assert model["home_random_routers"] and model["num_experts_per_tok"] == 2
    plain = {k: v for k, v in model.items()
             if k not in ("home_random_routers", "greedy_decode_rows")}

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
            sarvam_mla_cfg(max_seq_len=64, init_std=0.05, out_init_std=0.05, **model),
            DeepSpeedInferenceConfig(dtype="bfloat16", max_out_tokens=64), seed=seed)
        ids = np.random.default_rng(seed).integers(1, 2000, size=48).astype(np.int32)
        at = np.arange(40, 48) if path == "forward" else np.arange(39, 47)
        want = ref.next_token_logits(eng.params, plain, ids, at)
        spread = float(want.std(axis=-1).mean())
        got = (np.asarray(eng.forward(ids[None])[0, -8:], np.float32) if path == "forward"
               else decoded(eng, ids))
        program.append(float(np.abs(got - want).max()) / spread)
        low = ref.next_token_logits(rounded_matrices(eng.params, "float8_e4m3fn"),
                                    plain, ids, at)
        coarse.append(float(np.abs(low - want).max()) / spread)
    assert max(program) < SMALL_LIMIT < min(coarse), (program, coarse)
    assert max(program) <= limit, (program, limit)
