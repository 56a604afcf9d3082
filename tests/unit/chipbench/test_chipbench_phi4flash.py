"""Phi-4-mini-flash-reasoning's benchmark files: its configuration against the
catalog row it copies key for key (nothing is reduced), the yardstick's
arithmetic (``sambay_shapes.py``) against the program's own count and the
issue's numbers, its six readers on a synthesised trace (and ``None`` where the
program has no such scope, span field or kernel, or the configuration names no
``shapes``), how ``BENCHMARK.json`` lists them (every entry found by NAME, held
to FOLLOW the nine accepted cells: no list's end is pinned, so the next cell
outdates nothing here), and the labelled CPU rehearsal of its cell."""

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
from benchmarks.chipbench import program_spans as ps  # noqa: E402
from benchmarks.chipbench import registry  # noqa: E402
from benchmarks.chipbench import sambay_shapes as sh  # noqa: E402
from benchmarks.chipbench import trace_reduce as tr  # noqa: E402
from test_chipbench_hybrid import _record  # noqa: E402

BENCH = registry.load_benchmark(REPO)
DIRS = registry.search_dirs(BENCH, REPO)
CONFIG = "phi-4-mini-flash-reasoning"
CELL = "phi-4-mini-flash-reasoning.doc4k32"
URL = "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
ACCEPTED = ["bloom-7b1.chat", "gpt2-125m.seq1k", "bloom-7b1.docqa",
            "nemotron-3-super-120b-a12b.conv32", "sdar-30b-a3b-chat.conv32",
            "lfm2-8b-a1b.conv32", "granite-4.0-h-micro.conv64",
            "granite-4.0-h-small.conv32", "sarvam-105b.doc4k32"]
READERS = {      # name -> (unit, layer, moves), as each file declares itself
    "shared_kv_attn_dev_ms_per_step": ("ms", "compiled steps", "tpot_mean_ms"),
    "shared_kv_attn_roofline_pct": ("%", "kernels", "tpot_mean_ms"),
    "window_attn_dev_ms_per_step": ("ms", "compiled steps", "tpot_mean_ms"),
    "yoco_decode_hbm_roofline_pct": ("%", "compiled steps", "tpot_mean_ms"),
    "selective_scan_roofline_pct": ("%", "kernels", "ttft_p50_ms"),
    "prefill_cross_decoder_dev_ms": ("ms", "compiled steps", "ttft_p50_ms"),
}
JOINED = {
    "sched_host_ms_per_step", "decode_step_dev_ms", "serve_device_idle_pct",
    "tpot_p50_ms.layer", "sched_fetch_idle_ms_per_step", "decode_wasted_step_pct",
    "decode_scoped_pct", "decode_attn_dev_ms_per_step", "decode_head_dev_ms_per_step",
    "decode_per_chunk_dev_ms", "prefill_dev_ms", "sched_admit_host_ms"}
NO_LIST = {"setup_compile_s", "setup_engine_init_s"}
PEAKS = {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9}
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
    "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20,
    "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True,
    "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}


def _doc():
    with open(registry.config_file_of(BENCH, CONFIG, REPO)) as f:
        return json.load(f)


def _entry(group, name):
    (entry,) = [e for e in BENCH[group] if e["name"] == name]
    return entry


def _reader(name):
    return registry.load_module("layer_metrics", name, DIRS)


# ------------------------------------------------------------ the configuration
def test_the_configuration_is_the_catalog_row_key_for_key_and_cuts_nothing():
    doc = _doc()
    entry = _entry("configs", CONFIG)
    assert entry["reduced"] == doc["reduced"] == []
    assert entry["source"] == doc["source"] == URL
    assert entry["file"] == "benchmarks/chipbench/configs/phi-4-mini-flash-reasoning.json"
    assert len(entry["why"]) <= 200
    for key, value in CATALOG.items():
        assert doc[key] == value and type(doc[key]) is type(value), key
        assert doc["model"][key] == value, key
    m = doc["model"]
    assert set(m) == set(CATALOG) | {"out_init_std", "greedy_decode_rows"}
    assert m["greedy_decode_rows"] == doc["serve"]["slots"] == 32
    s = doc["serve"]
    assert (s["dtype"], s["max_seq_len"], s["chunk_size"], s["kv_pool"], s["kv_page_size"],
            s["max_queue"]) == ("bfloat16", 6144, 8, "paged", 16, 64)
    assert s["kv_total_pages"] == 32 * 384 + 1 and s["prefix_cache"] == {"enabled": False}
    for item in ("mamba", "layer_order", "differential_attention", "attention_bias",
                 "positions", "memory", "swiglu_gate", "window", "ssm_state_dtype", "init",
                 "greedy_decode_rows", "not_read"):
        assert len(doc["assumed"][item]) > 40, item
    assert doc["chips"] == 1 and "nothing cut" in doc["deployment"]
    assert doc["routes"] == {"decode_chunk": ["decode_attention", "selective_step"],
                             "prefill_flash_from": 256}
    for key in ("memory_arithmetic", "routes_note", "serve_note"):
        assert len(doc[key]) > 40, key
    assert "3,852,562,944" in doc["memory_arithmetic"]
    assert doc["reference"]["module"] == "phi4flash"
    assert 0 < doc["reference"]["tolerance_spreads"] <= 1
    assert 0 < doc["reference"]["logit_tolerance_spreads"] <= 2
    assert len(doc["reference"]["why"]) > 200
    assert set(doc["shapes"]) == {"params", "decode_step_bytes", "shared_attn_bytes",
                                  "shared_attn_flops", "scan_bytes", "scan_flops",
                                  "cross_decoder_from"}
    for spec in doc["shapes"].values():
        assert callable(registry.resolve(spec))
    # the rehearsal's widths are the tiny tests'
    from tests.unit import phi4flash_tiny as pt
    for key, value in doc["rehearsal"]["model"].items():
        if key != "greedy_decode_rows":
            assert pt.TINY[key] == value, key


def test_the_builder_takes_the_files_model_section_whole():
    from deepspeed_tpu.models.causal_lm import phi4flash_cfg
    doc = _doc()
    assert registry.resolve(doc["model_builder"]) is phi4flash_cfg
    cfg = phi4flash_cfg(max_seq_len=6144, **doc["model"])
    assert cfg.layer_pattern == "SFWF" * 8 + "SF*F" + "GFXF" * 7
    assert cfg.max_seq_len == 6144 and cfg.out_init_std == doc["model"]["out_init_std"]
    assert cfg.slot_state_layers == ("selective-state-space", "window-attention")
    tiny = phi4flash_cfg(max_seq_len=96, **registry.rehearsal_view(doc)["model"])
    assert tiny.layer_pattern == "SFWFSFWFSF*FGFXF" and tiny.sliding_window == 8


def test_the_cell_serves_the_traffic_file_the_benchmark_has():
    cell = _entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "doc4k32", 1)
    sarvam = _entry("workloads", "sarvam-105b.doc4k32")
    assert sarvam["traffic"] == cell["traffic"]
    t = registry.load_json("traffic", "doc4k32", DIRS)
    assert (t["kind"], t["clients"]) == ("serve_closed", _doc()["serve"]["slots"])
    assert t["parity_prompts"] == [4096, 512]


# --------------------------------------------------------------- the arithmetic
@pytest.mark.parametrize("size", ["published", "tiny"])
def test_the_arithmetic_is_the_programs_own_count(size):
    """Shape arithmetic only: nothing is allocated at the published size."""
    from deepspeed_tpu.models.causal_lm import phi4flash_cfg
    doc = _doc()
    m = doc["model"] if size == "published" else registry.rehearsal_view(doc)["model"]
    cfg = phi4flash_cfg(max_seq_len=6144, **m)
    assert sh.params(m) == cfg.num_params()
    kinds = {"mamba": "S", "window": "W", "full": "*", "cross": "X", "memory": "G"}
    assert "".join(kinds[k] + "F" for k in sh.mixers(m)) == cfg.layer_pattern
    assert sh.cross_decoder_from(m) == cfg.prefill_stop + 2


def test_the_arithmetic_reproduces_the_issues_numbers():
    m = _doc()["model"]
    assert sh.mamba_params(m) == 41_241_600
    assert sh.attention_params(m) == 19_668_864
    assert sh.cross_attention_params(m) == 13_112_704
    assert sh.memory_unit_params(m) == 26_214_400
    assert sh.mlp_params(m) == 78_643_200
    kinds = sh.mixers(m)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "memory", "cross")] == \
        [9, 8, 1, 7, 7]
    assert sh.params(m) == 3_852_562_944                      # 7.71 GB of bf16
    assert sh.kv_bytes_per_token(m) == 5120                   # for the WHOLE model
    assert sh.ring_bytes_per_slot(m) == 8 * 512 * 5120 == 20_971_520
    assert sh.state_bytes_per_slot(m) == 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    live = 32 * 3850.0
    assert sh.shared_readers(m) == 8
    assert sh.shared_attn_bytes(live, 32, m) == 8 * live * 5120      # 5.05 GB
    step = sh.decode_step_bytes(m, 32, live)
    assert 13.5e9 < step < 13.8e9
    assert step - sh.decode_step_bytes(m, 32, 0.0) == pytest.approx(8 * live * 5120)
    # a 4,096 bucket's nine scans: x, dt read and y written (5120 wide), B, C (16)
    assert sh.scan_bytes(4096, m) == 9 * 4096 * (3 * 5120 + 32) * 4
    assert sh.scan_flops(4096, m) == 9 * 4096 * 5120 * 16 * 6
    assert sh.cross_decoder_from(m) == 36


# ------------------------------------------------------------------ the readers
def _chunks_and_prefill(TA):
    """Two steps: an admission (a prefill that stops early) and a chunk, then
    a chunk alone."""
    import time
    for n in (1, 2):
        with TA("chipbench.step"):
            if n == 1:
                with TA("serving.admit", request_id=9, prompt_tokens=3600, prefix_len=0,
                        slot=3):
                    with TA("serving.prefill", request_id=9, bucket=4096, tokens=3600,
                            prefix_len=0, positions_self=4096, positions_cross=1):
                        time.sleep(0.05)
            with TA("serving.decode_chunk", chunk=n, active_slots=32, request_ids="1 2",
                    slot_steps_run=256, attn_rows=5120) as chunk:
                time.sleep(0.1)
                chunk.set_metadata(tokens_kept=250, deliveries=32, stalled_deliveries=1)


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("phi4flash"), _chunks_and_prefill)


def _ctx(path, config=None, with_device=True, scan=True):
    red = tr.reduce_trace(path)
    ops, programs = [], []
    for sp in ps.named(ps.load(path), "serving.decode_chunk"):
        a = sp.start + 0.001
        programs.append(("decode_chunk", a, a + 0.096))       # 12 ms a step... x 2 below
        ops += [("decode_attention.4", a, a + 0.0512), ("fusion.9", a + 0.0512, a + 0.096)]
    for sp in ps.named(ps.load(path), "serving.prefill"):
        a = sp.start + 0.001
        programs.append(("prefill", a, a + 0.045))
        ops += [("selective_scan.1" if scan else "fusion.1", a, a + 0.030),
                ("fusion.2", a + 0.030, a + 0.045)]
    red["devices"] = [{"id": 0, "ops": sorted(ops, key=lambda o: o[1]), "asyncs": [],
                       "programs": sorted(programs, key=lambda r: r[1])}] \
        if with_device else []
    return types.SimpleNamespace(
        trace_path=path, trace_reduced=red, on_tpu=True, config=config or _doc(),
        dirs=DIRS, peaks=lambda: PEAKS, spans=[],
        result=types.SimpleNamespace(window=tuple(red["window"]),
                                     counters={"chunk_size": 8,
                                               "live_tokens_mean": 32 * 3850.0}))


def _table(shared_s, window_s=0.016):
    # 16 steps in two chunks; the scopes' seconds as given
    rows = {("attn.heads", "forward"): [0.004, 480, 0.0, 0.0]}
    if shared_s:
        rows[("attn.shared", "forward")] = [shared_s, 128, 0.0, 0.0]
    if window_s:
        rows[("attn.window", "forward")] = [window_s, 384, 0.0, 0.0]
    return ds.Table("decode_chunk", 2, 16.0, rows, {})


def test_the_six_readers_on_a_synthetic_trace(trace, monkeypatch, capsys):
    ctx = _ctx(trace)
    m = ctx.config["model"]
    monkeypatch.setattr(ds, "table", lambda ctx, program: _table(0.160))
    # 160 ms under attn.shared and 16 ms under attn.window over 16 steps
    assert _reader("shared_kv_attn_dev_ms_per_step").read(ctx) == pytest.approx(10.0)
    assert _reader("window_attn_dev_ms_per_step").read(ctx) == pytest.approx(1.0)
    live = 32 * 3850.0
    least = sh.shared_attn_bytes(live, 32, m) / 819.0e9          # 6.2 ms: by bytes
    assert least > sh.shared_attn_flops(live, 32, m) / 197.0e12
    got = _reader("shared_kv_attn_roofline_pct").read(ctx)
    assert got == pytest.approx(100.0 * least / 10e-3, rel=1e-6) and 55 < got < 70
    need = sh.decode_step_bytes(m, 32, live)
    got = _reader("yoco_decode_hbm_roofline_pct").read(ctx)
    assert got == pytest.approx(100.0 * need / 819.0e9 / 0.012, rel=1e-6)
    # the prefill's scan kernel: a 4,096 bucket's nine scans over 30 ms
    got = _reader("selective_scan_roofline_pct").read(ctx)
    assert got == pytest.approx(100.0 * sh.scan_bytes(4096, m) / 819.0e9 / 0.030, rel=1e-6)
    assert 5 < got < 15
    # the cross-decoder's share of a prefill, by the layers' names
    cross = _reader("prefill_cross_decoder_dev_ms")
    seen = {}

    def layer_seconds(path, runs, first):
        seen.update(path=path, runs=len(runs), first=first)
        return 0.004

    monkeypatch.setattr(cross, "layer_seconds", layer_seconds)
    assert cross.read(ctx) == pytest.approx(4.0)
    assert seen == {"path": trace, "runs": 1, "first": 36}
    out = capsys.readouterr().out
    assert "sambay_shapes:shared_attn_bytes" in out and "least 6.1" in out
    assert "sambay_shapes:decode_step_bytes" in out and "live rows of the one cache" in out
    assert "sambay_shapes:scan_bytes" in out and "in 1 of 1 whole prefills" in out
    assert "layers_36 on" in out and "at 1 position a sequence" in out


def test_the_cross_decoders_ops_are_found_by_their_layers_names(monkeypatch):
    cross = _reader("prefill_cross_decoder_dev_ms")
    assert cross.LAYER_NAME.findall(
        "jit(prefill)/CausalLM/layers_36/ds.gmu.gate/dot_general:") == ["36"]

    def event(name, start_ms, dur_ms):
        return types.SimpleNamespace(name=name, start_ns=start_ms * 1e6,
                                     duration_ns=dur_ms * 1e6)

    names = {"%fusion.1 = f32[]": "jit(prefill)/CausalLM/layers_34/ds.attn.shared/dot:",
             "%fusion.2 = f32[]": "jit(prefill)/CausalLM/layers_36/ds.gmu.gate/dot:",
             "%fusion.3 = f32[]": "jit(prefill)/CausalLM/layers_63/ds.mlp.up/dot:",
             "%fusion.4 = f32[]": "jit(prefill)/CausalLM/ds.head/dot:"}
    line = types.SimpleNamespace(name="XLA Ops", events=[
        event(n, 10 * i, 2) for i, n in enumerate(names)] + [
        event("%fusion.3 = f32[]", 500, 2)])                    # outside every run
    plane = types.SimpleNamespace(name="/device:TPU:0", lines=[line])
    monkeypatch.setattr(tr, "load", lambda path: types.SimpleNamespace(planes=[plane]))
    monkeypatch.setattr(ds, "metadata", lambda path: {0: {
        n: ds.OpMeta(tf_op, "", 0.0, 0.0, 0.0) for n, tf_op in names.items()}})
    assert cross.layer_seconds("x", [(0.0, 0.1)], 36) == pytest.approx(0.004)
    assert cross.layer_seconds("x", [(0.0, 0.1)], 34) == pytest.approx(0.006)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_none_on_a_program_without_what_it_reads(name, trace, monkeypatch):
    """The parent commit cannot build this configuration, and the driver lays
    these files over it: on the parent's programs and configurations each
    reader returns nothing and does not raise."""
    by_scope = name not in ("selective_scan_roofline_pct", "prefill_cross_decoder_dev_ms")
    by_shapes = name not in ("shared_kv_attn_dev_ms_per_step", "window_attn_dev_ms_per_step")
    ctx = _ctx(trace)
    if by_scope:
        monkeypatch.setattr(ds, "table", lambda ctx, program: _table(0.0, 0.0))
        assert _reader(name).read(ctx) is None          # scoped, but not these scopes
        monkeypatch.setattr(ds, "table", lambda ctx, program: None)
        assert _reader(name).read(ctx) is None          # no scoped program at all
        monkeypatch.undo()
    assert _reader(name).read(_ctx(trace, with_device=False)) is None
    if name == "selective_scan_roofline_pct":           # a prefill without the kernel
        assert _reader(name).read(_ctx(trace, scan=False)) is None
    for other in ("bloom-7b1", "sarvam-105b"):          # no ``shapes`` of that name
        with open(os.path.join(REPO, "benchmarks", "chipbench", "configs",
                               other + ".json")) as f:
            if by_shapes:
                monkeypatch.setattr(ds, "table", lambda ctx, program: _table(0.160))
                assert _reader(name).read(_ctx(trace, config=json.load(f))) is None, other
                monkeypatch.undo()
    untraced = types.SimpleNamespace(
        trace_path=None, trace_reduced=None, on_tpu=True, config=_doc(), dirs=DIRS,
        peaks=lambda: PEAKS, result=types.SimpleNamespace(counters={}))
    assert _reader(name).read(untraced) is None
    if by_shapes:
        monkeypatch.setattr(ds, "table", lambda ctx, program: _table(0.160))
        on_cpu = types.SimpleNamespace(**{**vars(_ctx(trace)), "on_tpu": False})
        assert _reader(name).read(on_cpu) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_new_reader_declares_itself_as_the_benchmark_lists_it(name):
    mod = _reader(name)
    unit, layer, moves = READERS[name]
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (name, unit, layer, moves)
    assert mod.KINDS == ("serve_closed",)
    with open(registry.find("layer_metrics", name + ".py", DIRS)) as f:
        assert CELL not in f.read() and CONFIG not in f.read()   # a reader names no cell
    entry = _entry("per_layer", name)
    assert entry == {"name": name, "unit": unit,
                     "better": "lower" if unit == "ms" else "higher",
                     "source": "device_trace", "layer": layer, "moves": moves,
                     "workloads": [CELL]}


def test_the_cell_is_listed_by_name_and_follows_the_accepted_cells():
    """Found by NAME everywhere: where a list ENDS is not held, but that this
    PR's entries come after everything the benchmark had."""
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[:9] == ACCEPTED and cells.index(CELL) >= 9
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs.index(CONFIG) > configs.index("sarvam-105b")
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    cell = _entry("workloads", CELL)
    assert len(cell["why"]) <= 200 and "GB" in cell["why"] and "ONE cache" in cell["why"]
    e2e = {m["name"] for m in registry.metrics_of(BENCH, "end_to_end", CELL)}
    assert {"tpot_mean_ms", "setup_s"} <= e2e <= {"tpot_mean_ms", "setup_s", "ttft_p50_ms"}
    reports = {m["name"] for m in registry.metrics_of(BENCH, "per_layer", CELL)}
    ttft = {n for n in JOINED | set(READERS)
            if _entry("per_layer", n)["moves"] == "ttft_p50_ms"}
    want = JOINED | set(READERS) | NO_LIST
    assert reports == (want if "ttft_p50_ms" in e2e else want - ttft)
    assert all(m["moves"] in e2e for m in BENCH["per_layer"] if m["name"] in reports)
    names = [m["name"] for m in BENCH["per_layer"]]
    accepted_last = max(names.index(n) for n in names if n not in READERS)
    assert all(names.index(n) > accepted_last for n in READERS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        listed = m.get("workloads", [])
        assert [c for c in cells if c in listed] == listed, m["name"]   # in the cells' order
        if CELL in listed and m["name"] not in READERS:
            assert "sarvam-105b.doc4k32" in listed, m["name"]
    # every reader of a metric this cell reports is a file found by name, and
    # every layer it names stands in PERF.md's list of layers
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for name in reports:
        assert registry.find("layer_metrics", name + ".py", DIRS)
        assert _entry("per_layer", name)["layer"] in perf


# ---------------------------------------------------------------- the rehearsal
def test_the_cells_rehearsal_ends_in_one_correct_line(tmp_path):
    """The cell as the driver runs it, here at the rehearsal's tiny widths:
    routes, parity with ``engine.generate`` and the reference's comparison all
    run, the pool says its rings, nothing compiles inside the window."""
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
    assert "reference phi4flash" in out.stdout and "NOT compared" not in out.stdout
    assert out.stdout.count("parity vs engine.generate") == 2
    assert "heads_per_row=2" in out.stdout and "ring_bytes=8192" in out.stdout
    assert "programs compiled or loaded inside the window: 0" in out.stdout


def test_the_rehearsals_programs_open_the_new_scopes():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.decode_fns import (build_paged_decode_chunk,
                                                    build_prefill, make_slot_select_fn)
    from deepspeed_tpu.models.causal_lm import CausalLM, init_cache, phi4flash_cfg
    from deepspeed_tpu.observability.schema import SCOPES, SPANS
    assert SCOPES["attn.shared"][2].startswith("shared_kv_attn_dev_ms_per_step")
    assert SCOPES["attn.window"][2] == "window_attn_dev_ms_per_step"
    assert "selective_scan_roofline_pct" in SCOPES["ssm.update"][2]
    assert {"attn.diff", "ssm.select", "gmu.gate", "gmu.out"} <= set(SCOPES)
    assert {"positions_self", "positions_cross"} <= set(SPANS["serving.prefill"][2])
    assert "ring_bytes" in SPANS["setup.kv_pool"][2]
    view = registry.rehearsal_view(_doc())
    cfg = phi4flash_cfg(max_seq_len=96, dtype=jnp.float32, **view["model"])
    module = CausalLM(cfg)
    params = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    slots, cap, pages = 4, 96, 25
    caches = jax.eval_shape(lambda: init_cache(cfg, slots, cap, kv_shape=(pages, 1, 16, 32)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
    chunk = build_paged_decode_chunk(module, lambda p: p,
                                     make_slot_select_fn(False, 1.0, 0, 1.0), 8, kv_cap=cap)
    text = jax.jit(chunk).lower(
        params, i32(slots, 1), caches, i32(slots, cap // 16), i32(slots),
        jax.ShapeDtypeStruct((slots,), jnp.bool_), i32(slots), i32(slots), i32(slots),
        i32(slots), jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    one = jax.eval_shape(lambda: init_cache(cfg, 1, cap))
    pre = jax.jit(build_prefill(module, lambda p: p)).lower(
        params, i32(1, 64), one, i32(1)).as_text(debug_info=True)
    for scope in ("attn.shared", "attn.window", "attn.diff", "ssm.select", "ssm.update",
                  "gmu.gate", "gmu.out", "kv.append"):
        assert f"ds.{scope}/" in text and f"ds.{scope}/" in pre, scope
    # ONE gather of ONE layer's pages a chunk, whatever reads them
    assert text.count("ds.kv.gather/") > 0 and "layers_10" in text
