"""The Granite configuration's benchmark files: its configuration against the
catalog row it copies, the yardstick's arithmetic (``granite_shapes.py``), its
two readers on a synthesised trace (and ``None`` where the program has no such
scope or the configuration no such model), the labelled CPU rehearsal of its
cell with ``serving.clear_state`` among its spans, and the float8 probe of its
logit limit at a small width."""

import glob
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
from benchmarks.chipbench import granite_shapes as gs  # noqa: E402
from benchmarks.chipbench import program_spans as ps  # noqa: E402
from benchmarks.chipbench import registry  # noqa: E402
from benchmarks.chipbench import trace_reduce as tr  # noqa: E402
from test_chipbench_hybrid import _record, rounded_matrices  # noqa: E402

BENCH = registry.load_benchmark(REPO)
DIRS = registry.search_dirs(BENCH, REPO)
CONFIG = "granite-4.0-h-micro"
CELL = "granite-4.0-h-micro.conv64"
READERS = {      # name -> (unit, layer, moves), as each file declares itself
    "ssm_update_roofline_pct": ("%", "compiled steps", "tpot_mean_ms"),
    "granite_decode_hbm_roofline_pct": ("%", "compiled steps", "tpot_mean_ms"),
}
PEAKS = {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9}
SCOPED_DECODE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                             "decode_tiny_scoped.xplane.pb.gz")
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


def _doc():
    with open(registry.config_file_of(BENCH, CONFIG, REPO)) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_number_and_cuts_nothing():
    doc = _doc()
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == doc["reduced"] == []
    assert entry["source"] == doc["source"] and entry["file"].endswith(CONFIG + ".json")
    for key, value in CATALOG.items():
        assert doc[key] == value, key
    assert [i for i, t in enumerate(doc["layer_types"]) if t == "attention"] == \
        [5, 15, 25, 35]
    m = doc["model"]
    for key, value in m.items():             # the builder's keywords: the published ones
        if key in CATALOG:
            assert value == doc[key], key
    assert m["greedy_decode_rows"] == doc["serve"]["slots"] == 64
    s = doc["serve"]
    assert (s["max_seq_len"], s["chunk_size"], s["kv_page_size"], s["max_queue"]) == \
        (2048, 8, 16, 128)
    assert s["kv_total_pages"] == 64 * 128 + 1 and s["prefix_cache"] == {"enabled": False}
    # what the config does not give is listed as assumed, each with its reason
    for item in ("ssm_state_dtype", "init", "time_step_limit", "intermediate_size",
                 "rope", "greedy_decode_rows"):
        assert len(doc["assumed"][item]) > 40, item
    assert doc["chips"] == 1 and "one replica = one chip" in doc["deployment"]
    assert doc["routes"] == {"decode_chunk": [], "prefill_flash_from": 256}
    for key in ("memory_arithmetic", "routes_note", "serve_note"):
        assert len(doc[key]) > 40, key
    # no width differs in the rehearsal's file from what the tiny tests use
    assert set(doc["rehearsal"]["model"]) <= set(m)
    from tests.unit import granite_tiny as gt
    for key, value in doc["rehearsal"]["model"].items():
        if key not in ("vocab_size", "greedy_decode_rows"):
            assert gt.MODEL[key] == value, key


def test_the_arithmetic_reproduces_the_issues_numbers_and_the_programs_count():
    doc = _doc()
    m = doc["model"]
    assert gs.mamba_params(m) + 2048 == 25_849_280
    assert gs.attention_params(m) + 2048 == 10_487_808
    assert gs.mlp_params(m) + 2048 == 50_333_696
    assert gs.params(m) == 3_191_396_096                   # 6.383 GB of bf16
    from deepspeed_tpu.models.causal_lm import granite_hybrid_cfg
    assert gs.params(m) == granite_hybrid_cfg(max_seq_len=2048, **m).num_params()
    assert gs.ssm_state_bytes_per_slot(m) == 36 * 64 * 64 * 128 * 4 == 75_497_472
    assert gs.conv_state_bytes_per_slot(m) == 36 * 3 * 4352 * 2 == 940_032
    assert gs.kv_bytes_per_token(m) == 4 * 2 * 8 * 64 * 2 == 8_192
    # the pool's own count (setup.kv_pool state_bytes) at 64 slots
    assert 64 * (gs.ssm_state_bytes_per_slot(m) + gs.conv_state_bytes_per_slot(m)) \
        == 64 * 76_437_504 == 4_892_000_256
    assert gs.ssm_update_bytes(64, m) == 2 * 64 * 75_497_472 == 9_663_676_416
    step = gs.decode_step_bytes(m, 64, 64 * 320)
    assert 16.3e9 < step < 16.4e9                           # "16.34 GB a step"
    assert 0.58 < gs.ssm_update_bytes(64, m) / step < 0.60  # the state is most of it
    assert step == 2 * gs.params(m) + gs.ssm_update_bytes(64, m) \
        + 2 * 64 * 940_032 + 64 * 320 * 8_192
    # at 64 slots x 64 heads a layer's state is what the hybrid's is at 32 x 128
    assert 64 * 64 * 64 * 128 * 4 == 32 * 128 * 64 * 128 * 4 == 134_217_728
    assert "3,191,396,096" in doc["memory_arithmetic"]
    assert "4,892,000,256" in doc["memory_arithmetic"]


# ------------------------------------------------------- a synthesised trace
def _two_chunks(TA):
    import time
    for n in (1, 2):
        with TA("chipbench.step"):
            with TA("serving.decode_chunk", chunk=n, active_slots=64,
                    request_ids="1 2", slot_steps_run=512) as chunk:
                time.sleep(0.03)
                chunk.set_metadata(tokens_kept=500, deliveries=64, stalled_deliveries=1)


@pytest.fixture(scope="module")
def chunk_trace(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("granite"), _two_chunks)


def _ctx(path, with_device=True):
    red = tr.reduce_trace(path)
    ops, programs = [], []
    for sp in ps.named(ps.load(path), "serving.decode_chunk"):
        a = sp.start + 0.001
        programs.append(("decode_chunk", a, a + 0.024))
        ops += [("fusion.7", a, a + 0.020), ("fusion.9", a + 0.020, a + 0.024)]
    red["devices"] = [{"id": 0, "ops": ops, "asyncs": [], "programs": programs}] \
        if with_device else []
    return types.SimpleNamespace(
        trace_path=path, trace_reduced=red, on_tpu=True, config=_doc(), dirs=DIRS,
        peaks=lambda: PEAKS,
        result=types.SimpleNamespace(counters={"chunk_size": 8,
                                               "live_tokens_mean": 20480.0}))


def _reader(name):
    return registry.load_module("layer_metrics", name, DIRS)


def test_the_readers_on_two_synthetic_chunks(chunk_trace, monkeypatch, capsys):
    ctx = _ctx(chunk_trace)
    m = ctx.config["model"]
    # the whole step: 24 ms a chunk of 8 = 3 ms a step
    need = gs.decode_step_bytes(m, 64, 20480.0)
    assert _reader("granite_decode_hbm_roofline_pct").read(ctx) == pytest.approx(
        100.0 * need / 819.0e9 / 0.003, rel=1e-6)
    out = capsys.readouterr().out
    assert "6.383 of parameters" in out and "9.664 of recurrent state" in out
    assert "of convolution windows" in out and "0.1678 of keys and values" in out
    # the state update by its scope: 16 steps in two chunks, 0.2304 s in ssm.update
    table = ds.Table("decode_chunk", 2, 16.0, {
        ("ssm.update", "forward"): [0.2304, 576, 0.0, 0.0],
        ("ssm.in", "forward"): [0.03, 576, 0.0, 0.0],
        ("mlp.down", "forward"): [0.09, 640, 0.0, 0.0]}, {})
    monkeypatch.setattr(ds, "table", lambda ctx, program: table)
    got = _reader("ssm_update_roofline_pct").read(ctx)
    assert got == pytest.approx(100.0 * 9_663_676_416 / 819.0e9 / 0.0144, rel=1e-6)
    assert 81.9 < got < 82.0
    out = capsys.readouterr().out
    assert "ssm.update: 14.400 ms a step, 0.4000 a layer (36 layers) for 9.664 GB" in out


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_none_on_a_program_without_what_it_reads(name, tmp_path):
    """The parent commit runs no such model: no configuration of its has
    ``mamba_n_heads``, and a program from before the scopes opens no
    ``ssm.update``. Each reader returns nothing and does not raise: on
    Granite's file without a device plane, on another configuration's file,
    on a recorded trace of a scoped program without the scope, and untraced."""
    import time

    def old_program(TA):
        with TA("chipbench.step"):
            with TA("serving.decode_chunk", chunk=1, active_slots=2,
                    request_ids="1 2", slot_steps_run=16) as chunk:
                time.sleep(0.005)
                chunk.set_metadata(tokens_kept=12, deliveries=2, stalled_deliveries=0)

    path = _record(tmp_path, old_program)
    assert _reader(name).read(_ctx(path, with_device=False)) is None
    ctx = _ctx(path)
    if name == "ssm_update_roofline_pct":
        # Granite's file, a device plane, and no xplane metadata with scopes
        assert _reader(name).read(ctx) is None
    for other in ("bloom-7b1", "nemotron-3-super-120b-a12b", "lfm2-8b-a1b"):
        with open(os.path.join(REPO, "benchmarks", "chipbench", "configs",
                               other + ".json")) as f:
            ctx.config = json.load(f)
        assert _reader(name).read(ctx) is None, other
    recorded = types.SimpleNamespace(
        trace_path=SCOPED_DECODE, trace_reduced=tr.reduce_trace(SCOPED_DECODE),
        on_tpu=True, config=_doc(), dirs=DIRS, peaks=lambda: PEAKS,
        result=types.SimpleNamespace(counters={}))
    assert ds.table(recorded, "decode_chunk") is not None     # scoped, but no ssm.update
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


def test_the_cell_reports_the_model_agnostic_readers_and_adds_no_entry():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "conv64", 1)
    assert BENCH["workloads"][-1] is cell and BENCH["configs"][-1]["name"] == CONFIG
    e2e = {m["name"] for m in registry.metrics_of(BENCH, "end_to_end", CELL)}
    assert e2e == {"tpot_mean_ms", "setup_s"}
    reports = {m["name"] for m in registry.metrics_of(BENCH, "per_layer", CELL)}
    assert reports == {
        "sched_host_ms_per_step", "decode_step_dev_ms", "serve_device_idle_pct",
        "tpot_p50_ms.layer", "sched_fetch_idle_ms_per_step", "decode_wasted_step_pct",
        "decode_scoped_pct", "decode_attn_dev_ms_per_step", "decode_head_dev_ms_per_step",
        "decode_per_chunk_dev_ms", "setup_compile_s", "setup_engine_init_s"}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"] if m["name"] in reports)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL            # appended, nothing moved
    # the traffic: conv32's lengths unchanged, 64 callers = the cell's slots
    conv32 = registry.load_json("traffic", "conv32", DIRS)
    conv64 = registry.load_json("traffic", "conv64", DIRS)
    assert conv64["lengths"] == conv32["lengths"] and conv64["kind"] == "serve_closed"
    assert conv64["clients"] == _doc()["serve"]["slots"] == 64
    assert (conv64["document_tokens"], conv64["parity_prompts"],
            conv64["parity_output_tokens"], conv64["traced_seconds"]) == \
        (0, [512, 128], 17, 3.0)
    assert conv64["rehearsal"]["clients"] == 4


def test_the_cells_rehearsal_ends_in_one_correct_line_with_clear_state_in_its_spans(
        tmp_path):
    """The cell as the driver runs it, here at the rehearsal's tiny widths. The
    harness removes its trace when it ends, so the run keeps it (``rmtree``
    made a no-op in the child, its ``TMPDIR`` this test's) and the program's
    spans are read from the xplane as the readers read them."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7", PYTHONPATH="", TMPDIR=str(tmp_path))
    script = os.path.join(REPO, "benchmarks", "chipbench", "run.py")
    keep = ("import runpy, shutil, sys; shutil.rmtree = lambda *a, **k: None; "
            f"sys.argv = [{script!r}] + sys.argv[1:]; "
            f"runpy.run_path({script!r}, run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", keep, "--workload", CELL, "--seed", "3000000019",
         "--seconds", "3", "--trace", "1", "--rehearse-cpu"], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, out.stdout[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0 and last["metrics"] == {}
    assert "reference granite_hybrid" in out.stdout and "NOT compared" not in out.stdout
    assert "parity vs engine.generate" in out.stdout
    # 4 slots x 3 Mamba layers x (8 x 16 x 16 x 4 B of state + 3 x 160 x 2 B of window)
    assert "state_bytes=109824" in out.stdout and "heads_per_row=1" in out.stdout
    assert "programs compiled or loaded inside the window: 0" in out.stdout
    (trace,) = glob.glob(os.path.join(str(tmp_path), "chipbench_*", "trace", "**",
                                      "*.xplane.pb"), recursive=True)
    spans = ps.load(trace)
    cleared = ps.named(spans, "serving.clear_state")
    harvests = ps.named(spans, "serving.harvest")
    assert cleared and all(int(sp.stats["state_bytes"]) == 109824 // 4
                           and 0 <= int(sp.stats["slot"]) < 4 for sp in cleared)
    assert all(any(h.start <= sp.start and sp.end <= h.end for h in harvests)
               for sp in cleared)


@pytest.mark.parametrize("path", ["forward", "decode"])
def test_float8_weights_fail_a_logit_limit_that_the_bf16_program_passes(path):
    """The comparison ``serve_closed.check_reference`` makes of the program's
    forward (largest logit error over the last 8 positions, in spreads of the
    reference's logits) at a small width, four seeds: the bf16 program against
    the float32 reference on its own weights, and the reference on
    float8_e4m3fn matrices against itself. ``forward`` is ``engine.forward``,
    what the harness reads; ``decode`` reads the same 8 positions off the
    DECODE path (40 tokens prefilled under right padding, then one token a step
    through the cache: the window's roll, the one-token state update, decode
    attention), which the harness cannot (the scheduler hands out no logits).
    The configuration's limit is set between chip readings at the published
    widths (PERF.md section 6, PR 44); at this width the same comparison
    separates the two types around ``SMALL_LIMIT``: every program reading lies
    under it, every float8 reading above it, and the configuration's own limit
    holds every program reading here too."""
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
    for seed in range(4):
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
