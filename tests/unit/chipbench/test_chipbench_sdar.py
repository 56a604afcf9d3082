"""The SDAR configuration's benchmark files: its configuration against the
catalog row it copies, the yardstick's arithmetic (``sdar_shapes.py``), its
three readers on a synthesised trace (and ``None`` where the program has no
such spans), the labelled CPU rehearsal of its cell, and the fp8 probe of its
logit limit at a small width."""

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

from benchmarks.chipbench import block_trace as bt  # noqa: E402
from benchmarks.chipbench import program_spans as ps  # noqa: E402
from benchmarks.chipbench import registry  # noqa: E402
from benchmarks.chipbench import sdar_shapes as ss  # noqa: E402
from benchmarks.chipbench import trace_reduce as tr  # noqa: E402
from test_chipbench_hybrid import _record, rounded_matrices  # noqa: E402

BENCH = registry.load_benchmark(REPO)
DIRS = registry.search_dirs(BENCH, REPO)
CONFIG = "sdar-30b-a3b-chat"
CELL = "sdar-30b-a3b-chat.conv32"
READERS = ("block_tokens_per_forward", "block_forward_hbm_roofline_pct",
           "moe_gated_ffn_roofline_pct")
PEAKS = {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9}
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
           "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
           "max_position_embeddings": 32768, "max_window_layers": 48,
           "mlp_only_layers": [], "model_type": "sdar_moe",
           "moe_intermediate_size": 768, "norm_topk_prob": True,
           "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
           "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
           "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
           "tie_word_embeddings": False, "use_sliding_window": False,
           "vocab_size": 151936}


def _doc():
    with open(registry.config_file_of(BENCH, CONFIG, REPO)) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_number_and_states_its_cut():
    doc = _doc()
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == doc["reduced"] == ["num_hidden_layers"]
    for key, value in CATALOG.items():
        if key in doc["reduced"]:
            assert doc[key] != value and doc["published"][key] == value
        else:
            assert doc[key] == value, key
    assert doc["num_hidden_layers"] == 8 and 48 % 8 == 0     # 6 whole stages
    m = doc["model"]
    for key, value in m.items():             # the builder's keywords
        if key in CATALOG:
            assert value == doc[key], key
    # what the config does not give is listed as assumed, with the family's defaults
    assert (m["gen_block_length"], m["gen_denoising_steps"], m["mask_token_id"]) == \
        (4, 4, 151669)
    for word in ("gen_block_length", "gen_denoising_steps", "mask_token_id",
                 "gen_confidence_threshold", "sequential"):
        assert word in doc["assumed"]["generation"]
    assert m["experts_held"] == [0, m["num_experts"]]        # nothing is exchanged
    assert m["greedy_decode_rows"] == doc["serve"]["slots"]
    assert doc["serve"]["kv_page_size"] % m["gen_block_length"] == 0
    assert doc["serve"]["chunk_size"] == 2 * (m["gen_denoising_steps"] + 1)
    assert "six" in doc["deployment"] and "pipeline" in doc["deployment"]


def test_the_arithmetic_reproduces_the_published_size_and_the_programs_count():
    m = _doc()["model"]
    assert ss.expert_params(m) == 4_718_592
    assert ss.layer_params_beside_experts(m) == 19_140_864
    layer = 19_140_864 + 128 * 4_718_592
    assert layer == 623_120_640
    whole = 48 * layer + 2_048 + 2 * 151_936 * 2_048
    assert round(whole / 1e9, 2) == 30.53                    # "30B-A3B"
    assert ss.params_held(m) == 5_607_297_024
    from deepspeed_tpu.models.causal_lm import sdar_moe_cfg
    assert ss.params_held(m) == sdar_moe_cfg(max_seq_len=2048, **m).num_params()
    assert ss.kv_bytes_per_token(m) == 16_384
    assert ss.moe_ffn_bytes(1024, m) == 1024 * 9_437_184
    assert ss.moe_ffn_flops(8192, m) == 8192 * 2 * 4_718_592
    forward = ss.forward_bytes(m, 32, 1024, 32 * 400)
    assert 10.6e9 < forward < 11.0e9          # ISSUE 31's "~10.8 GB a forward"
    # memory-bound at the cell's load: 8 rows an expert a forward
    assert ss.moe_ffn_bytes(1024, m) / 819e9 > ss.moe_ffn_flops(8192, m) / 197e12


# ------------------------------------------------------- a synthesised trace
def _two_chunks(TA):
    import time
    time.sleep(0.005)          # room in the window for an execution that starts early
    for n, (touched, kept) in enumerate([(9400, 250), (9300, 256)], 1):
        with TA("chipbench.step"):
            with TA("serving.decode_chunk", chunk=n, active_slots=32,
                    request_ids="1 2", slot_steps_run=320) as chunk:
                time.sleep(0.03)
                chunk.set_metadata(
                    tokens_kept=kept, deliveries=32, stalled_deliveries=1,
                    moe_assignments=81920, moe_experts_touched=touched, forwards=10,
                    block_length=4, blocks_committed=64, positions_unmasked=256)


@pytest.fixture(scope="module")
def chunk_trace(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("sdar"), _two_chunks)


def _ctx(path, with_device=True, early=0.001):
    """Device executions placed ``early`` seconds BEFORE their spans begin,
    as a trace whose two clocks are a millisecond apart shows them."""
    red = tr.reduce_trace(path)
    ops, programs = [], []
    for sp in ps.named(ps.load(path), "serving.decode_chunk"):
        a = sp.start - early
        programs.append(("decode_chunk", a, a + 0.020))
        # per chunk: 15 ms in the expert kernel, 5 ms elsewhere
        ops += [("moe_grouped_ffn.96", a, a + 0.015), ("fusion.9", a + 0.015, a + 0.020)]
    red["devices"] = [{"id": 0, "ops": ops, "asyncs": [], "programs": programs}] \
        if with_device else []
    return types.SimpleNamespace(
        trace_path=path, trace_reduced=red, on_tpu=True, config=_doc(), dirs=DIRS,
        peaks=lambda: PEAKS,
        result=types.SimpleNamespace(counters={"chunk_size": 10,
                                               "live_tokens_mean": 12800.0}))


def _reader(name):
    return registry.load_module("layer_metrics", name, DIRS)


def test_the_readers_on_two_synthetic_chunks(chunk_trace, capsys):
    ctx = _ctx(chunk_trace)
    assert len(bt.decode_chunks(ctx)) == 2       # paired though they start early
    assert _reader("block_tokens_per_forward").read(ctx) == pytest.approx(
        (250 + 256) / (2 * 10 * 32))
    m = ctx.config["model"]
    least = (ss.moe_ffn_bytes(9400, m) + ss.moe_ffn_bytes(9300, m)) / 819.0e9
    assert _reader("moe_gated_ffn_roofline_pct").read(ctx) == pytest.approx(
        100.0 * least / 0.030, rel=1e-6)
    shares = [ss.forward_bytes(m, 32, t / 10, 12800.0) / 819.0e9 / 0.002
              for t in (9400, 9300)]
    assert _reader("block_forward_hbm_roofline_pct").read(ctx) == pytest.approx(
        100.0 * sum(shares) / 2, rel=1e-6)
    out = capsys.readouterr().out
    assert "bound by ['memory']" in out and "blocks committed" in out


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_none_on_a_program_without_generation_by_blocks(name, tmp_path):
    """The parent commit's chunk spans carry no ``forwards``: the reader
    returns nothing and does not raise, with and without a device plane, on
    another configuration's file too."""
    import time

    def old_program(TA):
        with TA("chipbench.step"):
            with TA("serving.decode_chunk", chunk=1, active_slots=2,
                    request_ids="1 2", slot_steps_run=16) as chunk:
                time.sleep(0.005)
                chunk.set_metadata(tokens_kept=12, deliveries=2, stalled_deliveries=0,
                                   moe_assignments=100, moe_experts_touched=50)

    path = _record(tmp_path, old_program)
    ctx = _ctx(path)
    ctx.config = {"model": {"n_layer": 30, "n_embd": 4096, "n_head": 32,
                            "vocab_size": 250880}, "serve": {"slots": 2}}
    assert _reader(name).read(ctx) is None
    assert _reader(name).read(_ctx(path, with_device=False)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_new_reader_is_declared_as_its_file_says(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    mod = _reader(name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
        (entry["name"], entry["unit"], entry["layer"], entry["moves"])
    assert mod.KINDS == ("serve_closed",) and entry["workloads"] == [CELL]


def test_the_cell_reports_what_the_issue_lists_and_not_the_token_waste():
    reports = {m["name"] for m in registry.metrics_of(BENCH, "per_layer", CELL)}
    assert set(READERS) <= reports
    assert {"decode_step_dev_ms", "serve_device_idle_pct", "sched_host_ms_per_step",
            "tpot_p50_ms.layer", "sched_fetch_idle_ms_per_step"} <= reports
    # its steps are forwards, not tokens; no shared prefix; the hybrid's five
    # readers stay the hybrid's (its own test file holds each to one cell)
    assert not reports & {"decode_wasted_step_pct", "prefix_hit_pct",
                          "ssm_decode_dev_ms_per_step", "moe_ffn_roofline_pct",
                          "moe_decode_dev_ms_per_step", "moe_experts_touched_per_step",
                          "hybrid_decode_hbm_roofline_pct"}
    # `ttft_p50_ms` is left off the cell: the median of ~206 first-token times a
    # window spread over six seeds by more than half its bound in the driver's
    # check (PERF.md section 6), and a metric the cell does not report takes the
    # readers that move it (`prefill_dev_ms`, `sched_admit_host_ms`) with it
    e2e = {m["name"] for m in registry.metrics_of(BENCH, "end_to_end", CELL)}
    assert e2e == {"tpot_mean_ms", "setup_s"}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"] if m["name"] in reports)


def test_the_cells_rehearsal_ends_in_one_correct_line():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7", PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3", "--trace", "1",
         "--rehearse-cpu"], env=env, cwd=REPO, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, out.stdout[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "reference sdar_moe" in out.stdout and "NOT compared" not in out.stdout
    assert "parity vs engine.generate" in out.stdout
    assert "generation by blocks of 4" in out.stdout      # the new span attributes
    assert "programs compiled or loaded inside the window: 0" in out.stdout


def test_float8_weights_fail_the_logit_limit_that_the_bf16_program_passes():
    """The comparison ``serve_closed.check_reference`` makes of the program's
    forward (largest logit error over the last 8 positions, in spreads of the
    reference's logits) at a small width, four seeds: the bf16 program through
    ``engine.forward`` (the clean copy beside the masked copies) against the
    float32 reference on its own weights, and the reference on float8_e4m3fn
    matrices against itself. Every program reading lies inside the
    configuration's limit and under every float8 reading; the largest float8
    reading lies outside the limit."""
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.causal_lm import sdar_moe_cfg
    doc = _doc()
    ref = registry.load_module("reference", doc["reference"]["module"], DIRS)
    limit = float(doc["reference"]["logit_tolerance_spreads"])
    model = {**doc["model"], **doc["rehearsal"]["model"], "num_hidden_layers": 4,
             "hidden_size": 128, "vocab_size": 2048, "mask_token_id": 2000}
    program, coarse = [], []
    for seed in range(4):
        eng = InferenceEngine(
            sdar_moe_cfg(max_seq_len=64, init_std=0.05, **model),
            DeepSpeedInferenceConfig(dtype="bfloat16", max_out_tokens=64), seed=seed)
        ids = np.random.default_rng(seed).integers(1, 2000, size=48).astype(np.int32)
        at = np.arange(40, 48)
        want = ref.next_token_logits(eng.params, model, ids, at)
        spread = float(want.std(axis=-1).mean())
        got = np.asarray(eng.forward(ids[None])[0, -8:], np.float32)
        program.append(float(np.abs(got - want).max()) / spread)
        low = ref.next_token_logits(rounded_matrices(eng.params, "float8_e4m3fn"),
                                    model, ids, at)       # the engine's matrices are gone
        coarse.append(float(np.abs(low - want).max()) / spread)
    assert max(program) <= limit < max(coarse), (program, coarse)
    assert max(program) < min(coarse), (program, coarse)
