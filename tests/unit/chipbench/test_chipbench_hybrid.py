"""The hybrid configuration's benchmark files: its configuration against the
published numbers it copies, the yardstick's arithmetic (``hybrid_shapes.py``),
its readers on a synthesised trace (and ``None`` where the program has no such
spans), and the labelled CPU rehearsal of its cell."""

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

from benchmarks.chipbench import hybrid_shapes as hs  # noqa: E402
from benchmarks.chipbench import hybrid_trace as ht  # noqa: E402
from benchmarks.chipbench import program_spans as ps  # noqa: E402
from benchmarks.chipbench import registry  # noqa: E402
from benchmarks.chipbench import trace_reduce as tr  # noqa: E402

BENCH = registry.load_benchmark(REPO)
DIRS = registry.search_dirs(BENCH, REPO)
CONFIG = "nemotron-3-super-120b-a12b"
READERS = ("moe_decode_dev_ms_per_step", "ssm_decode_dev_ms_per_step",
           "moe_experts_touched_per_step", "moe_ffn_roofline_pct",
           "hybrid_decode_hbm_roofline_pct")
PEAKS = {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9}


def _doc():
    with open(registry.config_file_of(BENCH, CONFIG, REPO)) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width_and_states_its_cuts():
    doc = _doc()
    m = doc["model"]
    # the builder's keywords are the published config's, at the published values
    for key, value in m.items():
        if key in ("experts_held", "hybrid_override_pattern", "n_routed_experts"):
            continue
        if key == "level_random_experts":       # the builder's own: see assumed.init
            assert value is True and key in doc["assumed"]["init"]
            continue
        if key == "greedy_decode_rows":         # likewise: the parity check's rows
            assert value == doc["serve"]["slots"] and key in doc["assumed"]
            continue
        assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "num_nextn_predict_layers"]
    pub = doc["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["num_nextn_predict_layers"]) == (88, 512, 1)
    assert m["n_routed_experts"] == 512 and m["experts_held"] == [0, doc["n_routed_experts"]]
    assert doc["num_hidden_layers"] == len(m["hybrid_override_pattern"]) == 11
    # one whole period of the published pattern, in the published ratio 40:40:8
    assert m["hybrid_override_pattern"] in pub["hybrid_override_pattern"]
    counts = [pub["hybrid_override_pattern"].count(c) for c in "ME*"]
    assert counts == [40, 40, 8]
    assert [m["hybrid_override_pattern"].count(c) for c in "ME*"] == [5, 5, 1]
    for key in ("assumed", "deployment", "memory_arithmetic", "routes", "rehearsal"):
        assert doc[key]
    assert doc["serve"]["prefix_cache"] == {"enabled": False}
    assert doc["serve"]["kv_total_pages"] == doc["serve"]["slots"] * (
        doc["serve"]["max_seq_len"] // doc["serve"]["kv_page_size"]) + 1
    for key in ("tolerance_spreads", "logit_tolerance_spreads", "why"):
        assert doc["reference"][key]


def test_the_arithmetic_reproduces_the_published_size_and_the_programs_count():
    m = _doc()["model"]
    assert hs.expert_params(m) == 5_505_024
    assert hs.layer_params_beside_experts(m, "E") == 54_530_560
    assert hs.layer_params_beside_experts(m, "M") == 109_640_064
    assert hs.layer_params_beside_experts(m, "*") == 35_655_680
    whole = 40 * (54_530_560 + 512 * 5_505_024) + 40 * 109_640_064 \
        + 8 * 35_655_680 + 2 * 131_072 * 4_096
    assert round(whole / 1e9, 2) == 120.67                 # "120B-A12B"
    from deepspeed_tpu.models.causal_lm import nemotron_h_cfg
    assert hs.params_held(m) == nemotron_h_cfg(max_seq_len=2048, **m).num_params()
    assert hs.ssm_state_bytes_per_slot(m) == 5 * 128 * 64 * 128 * 4
    assert hs.conv_state_bytes_per_slot(m) == 5 * 10_240 * 3 * 2
    assert hs.kv_bytes_per_token(m) == 1024
    assert hs.moe_ffn_bytes(97, m) == 97 * 11_010_048
    assert hs.moe_ffn_flops(176, m) == 176 * 2 * 5_505_024
    step = hs.decode_step_bytes(m, 32, 5 * 97, 32 * 300)
    assert 9.0e9 < step < 10.0e9            # ISSUE 27's "about 9.4 GB"


# ------------------------------------------------------- a synthesised trace
def _record(tmp_path, body):
    import jax
    from jax.profiler import TraceAnnotation as TA
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TA("chipbench.window"):
            body(TA)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    return path


def _two_chunks(TA):
    import time
    for n, (assigned, touched) in enumerate([(1408, 776), (1400, 760)], 1):
        with TA("chipbench.step"):
            with TA("serving.decode_chunk", chunk=n, active_slots=32,
                    request_ids="1 2", slot_steps_run=256) as chunk:
                time.sleep(0.02)
                chunk.set_metadata(tokens_kept=250, deliveries=32, stalled_deliveries=1,
                                   moe_assignments=assigned, moe_experts_touched=touched)


@pytest.fixture(scope="module")
def chunk_trace(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("hybrid"), _two_chunks)


STATE = "f32[32,128,64,128]"


def _ctx(path, with_device=True, monkeypatch=None):
    red = tr.reduce_trace(path)
    spans = ps.named(ps.load(path), "serving.decode_chunk")
    ops, text, programs = [], [], []
    for sp in spans:
        a = sp.start + 0.001
        programs.append(("decode_chunk", a, a + 0.016))
        # per chunk: 8 ms in the expert kernel, 4 ms on the state, 4 ms elsewhere
        ops += [("moe_grouped_ffn.3", a, a + 0.008), ("fusion.7", a + 0.008, a + 0.012),
                ("fusion.9", a + 0.012, a + 0.016)]
        text += [("%moe_grouped_ffn.3 = f32[2752,1024]{1,0} custom-call(...)", a, a + 0.008),
                 (f"%fusion.7 = {STATE}{{3,2,1,0}} fusion({STATE} %p)", a + 0.008, a + 0.012),
                 ("%fusion.9 = bf16[32,4096]{1,0} fusion(...)", a + 0.012, a + 0.016)]
    red["devices"] = [{"id": 0, "ops": ops, "asyncs": [], "programs": programs}] \
        if with_device else []
    if monkeypatch is not None:
        monkeypatch.setattr(ht, "ops_with_text", lambda p: text if with_device else [])
    doc = _doc()
    return types.SimpleNamespace(
        trace_path=path, trace_reduced=red, on_tpu=True, config=doc, dirs=DIRS,
        peaks=lambda: PEAKS,
        result=types.SimpleNamespace(counters={"chunk_size": 8, "live_tokens_mean": 9600.0}))


def _reader(name):
    return registry.load_module("layer_metrics", name, DIRS)


def test_the_readers_on_two_synthetic_chunks(chunk_trace, monkeypatch, capsys):
    ctx = _ctx(chunk_trace, monkeypatch=monkeypatch)
    assert len(ht.decode_chunks(ctx)) == 2
    assert _reader("moe_decode_dev_ms_per_step").read(ctx) == pytest.approx(1.0, rel=1e-6)
    assert _reader("ssm_decode_dev_ms_per_step").read(ctx) == pytest.approx(0.5, rel=1e-6)
    assert _reader("moe_experts_touched_per_step").read(ctx) == pytest.approx(96.0)
    m = ctx.config["model"]
    least = (hs.moe_ffn_bytes(776, m) + hs.moe_ffn_bytes(760, m)) / 819.0e9
    assert _reader("moe_ffn_roofline_pct").read(ctx) == pytest.approx(
        100.0 * least / 0.016, rel=1e-6)
    need = hs.decode_step_bytes(m, 32, 96.0, 9600.0)
    assert _reader("hybrid_decode_hbm_roofline_pct").read(ctx) == pytest.approx(
        100.0 * need / 819.0e9 / 0.002, rel=1e-6)
    out = capsys.readouterr().out
    assert "bound by ['memory']" in out and "96.0 experts touched" in out


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_none_on_a_program_without_its_spans_or_kernels(
        name, tmp_path, monkeypatch):
    """The parent commit has no such counts on its spans and no such kernel:
    the reader returns nothing and does not raise."""
    import time
    from jax.profiler import TraceAnnotation  # noqa: F401

    def old_program(TA):
        with TA("chipbench.step"):
            with TA("serving.decode_chunk", chunk=1, active_slots=2,
                    request_ids="1 2", slot_steps_run=16) as chunk:
                time.sleep(0.005)
                chunk.set_metadata(tokens_kept=12, deliveries=2, stalled_deliveries=0)

    path = _record(tmp_path, old_program)
    ctx = _ctx(path, monkeypatch=monkeypatch)
    for dev in ctx.trace_reduced["devices"]:
        dev["ops"] = [("fusion.1", s, e) for _, s, e in dev["ops"]]
    monkeypatch.setattr(ht, "ops_with_text", lambda p: [])
    ctx.config = {"model": {"n_layer": 30, "n_embd": 4096, "n_head": 32,
                            "vocab_size": 250880}, "serve": {"slots": 2}}
    assert _reader(name).read(ctx) is None
    assert _reader(name).read(_ctx(path, with_device=False, monkeypatch=monkeypatch)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_new_reader_is_declared_as_its_file_says(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    mod = _reader(name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
        (entry["name"], entry["unit"], entry["layer"], entry["moves"])
    assert mod.KINDS == ("serve_closed",) and len(entry["workloads"]) == 1


def test_the_cells_rehearsal_ends_in_one_correct_line():
    cell = next(w["name"] for w in BENCH["workloads"] if w["config"] == CONFIG)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7", PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "chipbench", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", "3", "--trace", "1",
         "--rehearse-cpu"], env=env, cwd=REPO, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, out.stdout[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(v["unit"] == "%" for v in last["metrics"].values())
    assert "reference nemotron_h" in out.stdout and "NOT compared" not in out.stdout
    assert "state_bytes=" in out.stdout          # the state pool's set-up phase
    assert "serving/moe_experts_touched_total" in out.stdout


def rounded_matrices(tree, dtype_name):
    """Every matrix of a parameter tree rounded to the mantissa bits of the
    named type, its range kept (as a deployment that stores a scale beside
    each float8 matrix keeps it; without one, weights of 0.02 fall under
    float8's smallest normal number). ``reduce_precision`` and no pair of
    casts: the compiler may keep the excess precision of a cast down and up
    again. This plants "the nearest precision below" from OUTSIDE the
    reference, which has no option for it; the chip readings in the
    configuration's ``reference.why`` are this function at the published
    widths."""
    import jax
    import jax.numpy as jnp
    nmant = jnp.finfo(jnp.dtype(dtype_name)).nmant
    one = jax.jit(lambda a: jax.lax.reduce_precision(
        a.astype(jnp.float32), 8, nmant).astype(a.dtype), donate_argnums=0)
    # a leaf keeps its type (fewer mantissa bits fit) and gives up its buffer:
    # at the published widths two copies of the tree do not fit the chip
    return jax.tree_util.tree_map(lambda a: one(a) if a.ndim >= 2 else a, tree)


def test_float8_weights_fail_the_logit_limit_that_the_bf16_program_passes():
    """The comparison ``serve_closed.check_reference`` makes (largest logit
    error over the last 8 positions, in spreads of the reference's logits) at
    the tests' small width, four seeds: the bf16 program against the float32
    reference on its own weights, and the reference on float8_e4m3fn weights
    against itself. Every program reading lies inside the configuration's
    limit and under every float8 reading; the largest float8 reading lies
    outside the limit (the small width reads 0.49-0.62 where the published
    one reads 0.67-0.86 on the chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, os.path.join(REPO, "tests", "unit"))
    import hybrid_tiny as tiny
    ref = tiny.reference()
    limit = float(_doc()["reference"]["logit_tolerance_spreads"])
    program, coarse = [], []
    for seed in range(4):
        module, params = tiny.init(tiny.config(dtype=jnp.bfloat16), seed=seed)
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
        ids = tiny.ids(48, seed=seed)[0]
        at = np.arange(40, 48)
        want = ref.next_token_logits(params, tiny.MODEL, ids, at)
        spread = float(want.std(axis=-1).mean())
        got = np.asarray(module.apply({"params": params}, ids[None])[0, -8:],
                         np.float32)
        program.append(float(np.abs(got - want).max()) / spread)
        low = ref.next_token_logits(rounded_matrices(params, "float8_e4m3fn"),
                                    tiny.MODEL, ids, at)    # params' matrices are gone
        coarse.append(float(np.abs(low - want).max()) / spread)
    assert max(program) <= limit < max(coarse), (program, coarse)
    assert max(program) < min(coarse), (program, coarse)
