"""``benchmarks/chipbench/run.py`` as the driver calls it: it finds a TPU or
fails; the labelled CPU rehearsal of each traffic kind runs the same control
flow and ends in one line with exactly the contract's keys."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
RUN = os.path.join("benchmarks", "chipbench", "run.py")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(args, cwd=REPO, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7", PYTHONPATH="")
    return subprocess.run([sys.executable, os.path.join(cwd, RUN)] + args, env=env,
                          cwd=cwd, capture_output=True, text=True, timeout=timeout)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_without_a_tpu_it_exits_2_and_prints_no_result():
    out = _run(["--workload", "gpt2-125m.seq1k", "--seed", "1", "--seconds", "1",
                "--trace", "0"])
    assert out.returncode == 2, out.stderr[-2000:]
    assert not _result_lines(out.stdout)
    assert "no TPU visible" in out.stderr


def test_a_cell_of_four_chips_is_refused_on_fewer(monkeypatch):
    import types
    import jax
    from benchmarks.chipbench import run
    from benchmarks.chipbench.harness import Refused
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [tpu])
    with pytest.raises(Refused, match="asks for 4"):
        run.claim_devices(4, rehearse=False)
    assert run.claim_devices(1, rehearse=False)[1] == [tpu]


def test_an_unknown_workload_fails_before_anything_runs():
    out = _run(["--workload", "no-such.cell", "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse-cpu"])
    assert out.returncode not in (0, 2) and not _result_lines(out.stdout)
    assert "no-such.cell" in out.stderr


def test_alone_with_only_the_benchmarks_files_it_fails(tmp_path):
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(["--workload", "gpt2-125m.seq1k", "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse-cpu"], cwd=str(tmp_path))
    assert out.returncode != 0, out.stdout[-2000:]
    assert "deepspeed_tpu" in out.stderr
    assert not _result_lines(out.stdout)


@pytest.mark.parametrize("workload,trace,seed", [
    ("gpt2-125m.seq1k", "0", "2147483999"),
    ("bloom-7b1.chat", "1", "5"),
    ("bloom-7b1.docqa", "0", "2147483999"),
])
def test_rehearsal_ends_in_one_line_with_exactly_the_contracts_keys(workload, trace, seed):
    out = _run(["--workload", workload, "--seed", seed, "--seconds", "3",
                "--trace", trace, "--rehearse-cpu"])
    assert out.returncode == 0, out.stderr[-3000:]
    assert "REHEARSAL" in out.stdout
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(last) == KEYS, sorted(last)        # no breakdown: nothing ran on a chip
    assert last["correct"] is True, out.stdout[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes",
                                   "memory_peak_in_use_bytes",
                                   "memory_peak_reserved_bytes"}
    assert last["device"]["platform"] == "cpu"
    # a CPU run prints no time and no rate under a metric's name
    assert all(v["unit"] == "%" for v in last["metrics"].values())
    assert "tokens/s/chip" not in out.stdout and "ttft_p50_ms" not in out.stdout
    assert "reference" in out.stdout and "NOT compared" not in out.stdout
    if workload.endswith("docqa"):
        assert "0 failed" in out.stdout and " requests hit" in out.stdout
    if workload.endswith("chat"):
        assert 0.0 <= last["metrics"].get("prefix_hit_pct", {"value": 0.0})["value"] <= 0.0


def test_the_training_driver_hands_numpy_and_keeps_a_block_in_flight(tmp_path, monkeypatch):
    """In process, at the rehearsal's widths: every leaf that reaches
    ``train_batch`` is a numpy array, and block i+1 is dispatched before the
    wait for block i."""
    import jax
    from benchmarks.chipbench import registry
    from benchmarks.chipbench.harness import Context
    from benchmarks.chipbench.probe import Probe
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    import time

    bench = registry.load_benchmark(REPO)
    dirs = registry.search_dirs(bench, REPO)
    cell = registry.cell_of(bench, "gpt2-125m.seq1k")
    config = registry.rehearsal_view(json.load(open(
        registry.config_file_of(bench, cell["config"], REPO))))
    traffic = registry.rehearsal_view(registry.load_json("traffic", cell["traffic"], dirs))
    kind = registry.load_module("traffic_kinds", traffic["kind"], dirs)

    seen = []
    real = DeepSpeedEngine.train_batch

    def spy(self, batch=None, data_iter=None):
        seen.extend(type(v) for v in batch.values())
        return real(self, batch=batch, data_iter=data_iter)

    monkeypatch.setattr(DeepSpeedEngine, "train_batch", spy)
    ctx = Context(cell=cell, config=config, traffic=traffic, kind_name="train",
                  seed=2 ** 31 + 5, seconds=1.0, trace=False, rehearse=True,
                  devices=jax.devices()[:1], probe=Probe(None), t0=time.monotonic(),
                  trace_dir=str(tmp_path / "trace"))
    result = kind.run(ctx)
    assert seen and all(t is np.ndarray for t in seen)
    order = result.counters["dispatch_order"]
    pos = {ev: n for n, ev in enumerate(order)}
    waits = [i for what, i in order if what == "wait" and i >= -1]
    assert len(waits) >= 4
    for i in waits[:-1]:
        assert pos[("dispatch", i + 1)] < pos[("wait", i)], (i, order)
    assert not result.reasons, result.reasons
    assert result.attempted == result.counters["steps"] > 0
    assert result.counters["blocks"] == len(waits) - 1
    assert any(n == "chipbench.block_wait" for n, _, _ in ctx.spans)


def _context(config, traffic, kind_name, devices, tmp_path, dirs=(), seconds=1.0):
    import time
    from benchmarks.chipbench.harness import Context
    from benchmarks.chipbench.probe import Probe
    return Context(cell={"name": "test.cell"}, config=config, traffic=traffic,
                   kind_name=kind_name, seed=5, seconds=seconds, trace=False,
                   rehearse=True, devices=devices, probe=Probe(None),
                   t0=time.monotonic(), trace_dir=str(tmp_path / "trace"),
                   dirs=list(dirs))


def test_a_configuration_of_another_family_runs_with_no_edit(tmp_path, capsys):
    """A later PR's configuration names the program's builder of its model in
    data: a GPT-NeoX-shaped one (rotary, parallel residual, untied head), in a
    directory of its own, is served by the same driver."""
    import jax
    from benchmarks.chipbench import registry
    extra = tmp_path / "morebench"
    (extra / "configs").mkdir(parents=True)
    bloom = json.load(open(os.path.join(REPO, "benchmarks", "chipbench", "configs",
                                        "bloom-7b1.json")))
    neox = registry.rehearsal_view(bloom)
    neox.update(name="neox-tiny", model_builder="deepspeed_tpu.models.causal_lm:gptneox_cfg",
                model={"n_layer": 2, "n_embd": 128, "n_head": 2, "vocab_size": 512})
    del neox["reference"]                        # this test brings no reference
    (extra / "configs" / "neox-tiny.json").write_text(json.dumps(neox))
    bench = registry.load_benchmark(REPO)
    dirs = registry.search_dirs(bench, REPO) + [str(extra)]
    config = registry.load_json("configs", "neox-tiny", dirs)
    traffic = registry.rehearsal_view(registry.load_json("traffic", "chat", dirs))
    kind = registry.load_module("traffic_kinds", traffic["kind"], dirs)
    ctx = _context(config, traffic, traffic["kind"], jax.devices()[:1], tmp_path, dirs)
    result = kind.run(ctx)
    out = capsys.readouterr().out
    assert not result.reasons, result.reasons
    assert result.attempted > 0 and result.failed == 0
    assert "parity vs engine.generate" in out and "names none" in out


def test_the_serving_reference_check_can_fail(tmp_path, capsys):
    """The served tokens against the float32 reference: the reference's own
    choices pass; one token replaced by an unlikely one fails."""
    import types
    import jax
    import jax.numpy as jnp
    from benchmarks.chipbench import registry
    from benchmarks.chipbench.reference import bloom
    from deepspeed_tpu.models import causal_lm
    bench = registry.load_benchmark(REPO)
    dirs = registry.search_dirs(bench, REPO)
    kind = registry.load_module("traffic_kinds", "serve_closed", dirs)
    cfg = causal_lm.bloom_cfg(vocab_size=384, max_seq_len=64, n_embd=64, n_layer=2,
                              n_head=4, dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(1, 384, size=(1, 24)).astype(np.int32)
    params = causal_lm.CausalLM(cfg).init(jax.random.PRNGKey(3), jnp.asarray(ids))["params"]
    prompt, tokens = ids[0, :20], []
    for _ in range(4):                                     # the reference's own greedy
        seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])
        tokens.append(int(np.asarray(bloom.forward(params, seq[None], 4))[0, -1].argmax()))
    config = {"model": {"n_head": 4},
              "reference": {"module": "bloom", "tolerance_spreads": 0.25,
                            "logit_tolerance_spreads": 0.05}}
    ctx = _context(config, {}, "serve_closed", jax.devices()[:1], tmp_path, dirs)
    module = causal_lm.CausalLM(cfg)
    engine = types.SimpleNamespace(
        params=params, forward=lambda x: module.apply({"params": params}, jnp.asarray(x)))
    assert kind.check_reference(ctx, engine, [(prompt, tokens, 0, True)]) == []
    # the program computing in a coarser type than the reference allows
    coarse = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), params)
    engine.forward = lambda x: module.apply({"params": coarse}, jnp.asarray(x))
    reasons = kind.check_reference(ctx, engine, [(prompt, tokens, 0, True)])
    assert len(reasons) == 1 and "spreads off" in reasons[0]
    engine.forward = lambda x: module.apply({"params": params}, jnp.asarray(x))
    logits = np.asarray(bloom.forward(
        params, np.concatenate([prompt, np.asarray(tokens[:2], np.int32)])[None], 4))[0, -1]
    wrong = tokens[:2] + [int(logits.argmin())] + tokens[3:]
    reasons = kind.check_reference(ctx, engine, [(prompt, wrong, 16, False)])
    assert len(reasons) == 1 and "spreads under" in reasons[0]
    assert "prefix hit 16" in capsys.readouterr().out


def test_the_training_reference_check_can_fail(tmp_path):
    import jax
    import jax.numpy as jnp
    from benchmarks.chipbench import registry
    from benchmarks.chipbench.reference import gpt2
    from deepspeed_tpu.models import GPT2Config, gpt2_model
    bench = registry.load_benchmark(REPO)
    dirs = registry.search_dirs(bench, REPO)
    kind = registry.load_module("traffic_kinds", "train", dirs)
    cfg = GPT2Config(vocab_size=256, n_positions=32, n_embd=64, n_layer=2, n_head=4,
                     dropout=0.0, scan_layers=True, dtype=jnp.float32)
    params = gpt2_model(cfg, sample_seq_len=32).init_fn(jax.random.PRNGKey(1))
    ids = np.random.default_rng(1).integers(0, 256, size=(4, 32)).astype(np.int32)
    loss, norm = gpt2.loss_and_grad_norm(params, {"n_head": 4}, ids, rows=2, ln_eps=1e-6)
    whole = jax.value_and_grad(lambda p: gpt2.loss(p, jnp.asarray(ids), 4, 1e-6))(params)
    assert loss == pytest.approx(float(whole[0]), rel=1e-5)        # slices = the batch
    assert norm == pytest.approx(float(jnp.sqrt(sum(
        jnp.sum(g * g) for g in jax.tree_util.tree_leaves(whole[1])))), rel=1e-4)
    config = {"model": {"n_head": 4}, "reference": {
        "module": "gpt2", "ln_eps": 1e-6, "rows_per_slice": 2,
        "loss_rel_tol": 0.005, "grad_norm_rel_tol": 0.03}}
    ctx = _context(config, {}, "train", jax.devices()[:1], tmp_path, dirs)
    step0 = {"params": params, "ids": ids, "loss": loss * 1.001, "grad_norm": norm * 0.99}
    assert kind.check_reference(ctx, step0) == []
    step0.update(loss=loss * 1.02)
    assert len(kind.check_reference(ctx, step0)) == 1
    step0.update(grad_norm=norm * 1.1)
    assert len(kind.check_reference(ctx, step0)) == 2


def test_the_four_chip_zero3_configuration_rehearses_on_four_devices(tmp_path):
    """``configs/gpt2-1.3b-zero3.json`` waits for the PR that adds its cell
    (PERF.md section 7); its files keep running meanwhile: the train driver
    on four CPU devices, ZeRO-3 over fsdp=4, reference check included."""
    import jax
    from benchmarks.chipbench import registry
    if len(jax.devices()) < 4:
        pytest.skip("needs four CPU devices")
    bench = registry.load_benchmark(REPO)
    dirs = registry.search_dirs(bench, REPO)
    config = registry.rehearsal_view(registry.load_json("configs", "gpt2-1.3b-zero3", dirs))
    traffic = registry.rehearsal_view(registry.load_json("traffic", "seq1k", dirs))
    kind = registry.load_module("traffic_kinds", traffic["kind"], dirs)
    ctx = _context(config, traffic, "train", jax.devices()[:4], tmp_path, dirs)
    result = kind.run(ctx)
    assert not result.reasons, result.reasons
    assert result.counters["tokens_per_step"] == 2 * 4 * 64
    assert registry.load_module("layer_metrics", "exposed_collective_pct", dirs).KINDS \
        == ("train",)
