"""The yardstick's arithmetic on synthetic inputs, the traffic generators'
fixed lengths, and the trace reduction on the recorded trace."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import lengths, probe, registry, shapes, stats  # noqa: E402
from benchmarks.chipbench import trace_reduce as tr  # noqa: E402

BENCH = registry.load_benchmark(REPO)
DIRS = registry.search_dirs(BENCH, REPO)
SAMPLE = os.path.join(REPO, "benchmarks", "chipbench", "testdata",
                      "train_125m_8steps.xplane.pb.gz")


# ------------------------------------------------------------------ arithmetic
def test_block_readings_and_their_median():
    ready = [10.0, 12.0, 14.0, 18.0, 20.0]            # one slow block
    got = stats.block_readings(ready, tokens_per_block=8 * 1000, chips=2)
    assert got == [2000.0, 2000.0, 1000.0, 2000.0]
    import statistics
    assert statistics.median(got) == 2000.0           # the stall does not move it
    assert 8 * 1000 * 4 / (20.0 - 10.0) / 2 == 1600.0  # tokens over wall shows it


def test_percentiles_and_counts_beyond():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50.5
    assert stats.percentile(xs, 90) == pytest.approx(90.1)
    assert stats.beyond(xs, 90) == 10
    assert stats.percentile([7.0], 90) == 7.0
    assert stats.quartiles([1, 2, 3, 4, 5, 6]) == [1.75, 3.5, 5.25]
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_per_request_times():
    t = stats.request_times(submitted=1.0, first_token_at=1.05, last_token_at=2.05,
                            tokens=41)
    assert t["ttft_ms"] == pytest.approx(50.0) and t["tpot_ms"] == pytest.approx(25.0)
    assert stats.request_times(0.0, 0.1, 0.1, 1)["tpot_ms"] is None


def test_compile_span_union_and_kernel_names():
    assert probe.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert probe.union_seconds([(0, 2), (1, 3), (5, 6)], lo=1.5, hi=5.5) == 2.0
    text = ('func.func public @main(%arg0: tensor<1x128xi32>) -> tensor<1xi32> {\n'
            ' %0 = stablehlo.custom_call @tpu_custom_call(%a) {backend_config = '
            '"{}", kernel_name = "flash_fwd"} : () -> ()\n'
            ' %1 = stablehlo.custom_call @tpu_custom_call(%a) {kernel_name = "flash_fwd"}\n}')
    assert probe.kernel_names(text) == {"flash_fwd": 2}
    assert probe.first_int_arg_shape(text) == "1x128"


def test_operation_and_byte_counts():
    assert shapes.gpt2_params(12, 768, 50304, 1024) == 124475904
    assert shapes.gpt2_train_flops_per_token(12, 768, 50304, 1024, 1024) \
        == 6.0 * 124475904 + 12.0 * 12 * 768 * 1024
    f = shapes.flash_call_flops("flash_fwd", 288, 1024, 64)
    assert f == 2 * 2.0 * 288 * 1024 * 1024 * 64 / 2
    assert shapes.flash_call_flops("flash_bwd_dkv", 288, 1024, 64) == 2 * f
    assert shapes.flash_call_bytes("flash_fwd", 288, 1024, 64) == 4 * 2.0 * 288 * 1024 * 64
    assert shapes.kv_bytes_per_token(30, 32, 128) == 491520
    w = shapes.causal_lm_params(30, 4096, 250880) * 2
    assert shapes.decode_step_bytes(30, 4096, 32, 250880, 100) == w + 100 * 491520
    from benchmarks.chipbench.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819.0e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9")


# --------------------------------------------------------------------- traffic
@pytest.mark.parametrize("mix", ["chat", "docqa"])
def test_serving_lengths_are_the_same_for_every_seed(mix):
    traffic = registry.load_json("traffic", mix, DIRS)
    kind = registry.load_module("traffic_kinds", traffic["kind"], DIRS)
    requests = lengths.fixed_requests(traffic, 576)
    assert requests == lengths.fixed_requests(traffic, 576)     # no seed in it
    n = len(requests)

    def first_cycles(seed, cycles=3):
        s = kind.request_stream(requests, seed)
        return [next(s) for _ in range(cycles * n)]

    a, b = first_cycles(1), first_cycles(2 ** 31 + 11)
    assert a != b                                   # the seed orders them
    base = sorted(requests)
    for c in range(3):                              # whole cycles of ONE multiset
        assert sorted(a[c * n:(c + 1) * n]) == base == sorted(b[c * n:(c + 1) * n])
    assert first_cycles(1) == a                     # and the same seed repeats


def test_callers_draw_tokens_from_the_seed_and_keep_a_document_for_four_asks():
    traffic = registry.load_json("traffic", "docqa", DIRS)
    kind = registry.load_module("traffic_kinds", traffic["kind"], DIRS)

    def prompts(seed):
        c = kind.Caller(0, seed, 250880, traffic["document_tokens"],
                        traffic["asks_per_document"])
        out = []
        for _ in range(6):
            out.append(c.prompt(40))
            c.sent += 1
        return out

    p, q = prompts(5), prompts(6)
    doc = traffic["document_tokens"]
    assert all(x.size == doc + 40 and x.dtype == np.int32 for x in p)
    assert all(np.array_equal(p[0][:doc], x[:doc]) for x in p[1:4])   # one document
    assert not np.array_equal(p[0][:doc], p[4][:doc])                 # then the next
    assert not np.array_equal(p[0][doc:], p[1][doc:])                 # new questions
    assert not np.array_equal(p[0], q[0])                             # seed draws tokens
    assert all(np.array_equal(a, b) for a, b in zip(p, prompts(5)))


def test_lengths_are_the_quantiles_of_the_named_distribution():
    spec = {"distribution": "lognormal", "mean": 58.45, "sigma": 0.9, "min": 2, "max": 256}
    q = lengths.quantiles(spec, 200)
    assert q == sorted(q) and q[0] >= 2 and q[-1] <= 256
    import math
    assert q[100] == pytest.approx(58.45 * math.exp(-0.405), abs=1.5)   # the median
    assert sum(q) / 200 == pytest.approx(58.45, rel=0.06)     # the clip takes a little
    with pytest.raises(ValueError):
        lengths.quantiles(dict(spec, distribution="uniform"), 4)
    # a document leaves less room under the cap, and an output is cut to it
    traffic = {"document_tokens": 500, "lengths": {
        "count": 8, "pairing_seed": 1,
        "prompt": dict(spec, mean=19.31, sigma=0.6, min=4),
        "output": spec}}
    pairs = lengths.fixed_requests(traffic, 576)
    assert all(500 + p + o <= 576 and o >= 2 for p, o in pairs)
    assert max(o for _, o in lengths.fixed_requests(dict(traffic, document_tokens=0), 576)) \
        > max(o for _, o in pairs)
    with pytest.raises(ValueError):
        lengths.fixed_requests(dict(traffic, document_tokens=560), 576)
    assert lengths.fixed_requests({"requests": [[3, 4]]}, 576) == [(3, 4)]


@pytest.mark.parametrize("mix,mean_out", [("chat", 58.45), ("docqa", 20.0)])
def test_serving_mixes_are_what_their_files_say(mix, mean_out):
    traffic = registry.load_json("traffic", mix, DIRS)
    spec = traffic["lengths"]
    assert "arXiv" in spec["source"] and spec["assumed"] and spec["cut"]
    assert spec["output"]["mean"] == mean_out and spec["prompt"]["mean"] == 19.31
    pairs = lengths.fixed_requests(traffic, 576)
    assert len(pairs) == spec["count"] == 24
    outs = sorted(o for _, o in pairs)
    assert outs[-1] > 3 * outs[len(outs) // 2]                # a tail
    assert len({(o - 1) % 8 for o in outs}) >= 5              # ends anywhere in a chunk
    assert sum(outs) / len(outs) == pytest.approx(mean_out, rel=0.05)
    assert traffic["document_tokens"] % 16 == 0
    assert all(traffic["document_tokens"] + p + o <= 576 for p, o in pairs)
    # nothing of the list was cut by the cap
    assert sorted(o for _, o in pairs) == sorted(lengths.quantiles(spec["output"], 24))


def test_deliveries_and_the_time_per_token_over_all_requests():
    r = {"delivered": 0, "delivered_at": None, "delivery_gaps": []}
    stats.note_delivery(r, 0, None, 1.0)                 # queued: nothing yet
    stats.note_delivery(r, 9, 1.25, 1.5)                 # first token at 1.25, 8 more
    stats.note_delivery(r, 17, 1.25, 1.7)
    stats.note_delivery(r, 17, 1.25, 1.9)                # a step that brought nothing
    stats.note_delivery(r, 20, 1.25, 2.2)
    assert r["delivery_gaps"] == pytest.approx([0.25, 0.2, 0.5])
    one = {"delivered": 0, "delivered_at": None, "delivery_gaps": []}
    stats.note_delivery(one, 1, 3.0, 3.1)                # only the first token
    stats.note_delivery(one, 4, 3.0, 3.4)
    assert one["delivery_gaps"] == pytest.approx([0.4])


def test_train_stream_has_one_shape_and_seeded_tokens():
    kind = registry.load_module("traffic_kinds", "train", DIRS)
    a = kind.BatchStream(1, 4, 64, 16, 512).next()["input_ids"]
    b = kind.BatchStream(2, 4, 64, 16, 512).next()["input_ids"]
    assert type(a) is np.ndarray and a.shape == b.shape == (4, 64)
    assert a.dtype == np.int32 and a.max() < 512 and not np.array_equal(a, b)
    assert np.array_equal(a[:, :16], a[:, 16:32])            # the motif repeats
    assert np.array_equal(a, kind.BatchStream(1, 4, 64, 16, 512).next()["input_ids"])


def test_pipeline_order_check():
    kind = registry.load_module("traffic_kinds", "train", DIRS)
    good = [("dispatch", -1), ("dispatch", 0), ("wait", -1), ("dispatch", 1),
            ("wait", 0), ("wait", 1)]
    assert kind.check_pipeline(good) == []
    bad = [("dispatch", -1), ("wait", -1), ("dispatch", 0), ("wait", 0)]
    assert kind.check_pipeline(bad)


# ------------------------------------------------------------- trace reduction
def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert tr.union([(0, 2), (5, 9)], lo=1, hi=6) == [(1, 2), (5, 6)]
    assert tr.length([(0, 3), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert tr.op_name("%flash_fwd.13 = (bf16[288,1024,64]{2,1,0}) custom-call(") \
        == "flash_fwd.13"
    assert tr.base_name("flash_fwd.13") == "flash_fwd"
    assert tr.program_name("jit_train_step(14793831365663163517)") == "train_step"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(SAMPLE)


def test_recorded_trace_gives_fixed_numbers(reduced):
    """Eight steps of GPT-2 125M (seq 1024 x micro 24) traced on a v5e chip
    (chip run, PR 24); the sample is under 1 MB."""
    assert os.path.getsize(SAMPLE) < 1_000_000
    assert len(reduced["devices"]) == 1
    assert tr.window_s(reduced) == pytest.approx(1.890474384, abs=1e-8)
    assert tr.device_busy_s(reduced) == pytest.approx(1.89000997, abs=1e-7)
    steps = tr.programs(reduced, "train_step", whole_only=False)
    assert len(steps) == 8
    import statistics
    assert statistics.median(tr.busy_inside(reduced, steps)) == \
        pytest.approx(0.2362457, abs=1e-6)
    by_kernel = tr.op_seconds(reduced)
    assert by_kernel["flash_bwd_dkv"] == pytest.approx(0.17566162, abs=1e-7)
    assert by_kernel["flash_fwd"] == pytest.approx(0.10254870 + 0.10238513, abs=1e-6)
    assert "while" not in by_kernel                     # containers are left out
    assert tr.exposed_collective_s(reduced) == (0.0, 0.0)   # one chip


def test_recorded_trace_breakdown_and_host_spans(reduced):
    names = {n for n, _, _ in reduced["host"]}
    assert {"chipbench.make_batch", "chipbench.step", "chipbench.block_wait",
            "train_step"} <= names
    assert not any(n.startswith("$") for n in names)
    bd = tr.breakdown(reduced)
    assert len(bd["device_ops"]) == 10 and bd["device_ops"][0][0] == "flash_bwd_dkv.10"
    assert [k for k, _ in bd["idle_gaps"]] == ["between_ops_under_20us"]
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(
        tr.window_s(reduced) - tr.device_busy_s(reduced), abs=1e-9)
    # a gap is named by the benchmark's span over it
    host = [("chipbench.window", 0.0, 10.0), ("chipbench.step", 1.0, 4.0),
            ("serving.prefill", 2.0, 3.0)]
    assert tr._covering(host, 2.2, 2.4) == "chipbench.step/serving.prefill"
    assert tr._covering(host, 3.5, 3.6) == "chipbench.step"
    assert tr._covering(host, 5.0, 6.0) == "no_benchmark_span"


def test_layer_metric_readers_on_the_recorded_trace(reduced):
    """The per-layer readers of the training cells, fed the recorded trace."""
    import types
    ctx = types.SimpleNamespace(
        trace_reduced=reduced, on_tpu=True, rehearse=False,
        config={"model": {"n_layer": 12, "n_embd": 768, "n_head": 12,
                          "vocab_size": 50304, "n_positions": 1024}},
        result=types.SimpleNamespace(counters={
            "micro_batch_per_chip": 24, "sequence_length": 1024,
            "tokens_per_step": 24576, "block_median_tokens_per_s_per_chip": 104000.0}),
        chips=1,
        peaks=lambda: {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9})

    def read(name):
        return registry.load_module("layer_metrics", name, DIRS).read(ctx)

    assert read("train_step_dev_ms") == pytest.approx(236.2457, abs=1e-3)
    assert read("train_device_idle_pct") == pytest.approx(0.02457, abs=1e-4)
    # the gaps between the starts of the steps that begin inside the window,
    # on the device's clock
    lo, hi = reduced["window"]
    steps = sorted(s for s, _ in tr.programs(reduced, "train_step", whole_only=False)
                   if lo <= s <= hi)
    assert len(steps) == 7
    rate = 6 * 24576 / (steps[-1] - steps[0])
    assert rate == pytest.approx(104000, rel=2e-3)
    assert read("train_mfu_pct") == pytest.approx(
        100 * rate * shapes.gpt2_train_flops_per_token(12, 768, 50304, 1024, 1024)
        / 197.0e12, abs=1e-6)
    assert 45.0 < read("train_mfu_pct") < 45.8
    assert read("train_block_tokens_per_s_p50") == 104000.0
    assert read("flash_roofline_pct") == pytest.approx(100 * 0.1036 / 0.5072, abs=5e-2)
    assert read("exposed_collective_pct") is None          # one chip: nothing to read
    assert read("decode_step_dev_ms") is None              # no decode_chunk in it
    ctx.trace_reduced = None
    assert read("train_step_dev_ms") is None and read("serve_device_idle_pct") is None
