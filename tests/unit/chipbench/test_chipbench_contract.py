"""``BENCHMARK.json`` against the contract, and the harness's finding of files
by name: a cell, configuration, traffic mix or per-layer metric is added by
adding files and entries, never by editing one."""

import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import registry  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
BENCH = registry.load_benchmark(REPO)
DIRS = registry.search_dirs(BENCH, REPO)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert sorted(BENCH) == sorted(["command", "paths", "run_seconds", "configs",
                                    "workloads", "end_to_end", "per_layer"])
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16 and all(PATH.match(p) for p in BENCH["paths"])
    assert len(BENCH["command"]) <= 32
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    # the budget of a full check at the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    assert NAME.match(entry["name"]), entry["name"]
    if "unit" in entry:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]
    allowed = {"configs": {"name", "source", "file", "reduced", "why"},
               "workloads": {"name", "config", "traffic", "chips", "why"},
               "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
               "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                             "workloads"}}
    group = next(g for g in allowed if entry in BENCH[g])
    assert set(entry) <= allowed[group], set(entry) - allowed[group]


def test_names_are_unique_and_bounds_within_limits():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_files(cell):
    w = registry.cell_of(BENCH, cell)
    assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    path = registry.config_file_of(BENCH, w["config"], REPO)
    assert os.path.isfile(path)
    assert any(os.path.abspath(path).startswith(os.path.join(REPO, p) + os.sep)
               for p in BENCH["paths"])
    with open(path) as f:
        config = json.load(f)
    assert config["name"] == w["config"] and config["chips"] == w["chips"]
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert entry["reduced"] == config["reduced"]
    traffic = registry.load_json("traffic", w["traffic"], DIRS)
    kind = registry.load_module("traffic_kinds", traffic["kind"], DIRS)
    assert callable(kind.run)
    e2e = [m["name"] for m in registry.metrics_of(BENCH, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert registry.metrics_of(BENCH, "per_layer", cell)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    mod = registry.load_module("layer_metrics", metric["name"], DIRS)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
        (metric["name"], metric["unit"], metric["layer"], metric["moves"])
    cells = metric.get("workloads", CELLS)
    assert cells and set(cells) <= set(CELLS)
    for cell in cells:
        reported = [m["name"] for m in registry.metrics_of(BENCH, "end_to_end", cell)]
        assert metric["moves"] in reported, (cell, metric["moves"])
        traffic = registry.load_json(
            "traffic", registry.cell_of(BENCH, cell)["traffic"], DIRS)
        assert traffic["kind"] in mod.KINDS      # the cell's kind, not a list of cells
    src = open(registry.find("layer_metrics", metric["name"] + ".py", DIRS)).read()
    assert not any(c in src for c in CELLS), "a metric's file names no cell"


def test_every_config_is_used_and_every_layer_is_in_perf_md():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    perf = open(os.path.join(REPO, "PERF.md")).read()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert layer in perf, layer


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_names_its_builder_and_its_reference_in_data(config):
    """No driver holds a model's name: the configuration names the program's
    builder of its model, and the plain reference its outputs are held to."""
    doc = json.load(open(os.path.join(REPO, config["file"])))
    assert doc["name"] == config["name"] and doc["reduced"] == config["reduced"]
    assert callable(registry.resolve(doc["model_builder"]))
    if "model_factory" in doc:
        assert callable(registry.resolve(doc["model_factory"]))
    ref = registry.load_module("reference", doc["reference"]["module"], DIRS)
    kinds = {registry.load_json("traffic", w["traffic"], DIRS)["kind"]
             for w in BENCH["workloads"] if w["config"] == config["name"]}
    for kind in kinds:
        need = {"train": "loss_and_grad_norm", "serve_closed": "next_token_logits"}[kind]
        assert callable(getattr(ref, need))
        src = open(registry.find("traffic_kinds", kind + ".py", DIRS)).read()
        assert "bloom" not in src.lower() and "gpt2" not in src.lower()
    with pytest.raises(AttributeError):
        registry.resolve("deepspeed_tpu.models.causal_lm:no_such_cfg")


def test_files_added_in_a_new_directory_are_found_with_no_edit(tmp_path):
    extra = tmp_path / "morebench"
    for sub in ("configs", "traffic", "traffic_kinds", "layer_metrics"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "tiny-x.json").write_text(json.dumps(
        {"name": "tiny-x", "chips": 1, "reduced": []}))
    (extra / "traffic" / "burst.json").write_text(json.dumps(
        {"name": "burst", "kind": "serve_open"}))
    (extra / "traffic_kinds" / "serve_open.py").write_text(
        "def run(ctx):\n    return 'ran serve_open'\n")
    (extra / "layer_metrics" / "queue_wait_ms.v2.py").write_text(
        "NAME='queue_wait_ms.v2'\nUNIT='ms'\nLAYER='serve scheduler'\n"
        "MOVES='ttft_p50_ms'\nKINDS=('serve_open',)\n"
        "def read(ctx):\n    return 1.5\n")
    root = tmp_path / "root"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    bench = registry.load_benchmark(str(root))
    bench["paths"].append(os.path.relpath(extra, root))
    bench["workloads"].append({"name": "tiny-x.burst", "config": "tiny-x",
                               "traffic": "burst", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "queue_wait_ms.v2", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "serve scheduler", "moves": "ttft_p50_ms",
                               "workloads": ["tiny-x.burst"]})
    dirs = registry.search_dirs(bench, str(root))
    cell = registry.cell_of(bench, "tiny-x.burst")
    traffic = registry.load_json("traffic", cell["traffic"], dirs)
    assert registry.load_module("traffic_kinds", traffic["kind"], dirs).run(None) \
        == "ran serve_open"
    assert registry.load_json("configs", cell["config"], dirs)["name"] == "tiny-x"
    got = registry.metrics_of(bench, "per_layer", "tiny-x.burst")
    assert [m["name"] for m in got if "workloads" in m] == ["queue_wait_ms.v2"]
    assert registry.load_module("layer_metrics", "queue_wait_ms.v2", dirs).read(None) == 1.5
    # the files this PR wrote are still found first, and unknown names fail
    assert registry.find("traffic", "chat.json", dirs).startswith(registry.HERE)
    with pytest.raises(registry.NotFound):
        registry.load_json("traffic", "no-such-mix", dirs)


def test_rehearsal_view_overlays_one_level():
    doc = {"a": 1, "serve": {"slots": 2, "cap": 576}, "rehearsal": {"serve": {"cap": 96}, "a": 2}}
    assert registry.rehearsal_view(doc) == {"a": 2, "serve": {"slots": 2, "cap": 96}}
