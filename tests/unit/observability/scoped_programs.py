"""The six compiled programs the device scopes are held on, at the rehearsal
widths of the benchmark's configurations, as ``(jitted function, the shapes
of its first call)``: ``train_step`` (scanned layers, ``remat_policy: dots``),
BLOOM's ``decode_chunk`` and one of its prefills, the hybrid's
``decode_chunk``, SDAR's block chunk and LFM2's ``decode_chunk``. Each is taken from the program's own
call path: the engine or the scheduler is built as the benchmark builds it,
one step or one request is run, and every ``jax.jit`` the program makes
meanwhile is recorded with the shapes of its first call. ``null_scopes``
builds the same with ``observability.scope`` patched to a null context."""

import contextlib
import importlib
import json
import os

import jax
import numpy as np

from deepspeed_tpu.observability import schema

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
PROGRAMS = ("train_step", "bloom.decode_chunk", "bloom.prefill",
            "hybrid.decode_chunk", "sdar.decode_chunk", "lfm2.decode_chunk")
_CONFIGS = {"train_step": "gpt2-125m", "bloom": "bloom-7b1",
            "hybrid": "nemotron-3-super-120b-a12b", "sdar": "sdar-30b-a3b-chat",
            "lfm2": "lfm2-8b-a1b"}


class _Recorded:
    """A jitted function that keeps the shapes of its first call."""

    def __init__(self, jitted, name, calls):
        self._jitted, self._name, self._calls = jitted, name, calls

    def __call__(self, *args, **kw):
        if self._name not in self._calls:
            shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    np.shape(x), getattr(x, "dtype", None) or np.asarray(x).dtype),
                (args, kw))
            self._calls[self._name] = (self._jitted, shapes)
        return self._jitted(*args, **kw)

    def __getattr__(self, attr):
        return getattr(self._jitted, attr)


@contextlib.contextmanager
def _recording(calls):
    real = jax.jit

    def jit(fn, **kw):
        return _Recorded(real(fn, **kw), getattr(fn, "__name__", "?"), calls)

    jax.jit = jit
    try:
        yield
    finally:
        jax.jit = real


def _rehearsal(config_name):
    from benchmarks.chipbench import registry
    with open(os.path.join(ROOT, "benchmarks", "chipbench", "configs",
                           config_name + ".json")) as f:
        return registry.rehearsal_view(json.load(f)), registry


def _train(calls):
    from deepspeed_tpu.parallel.mesh import MeshSpec
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    cfg, registry = _rehearsal(_CONFIGS["train_step"])
    m, t = cfg["model"], cfg["train"]
    seq, micro = 64, int(t["micro_batch_per_chip"])
    model_cfg = registry.resolve(cfg["model_builder"])(**m, **t["model_options"])
    model = registry.resolve(cfg["model_factory"])(model_cfg, sample_seq_len=seq)
    engine = DeepSpeedEngine(model=model, config={
        "train_batch_size": micro, "train_micro_batch_size_per_gpu": micro,
        "optimizer": t["optimizer"], "bf16": {"enabled": True},
        "zero_optimization": {"stage": int(t["zero_stage"])},
        "gradient_clipping": t["gradient_clipping"], "steps_per_print": 10 ** 9},
        mesh_spec=MeshSpec({"fsdp": 1}, devices=jax.devices()[:1]), seed=0)
    ids = np.random.default_rng(0).integers(0, m["vocab_size"], (micro, seq))
    with _recording(calls):
        engine.train_batch({"input_ids": ids.astype(np.int32)})


def _serve(which, calls):
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    cfg, registry = _rehearsal(_CONFIGS[which])
    s = cfg["serve"]
    cap = int(s["max_seq_len"])
    model_cfg = registry.resolve(cfg["model_builder"])(max_seq_len=cap, **cfg["model"])
    with _recording(calls):
        engine = InferenceEngine(model_cfg, DeepSpeedInferenceConfig(
            dtype=s["dtype"], max_out_tokens=cap), seed=0)
        sched = ContinuousBatchingScheduler(engine, ServingConfig(
            slots=int(s["slots"]), chunk_size=int(s["chunk_size"]), max_seq_len=cap,
            max_queue=int(s["max_queue"]), kv_pool=s["kv_pool"],
            kv_page_size=int(s["kv_page_size"]),
            kv_total_pages=int(s["kv_total_pages"]),
            prefix_cache=PrefixCacheConfig(**s["prefix_cache"])))
        prompt = np.random.default_rng(0).integers(
            1, model_cfg.vocab_size, size=11).astype(np.int32)
        sched.submit(prompt, max_new_tokens=6)
        sched.run()


class _Null(contextlib.ContextDecorator):
    """What ``scope`` is without its name: a ``with`` and a decorator."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@contextlib.contextmanager
def null_scopes():
    """Every module that opens a scope sees a null context for it."""
    null = lambda name: _Null()   # noqa: E731
    mods = [importlib.import_module(rel[:-3].replace("/", "."))
            for rel in schema.SCOPE_MODULES]
    mods += [importlib.import_module("deepspeed_tpu.observability"),
             importlib.import_module("deepspeed_tpu.observability.trace")]
    saved = [(m, m.scope) for m in mods if hasattr(m, "scope")]
    for m, _ in saved:
        m.scope = null
    try:
        yield
    finally:
        for m, real in saved:
            m.scope = real


def build(program: str):
    """``(jitted, (args, kwargs) as shapes)`` of one of :data:`PROGRAMS`."""
    family, _, name = program.partition(".")
    calls = {}
    jax.clear_caches()
    if family == "train_step":
        _train(calls)
        return calls["train_step"]
    _serve(family, calls)
    return calls[name]


def lowered(program: str):
    """The program traced anew (jax's caches cleared: a cached trace would
    keep the names it was traced under) and lowered for this backend."""
    jitted, (args, kw) = build(program)
    jax.clear_caches()
    return jitted.lower(*args, **kw)
