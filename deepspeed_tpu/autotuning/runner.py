"""Real experiment runner for the subprocess autotuner lane.

The reference tuner launches actual training jobs per experiment
(``deepspeed/autotuning/autotuner.py:39`` via ``launcher/runner.py:351``); this is
the TPU equivalent: a fresh process (own XLA backend, own HBM — a config that
OOMs kills only this experiment) that builds a REAL engine from the merged
config, times train steps on synthetic data, and writes the scheduler-protocol
result JSON.

The base config carries a ``model`` block telling the runner what to build::

    "model": {
        "factory": "deepspeed_tpu.models:gpt2_model",      # module:callable
        "config_class": "deepspeed_tpu.models:GPT2Config",
        "config": {"vocab_size": 50304, "n_layer": 12, ...},  # class kwargs
        "sample_seq_len": 1024,
        "measure_steps": 20,                                # timed steps
        "warmup_steps": 3,
    }

Override keys are dotted paths into the merged config; ``model.config.*`` keys
therefore tune MODEL knobs (remat policy, attention impl, flash block sizes)
alongside engine knobs (micro batch, zero stage) in one space. Invoke as
``python -m deepspeed_tpu.autotuning.runner --config f --overrides f --out f``
(set ``autotuning.experiment_runner: "deepspeed_tpu.autotuning.runner"``).
"""

import argparse
import importlib
import json
import time


def _resolve(spec: str):
    mod, _, attr = spec.partition(":")
    return getattr(importlib.import_module(mod), attr)


def run_experiment(config: dict, overrides: dict) -> dict:
    from .autotuner import apply_overrides

    merged = apply_overrides(config, overrides)
    merged.pop("autotuning", None)
    model_spec = merged.pop("model", None)
    if not model_spec:
        raise ValueError(
            "runner config needs a 'model' block "
            "({factory, config_class, config, sample_seq_len})")

    import jax
    import numpy as np

    import deepspeed_tpu as ds

    factory = _resolve(model_spec["factory"])
    cfg_cls = _resolve(model_spec["config_class"])
    model_cfg = cfg_cls(**model_spec.get("config", {}))
    seq = int(model_spec.get("sample_seq_len",
                             getattr(model_cfg, "n_positions", 1024)))
    model = factory(model_cfg, sample_seq_len=seq)

    engine, _, _, _ = ds.initialize(model=model, config=merged)
    batch_size = engine.train_batch_size()
    vocab = int(getattr(model_cfg, "vocab_size", 32000))
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, vocab, size=(batch_size, seq),
                                       dtype=np.int32)}

    # ≥1 warmup binds `loss` for the sync below; ≥1 measured step for dt/steps
    warmup = max(1, int(model_spec.get("warmup_steps", 3)))
    steps = max(1, int(model_spec.get("measure_steps", 20)))
    for _ in range(warmup):
        loss = engine.train_batch(batch)
    float(loss)                                   # sync: exclude compile/warmup
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    final_loss = float(loss)                      # sync: all steps retired
    dt = time.perf_counter() - t0

    tokens_per_s = batch_size * seq * steps / dt
    n_params = model_cfg.num_params() if hasattr(model_cfg, "num_params") else 0
    return {"status": "ok",
            "latency_s": dt / steps,
            "throughput": tokens_per_s,
            "flops": 6.0 * n_params * tokens_per_s,   # fwd+bwd estimate
            "loss": final_loss,
            "batch_size": batch_size,
            "devices": jax.device_count()}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--overrides", required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    with open(args.overrides) as f:
        overrides = json.load(f)
    result = run_experiment(config, overrides)
    with open(args.out, "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
