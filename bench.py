"""Benchmark entry point — prints ONE JSON line.

Runs the BASELINE config-1 workload shape on the attached chip: GPT-2 125M causal-LM
training, ZeRO stage 1, bf16, fused train step (flash-attention Pallas kernel on TPU).
Metric: training throughput in tokens/sec/chip, plus ``tflops_per_chip`` (model FLOPs,
not recompute) and ``mfu`` against the chip's published peak bf16 rate
(``deepspeed_tpu.utils.device.PEAKS``).

``--mode inference`` benches the serving path: p50 TTFT (prefill) + decode tokens/sec on the
flagship model — the second BASELINE north-star (config 5 shape, scaled to one chip).

Every measuring lane needs a TPU and fails without one; ``--smoke`` (with
``JAX_PLATFORMS=cpu``) is the explicit tiny-shape harness check the tests run.
"""

import argparse
import json
import os
import sys
import time


def _sync(x):
    import jax
    return jax.block_until_ready(x)


def require_tpu(what: str, n_devices: int = 1):
    """A measuring lane runs on the chip or not at all."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n_devices:
        raise SystemExit(
            f"bench.py {what}: needs {n_devices} TPU device(s), found "
            f"{len(devs)} x {devs[0].platform} — nothing was measured")


def peak_tflops():
    from deepspeed_tpu.utils.device import device_peaks
    return device_peaks()["bf16_tflops"]


def kernel_gate(mode: str):
    """Compiled-kernel pre-bench check: verify the Mosaic kernels the selected
    bench relies on against their XLA references on the chip before any number
    is recorded, so a kernel regression fails the bench loudly. Checks and
    tolerances live in ``deepspeed_tpu.ops.kernel_checks`` — the SAME source
    the TPU test lane and ``chip_smoke.py`` run, so they cannot drift. Returns
    the per-kernel max-abs-err dict; raises on any failure."""
    from deepspeed_tpu.ops.kernel_checks import run_kernel_checks
    names = {"train": ("flash_fwd", "flash_fwd_bf16", "flash_bwd", "block_sparse"),
             "inference": ("flash_fwd", "flash_alibi", "decode")}[mode]
    return run_kernel_checks(names)


def bench_train():
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, gpt2_model

    import jax

    seq = int(os.environ.get("BENCH_SEQ", 1024))
    micro = int(os.environ.get("BENCH_MICRO", 24))
    steps = int(os.environ.get("BENCH_STEPS", 20))
    warmup = 3

    n_chips = jax.device_count()
    # micro=24 + dots remat (save matmul outputs, recompute elementwise) + length-
    # dispatched attention measured fastest on v5e: 67.8k tok/s vs 62.5k for the
    # round-1 micro=32 full-remat flash config
    # BENCH_VOCAB_CHUNK>0 switches the loss to the chunked-vocab CE (no (b, t, V)
    # logits buffer) — required for the long-sequence shapes (seq 32k+)
    cfg = GPT2Config(vocab_size=50304,  # padded to 128 multiple for MXU tiling
                     n_positions=seq, n_embd=768, n_layer=12, n_head=12,
                     dropout=0.0, remat=True,
                     # "dots" (save matmul outputs) is fastest at the canonical
                     # shape; extreme sequence lengths need "full" remat
                     remat_policy=os.environ.get("BENCH_REMAT_POLICY", "dots"),
                     scan_layers=True,
                     vocab_chunk=int(os.environ.get("BENCH_VOCAB_CHUNK", 0)))
    model = gpt2_model(cfg, sample_seq_len=seq)
    config = {
        "train_batch_size": micro * n_chips,
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "AdamW", "params": {"lr": 2e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "gradient_clipping": 1.0,
        "steps_per_print": 10**9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 50304, size=(micro * n_chips, seq),
                                       dtype=np.int32)}
    for _ in range(warmup):
        loss = engine.train_batch(batch)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    _sync(loss)
    dt = time.perf_counter() - t0

    tokens_per_sec_per_chip = micro * n_chips * seq * steps / dt / n_chips
    flops_per_token = cfg.flops_per_token()          # 6N + attention (model FLOPs, no remat)
    tflops_per_chip = tokens_per_sec_per_chip * flops_per_token / 1e12
    peak = peak_tflops()

    out = {
        "metric": "gpt2_125m_zero1_bf16_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_per_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": 1.0,
        "tflops_per_chip": round(tflops_per_chip, 2),
        "micro_batch": micro,
        "seq": seq,
        "mfu": round(tflops_per_chip / peak, 4),
    }
    print(json.dumps(_with_gate(out)))


def bench_inference():
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import gpt2_cfg

    prompt_len = int(os.environ.get("BENCH_PROMPT", 512))
    # long enough that the differencing signal (dt_long - dt_short ≈ 550ms at 125M)
    # dwarfs host-side jitter
    gen_len = int(os.environ.get("BENCH_GEN", 1536))
    batch = int(os.environ.get("BENCH_INFER_BATCH", 1))
    iters = int(os.environ.get("BENCH_INFER_ITERS", 13))

    # BENCH_MOE_EXPERTS>0 benches the MoE serving path (every 2nd layer's FFN is
    # a gated expert mixture — reference moe_inference.py)
    n_experts = int(os.environ.get("BENCH_MOE_EXPERTS", 0))
    cfg = gpt2_cfg(vocab_size=50304, max_seq_len=prompt_len + gen_len,
                   n_embd=768, n_layer=12, n_head=12, num_experts=n_experts)
    engine = ds.init_inference(model=cfg, config={"dtype": "bfloat16",
                                                  "max_out_tokens": prompt_len + gen_len})

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 50304, size=(batch, prompt_len), dtype=np.int32)

    # warmup (compiles prefill + decode)
    out = engine.generate(ids, max_new_tokens=8)
    _sync(out)

    # Decode tok/s is measured by DIFFERENCING two generation lengths —
    # (T_long - T_short) / (len_long - len_short) — which cancels every constant
    # overhead (prefill, dispatch) exactly.
    assert gen_len >= 16, f"BENCH_GEN must be >= 16 for differencing (got {gen_len})"
    short_len = max(8, gen_len // 4)
    # compile BOTH loop lengths so no timed sample pays XLA compilation
    _sync(engine.generate(ids, max_new_tokens=short_len))
    _sync(engine.generate(ids, max_new_tokens=gen_len))

    def timed(n_tokens):
        t0 = time.perf_counter()
        out = engine.generate(ids, max_new_tokens=n_tokens)
        _sync(out)
        return time.perf_counter() - t0

    ttfts, decode_tps = [], []
    for _ in range(iters):
        dt_long = timed(gen_len)
        ttfts.append(engine.ttft)
        dt_short = timed(short_len)
        per_token = max(dt_long - dt_short, 1e-9) / (gen_len - short_len)
        decode_tps.append(batch / per_token)

    ttft_p50 = sorted(ttfts)[len(ttfts) // 2] * 1e3 if ttfts else None
    tps = sorted(decode_tps)[len(decode_tps) // 2]
    out = {
        "metric": ("gpt2_125m_moe_bf16_decode_tokens_per_sec" if n_experts
                   else "gpt2_125m_bf16_decode_tokens_per_sec"),
        "value": round(tps, 2),
        "unit": "tokens/s",
        "vs_baseline": 1.0,
    }
    if n_experts:
        out["num_experts"] = n_experts
    if ttft_p50 is not None:
        out["ttft_p50_ms"] = round(ttft_p50, 2)
    print(json.dumps(_with_gate(out)))


def bench_train_13b():
    """North-star config 3 (BASELINE.json): GPT-2 1.3B, ZeRO-3 param partitioning —
    scaled to one chip via the host optimizer-offload tier (fp32 masters + moments in
    host RAM; HBM holds bf16 params + grads, which is the only way 1.3B trains on a
    16 GB chip without a pod). Reports BOTH the wall-clock tokens/s (host Adam and
    the H2D/D2H transfers included) and the device-compute-only tokens/s (the jitted
    fwd+bwd step alone), so the host link's share of a step is visible.
    """
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, gpt2_model

    import jax

    seq = int(os.environ.get("BENCH_SEQ", 1024))
    micro = int(os.environ.get("BENCH_13B_MICRO", 4))
    steps = int(os.environ.get("BENCH_13B_STEPS", 2))

    cfg = GPT2Config(vocab_size=50304, n_positions=seq, n_embd=2048, n_layer=24,
                     n_head=16, dropout=0.0, remat=True, remat_policy="dots",
                     scan_layers=True)
    model = gpt2_model(cfg, sample_seq_len=seq)
    config = {
        "train_batch_size": micro,
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3,
                              "offload_optimizer": {"device": "cpu"}},
        "gradient_clipping": 1.0,
        "steps_per_print": 10**9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(engine.state.params))

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 50304, size=(micro, seq), dtype=np.int32)}
    loss = engine.train_batch(batch)      # compile + first host step
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    _sync(loss)
    dt = (time.perf_counter() - t0) / steps
    wall_tps = micro * seq / dt

    # device-compute-only: repeated dispatch of the jitted grad step (no host Adam /
    # transfers in the timed region); N-chain differencing cancels dispatch overhead
    jitted = engine._fns["train_step"]
    gbatch = engine._globalize(engine._reshape_for_gas(batch), leading_gas=True)
    theta = np.float32(1.0)

    def run_n(n):
        t0 = time.perf_counter()
        for _ in range(n):
            st, grads, _m = jitted(engine.state, gbatch, theta)
            engine.state = st
        _sync(_m["loss"])
        return time.perf_counter() - t0

    run_n(1)
    t2, t6 = run_n(2), run_n(6)
    dev_dt = max((t6 - t2) / 4, 1e-9)
    dev_tps = micro * seq / dev_dt

    flops_per_token = cfg.flops_per_token()
    peak = peak_tflops()
    out = {
        "metric": "gpt2_1.3b_zero3_offload_train_tokens_per_sec_per_chip",
        "value": round(wall_tps, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": 1.0,
        "params": n_params,
        "device_compute_tokens_per_sec": round(dev_tps, 2),
        "device_compute_tflops_per_chip": round(dev_tps * flops_per_token / 1e12, 2),
        "micro_batch": micro,
        "seq": seq,
        "device_compute_mfu": round(dev_tps * flops_per_token / 1e12 / peak, 4),
    }
    print(json.dumps(_with_gate(out)))


def bench_inference_7b():
    """North-star config 5 (BASELINE.json): BLOOM-7B serving TTFT — scaled to one
    chip (reference runs TP over v4-16; one v5e chip holds the 7.1B bf16 weights).
    Weights are randomly initialised ON DEVICE (no 14 GB host transfer; TTFT does
    not depend on weight values)."""
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import bloom_cfg

    prompt_len = int(os.environ.get("BENCH_PROMPT", 512))
    iters = int(os.environ.get("BENCH_7B_ITERS", 3))
    # batched serving throughput (reference inference story is per-GPU THROUGHPUT,
    # engine.py:541 forward batching): decode_tokens_per_sec is the batch aggregate
    batch = int(os.environ.get("BENCH_7B_BATCH", 1))

    # BLOOM-7B1 shape: 30 layers, hidden 4096, 32 heads, alibi, vocab 250880
    cfg = bloom_cfg(vocab_size=250880, max_seq_len=prompt_len + 64,
                    n_embd=4096, n_layer=30, n_head=32)
    engine = ds.init_inference(model=cfg, config={"dtype": "bfloat16",
                                                  "max_out_tokens": prompt_len + 64})

    import jax
    import jax.numpy as jnp_
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(batch, prompt_len), dtype=np.int32)

    _sync(engine.generate(ids, max_new_tokens=4))    # compile prefill+decode
    ttfts = []
    for _ in range(iters):
        _sync(engine.generate(ids, max_new_tokens=4))
        ttfts.append(engine.ttft)
    ttft_p50 = sorted(ttfts)[len(ttfts) // 2] * 1e3

    # Pure prefill execution time by k-differencing (cancels dispatch exactly):
    # k sequential prefill dispatches, fetch the last token — (T_k2 - T_k1)/(k2 - k1).
    from deepspeed_tpu.models.causal_lm import init_cache
    prefill, _ = engine._loop_fns(False, 1.0, 0, 1.0, prompt_len + 64)
    caches = init_cache(engine.model_config, batch, prompt_len + 64,
                        dtype=engine.dtype)
    lens0 = jnp_.full((batch,), prompt_len, jnp_.int32)
    ids_dev = jnp_.asarray(ids)
    key = jax.random.PRNGKey(0)

    def prefill_k(k):
        t0 = time.perf_counter()
        for _ in range(k):
            tok0, _, _ = prefill(engine.params, ids_dev, caches, lens0, key)
        _sync(tok0)
        return time.perf_counter() - t0

    prefill_k(1)
    exec_ms = []
    for _ in range(iters):
        t1, t9 = prefill_k(1), prefill_k(9)
        exec_ms.append((t9 - t1) / 8 * 1e3)
    prefill_exec_p50 = sorted(exec_ms)[len(exec_ms) // 2]

    # Steady-state decode tokens/s by generation-length differencing (same
    # methodology as bench_inference): cancels prefill + all constant overhead.
    short_len, long_len = 16, 64
    _sync(engine.generate(ids, max_new_tokens=short_len))
    _sync(engine.generate(ids, max_new_tokens=long_len))
    decode_tps = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _sync(engine.generate(ids, max_new_tokens=long_len))
        dt_long = time.perf_counter() - t0
        t0 = time.perf_counter()
        _sync(engine.generate(ids, max_new_tokens=short_len))
        dt_short = time.perf_counter() - t0
        per_token = max(dt_long - dt_short, 1e-9) / (long_len - short_len)
        decode_tps.append(batch / per_token)
    decode_p50 = sorted(decode_tps)[len(decode_tps) // 2]

    # Executed prefill matmul FLOPs: the head (tied wte, v*d params) runs at ONE
    # position (logits_positions), not all prompt_len — billing it per-position
    # would overstate MFU by ~1.14x at BLOOM's 250k vocab.
    vd = cfg.vocab_size * cfg.n_embd
    flops_prefill = 2.0 * ((cfg.num_params() - vd) * prompt_len + vd) * batch
    prefill_tflops = flops_prefill / (prefill_exec_p50 / 1e3) / 1e12
    peak = peak_tflops()
    # Headline is the single-shot TTFT (host-synced first token);
    # prefill_exec_p50_ms is the k-differenced on-device execution time and the
    # basis for prefill_mfu.
    out = {
        "metric": "bloom_7b_bf16_prefill_ttft_p50_ms",
        "value": round(ttft_p50, 2),
        "unit": "ms",
        "vs_baseline": 1.0,
        "params": cfg.num_params(),
        "prompt_len": prompt_len,
        "prefill_exec_p50_ms": round(prefill_exec_p50, 2),
        "prefill_tflops": round(prefill_tflops, 1),
        "decode_tokens_per_sec": round(decode_p50, 2),
        "batch": batch,
        "prefill_mfu": round(prefill_tflops / peak, 4),
    }
    print(json.dumps(_with_gate(out)))


def bench_overlap(smoke: bool = False, out_path: str = None):
    """Interleaved A/B bench of the comm-overlap paths (one process, alternating
    rounds, so drift hits both sides alike). Emits ONE JSON line and writes
    ``BENCH_OVERLAP_*.json``.

    Two lanes:
    - primitive GEMM A/B: monolithic vs chunked (uni/bidirectional ring)
      allgather-matmul and matmul-reduce-scatter over a ``tensor``-axis mesh;
    - end-to-end decode A/B: two InferenceEngines (overlap off/on) at tp>=2,
      alternating generate() rounds, decode tokens/sec medians.

    Needs >= 2 TPU chips and fails without them (chunk count == tp, so overlap
    headroom only exists at tp >= 2). ``--smoke`` runs tiny shapes on whatever
    devices the environment gives (the tests pass ``JAX_PLATFORMS=cpu`` and
    eight virtual devices): that checks the harness and the bytes-on-wire
    accounting, NOT ICI overlap; ``platform`` in the JSON says which you got.
    """
    import numpy as np

    if not smoke:
        require_tpu("--overlap", 2)

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel.mesh import (AXIS_TENSOR, MeshSpec,
                                             set_global_mesh)
    from deepspeed_tpu.parallel import overlap as ov
    from deepspeed_tpu.utils.comms_logging import (collective_spans,
                                                   spans_overlap_ratio,
                                                   spans_total_bytes)
    from deepspeed_tpu.utils.jax_compat import shard_map

    # largest power of two ≤ device_count (cap 8): the GEMM shapes below are
    # powers of two, so a 6-device host must bench tp=4, not crash on tp=6
    tp = 1 << min(3, jax.device_count().bit_length() - 1)
    mesh = MeshSpec({"tensor": tp}, jax.devices()[:tp])
    on_tpu = jax.default_backend() == "tpu"
    if smoke:
        m, k, n, iters, rounds = 256, 128, 128, 2, 2
    else:
        m, k, n, iters, rounds = 4096, 4096, 4096, 10, 7
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.default_rng(0)
    xg = jnp.asarray(rng.standard_normal((m, k)), dt)    # AG: rows sharded
    wg = jnp.asarray(rng.standard_normal((k, n)), dt)
    xr = jnp.asarray(rng.standard_normal((m, k)), dt)    # RS: k sharded
    wr = jnp.asarray(rng.standard_normal((k, n)), dt)

    def smap(fn, in_specs, out_specs):
        return jax.jit(shard_map(fn, mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                                 in_specs=in_specs, out_specs=out_specs,
                                 check_vma=False))

    ag_specs = ((P(AXIS_TENSOR, None), P(None, None)), P(None, None))
    rs_specs = ((P(None, AXIS_TENSOR), P(AXIS_TENSOR, None)),
                P(AXIS_TENSOR, None))
    lanes = {
        "ag_monolithic": (smap(lambda a, b: ov.allgather_matmul_monolithic(
            a, b, AXIS_TENSOR, site="gemm.ag_monolithic"), *ag_specs),
            (xg, wg)),
        "ag_chunked": (smap(lambda a, b: ov.chunked_allgather_matmul(
            a, b, AXIS_TENSOR, bidirectional=False, site="gemm.ag_chunked"),
            *ag_specs), (xg, wg)),
        "ag_chunked_bidir": (smap(lambda a, b: ov.chunked_allgather_matmul(
            a, b, AXIS_TENSOR, bidirectional=True,
            site="gemm.ag_chunked_bidir"), *ag_specs), (xg, wg)),
        "rs_monolithic": (smap(lambda a, b: ov.matmul_reduce_scatter_monolithic(
            a, b, AXIS_TENSOR, site="gemm.rs_monolithic"), *rs_specs),
            (xr, wr)),
        "rs_chunked": (smap(lambda a, b: ov.chunked_matmul_reduce_scatter(
            a, b, AXIS_TENSOR, bidirectional=False, site="gemm.rs_chunked"),
            *rs_specs), (xr, wr)),
        "rs_chunked_bidir": (smap(lambda a, b: ov.chunked_matmul_reduce_scatter(
            a, b, AXIS_TENSOR, bidirectional=True,
            site="gemm.rs_chunked_bidir"), *rs_specs), (xr, wr)),
    }
    collective_spans.reset()
    for fn, args in lanes.values():                      # compile outside timing
        jax.block_until_ready(fn(*args))
    gemm_spans = collective_spans.summary()
    times = {name: [] for name in lanes}
    for _ in range(rounds):                              # interleaved rounds
        for name, (fn, args) in lanes.items():
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
            times[name].append((time.perf_counter() - t0) / iters)
    med = {name: sorted(ts)[len(ts) // 2] for name, ts in times.items()}

    # ---- decode A/B: overlap off vs on, alternating generate() rounds -------
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import gpt2_cfg
    set_global_mesh(None)
    dec_tp = min(2 if smoke else tp, jax.device_count())
    if smoke:
        n_embd, n_layer, n_head, vocab, gen, prompt, dec_rounds = \
            64, 2, 4, 256, 8, 8, 2
    else:
        n_embd, n_layer, n_head, vocab, gen, prompt, dec_rounds = \
            768, 12, 12, 50304, 128, 64, 5
    cfg_kw = dict(vocab_size=vocab, max_seq_len=prompt + gen, n_embd=n_embd,
                  n_layer=n_layer, n_head=n_head)
    dtype_key = "bfloat16" if on_tpu else "float32"
    # decode batch must put >= tp rows through each step or
    # _overlap_dense_eligible rejects chunking in the (b, 1, k) decode body
    # and the A/B compares two identical compiled loops
    dec_batch = 2 * dec_tp
    ids = rng.integers(0, vocab, size=(dec_batch, prompt)).astype(np.int32)
    engines, dec_spans = {}, {}
    for name, enabled in (("decode_monolithic", False), ("decode_overlap", True)):
        engines[name] = ds.init_inference(
            model=gpt2_cfg(**cfg_kw),
            config={"dtype": dtype_key, "max_out_tokens": prompt + gen,
                    "tensor_parallel": {"tp_size": dec_tp},
                    "comm_overlap": {"enabled": enabled}})
        # capture each engine's trace spans separately: blending A and B would
        # make overlap_ratio a property of the harness mix, not of either config
        collective_spans.reset()
        engines[name].generate(ids, max_new_tokens=gen)  # compile
        dec_spans[name] = collective_spans.summary()
    dec_tps = {name: [] for name in engines}
    toks = {}
    for _ in range(dec_rounds):                          # interleaved
        for name, e in engines.items():
            toks[name] = e.generate(ids, max_new_tokens=gen)
            if e.decode_tps:
                dec_tps[name].append(e.decode_tps)
    greedy_match = bool(np.array_equal(toks["decode_monolithic"],
                                       toks["decode_overlap"]))
    dec_med = {name: (sorted(v)[len(v) // 2] if v else None)
               for name, v in dec_tps.items()}

    def ratio(a, b):
        return round(a / b, 4) if (a and b) else None

    result = {
        "metric": "comm_overlap_interleaved_ab",
        "value": ratio(med["ag_monolithic"], med["ag_chunked_bidir"]) or 0.0,
        "unit": "speedup_x (allgather-matmul, chunked-bidir vs monolithic)",
        "vs_baseline": 1.0,
        "platform": jax.devices()[0].platform,
        "device_count": jax.device_count(),
        "tp": tp,
        "gemm_shape": [m, k, n],
        "gemm_ms": {kk: round(v * 1e3, 3) for kk, v in med.items()},
        "speedup": {
            "ag_chunked": ratio(med["ag_monolithic"], med["ag_chunked"]),
            "ag_chunked_bidir": ratio(med["ag_monolithic"],
                                      med["ag_chunked_bidir"]),
            "rs_chunked": ratio(med["rs_monolithic"], med["rs_chunked"]),
            "rs_chunked_bidir": ratio(med["rs_monolithic"],
                                      med["rs_chunked_bidir"]),
        },
        "decode": {"tp": dec_tp, "gen_tokens": gen,
                   "tokens_per_sec": {kk: round(v, 2) if v else None
                                      for kk, v in dec_med.items()},
                   "speedup": ratio(dec_med["decode_overlap"],
                                    dec_med["decode_monolithic"]),
                   "greedy_tokens_match": greedy_match},
        # honesty: bytes/ratio are the OVERLAP engine's decode+prefill traces
        # only (the config a user would deploy); the monolithic engine's and
        # GEMM lanes' spans ride along for comparison
        "bytes_on_wire_per_trace": spans_total_bytes(
            dec_spans["decode_overlap"]),
        "overlap_ratio": round(
            spans_overlap_ratio(dec_spans["decode_overlap"]), 4),
        "collective_spans": {"gemm": gemm_spans, **dec_spans},
        "method": "interleaved A/B in one process; medians over "
                  "alternating rounds",
        "smoke": bool(smoke),
    }
    if not on_tpu:
        result["note"] = ("virtual CPU mesh: validates the harness and "
                          "bytes-on-wire accounting, NOT ICI overlap — ring "
                          "ppermutes on CPU are memcpy-bound, so chunked can "
                          "measure slower here; judge overlap on tp>=2 TPU")
    out_path = out_path or f"BENCH_OVERLAP_{'smoke' if smoke else 'local'}.json"
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


def bench_wq(smoke: bool = False, out_path: str = None):
    """Interleaved A/B/C bench of weight-streaming quantized decode (``--wq``):
    bf16 vs int8 vs int4 engines on the same weights, alternating generate()
    rounds. Emits ONE JSON line and writes ``BENCH_WQ_*.json``.

    Per lane: decode tokens/sec (generation-length differencing — cancels
    prefill + dispatch overhead exactly), TTFT, greedy-token parity rate vs the
    bf16 lane, and the engine's modeled weight-stream bytes per step
    (``weight_stream_report`` — the fused kernel's own block accounting:
    payload + scales, each block read exactly once).

    Needs a TPU and fails without one. ``--smoke`` runs a tiny model on
    whatever backend the environment gives (the tests pass
    ``JAX_PLATFORMS=cpu``): decode there runs the XLA path (hoisted whole-tree
    dequant), so tok/s ratios check the harness, NOT HBM streaming; the
    modeled bytes reduction is the meaningful figure (``platform`` says which
    you got). On a TPU the 7B lanes run SEQUENTIALLY (bf16 + int8
    engines do not co-fit in 16 GB HBM); engines share one init seed so
    parity is still apples-to-apples.
    """
    import numpy as np

    if not smoke:
        require_tpu("--wq")

    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import bloom_cfg, gpt2_cfg

    on_tpu = jax.default_backend() == "tpu"
    seq_lanes = on_tpu and not smoke          # 7B lanes don't co-fit in HBM
    if smoke:
        prompt, gen, rounds, batch = 8, 8, 2, 2
        mk_cfg = lambda: gpt2_cfg(vocab_size=256, max_seq_len=prompt + gen,
                                  n_embd=64, n_layer=2, n_head=4)
        dtype_key = "float32"
    else:
        # north-star config 5 shape: 7B weights-dominated single-stream decode
        prompt, gen, rounds, batch = 512, 64, 3, 1
        mk_cfg = lambda: bloom_cfg(vocab_size=250880, max_seq_len=prompt + gen,
                                   n_embd=4096, n_layer=30, n_head=32)
        dtype_key = "bfloat16"

    lane_cfgs = {
        "bf16": {},
        "int8": {"weight_quant": {"enabled": True, "bits": 8}},
        "int4": {"weight_quant": {"enabled": True, "bits": 4}},
    }
    rng = np.random.default_rng(0)
    vocab = mk_cfg().vocab_size
    ids = rng.integers(0, vocab, size=(batch, prompt)).astype(np.int32)
    short_len = max(4, gen // 4)

    def build(name):
        cfg = {"dtype": dtype_key, "max_out_tokens": prompt + gen,
               **lane_cfgs[name]}
        # engines share the default init seed: identical fp weights before
        # quantization, so greedy parity is a property of the quantization
        return ds.init_inference(model=mk_cfg(), config=cfg)

    def warmup(e):
        _sync(e.generate(ids, max_new_tokens=short_len))
        _sync(e.generate(ids, max_new_tokens=gen))

    def one_round(e):
        t0 = time.perf_counter()
        out = e.generate(ids, max_new_tokens=gen)
        _sync(out)
        dt_long = time.perf_counter() - t0
        ttft = e.ttft
        t0 = time.perf_counter()
        _sync(e.generate(ids, max_new_tokens=short_len))
        dt_short = time.perf_counter() - t0
        per_token = (dt_long - dt_short) / (gen - short_len)
        # differencing can go non-positive when the model is so small that
        # noise dominates (smoke lane) — report None rather than a fake tps
        tps = batch / per_token if per_token > 0 else None
        return tps, ttft, np.asarray(out)[:, prompt:]

    # Greedy-token parity is TEACHER-FORCED: each quant engine's per-step
    # argmax over the bf16 lane's own (prompt + generation) context, compared
    # position-wise against the bf16 argmax. Free-running comparison would
    # compound one near-tie flip into a diverged suffix and report the
    # divergence POINT, not the per-token agreement rate.
    def tf_argmax(e, full):
        return np.asarray(e(full))[:, prompt - 1:-1].argmax(-1)

    parity = {}
    if not seq_lanes:
        engines = {name: build(name) for name in lane_cfgs}
        for e in engines.values():
            warmup(e)
        samples = {name: [] for name in engines}
        toks = {}
        for _ in range(rounds):                          # interleaved
            for name, e in engines.items():
                tps, ttft, t = one_round(e)
                samples[name].append((tps, ttft))
                toks[name] = t
        full = np.concatenate([ids, toks["bf16"]], axis=1)
        ref = tf_argmax(engines["bf16"], full)
        for name, e in engines.items():
            if name != "bf16":
                parity[name] = float((tf_argmax(e, full) == ref).mean())
        reports = {name: (e.weight_stream_report(), e.quant_audit)
                   for name, e in engines.items()}
    else:
        samples, toks, reports = {}, {}, {}
        full = ref = None
        for name in lane_cfgs:                           # sequential: free HBM
            e = build(name)
            warmup(e)
            samples[name] = []
            for _ in range(rounds):
                tps, ttft, t = one_round(e)
                samples[name].append((tps, ttft))
            toks[name] = t
            if name == "bf16":
                full = np.concatenate([ids, toks["bf16"]], axis=1)
                ref = tf_argmax(e, full)
            else:
                parity[name] = float((tf_argmax(e, full) == ref).mean())
            reports[name] = (e.weight_stream_report(), e.quant_audit)
            del e
            import gc
            gc.collect()

    def med(vals):
        s = sorted(vals)
        return s[len(s) // 2] if s else None

    result_lanes = {}
    for name, ss in samples.items():
        tps_med = med([t for t, _ in ss if t])
        ttft_med = med([tt for _, tt in ss if tt])
        rep, audit = reports[name]
        lane = {"decode_tokens_per_sec": round(tps_med, 2) if tps_med else None,
                "ttft_ms": round(ttft_med * 1e3, 2) if ttft_med else None}
        if name != "bf16":
            lane["greedy_parity_vs_bf16"] = round(parity[name], 4)
            lane["modeled_step_bytes"] = rep["modeled_step_bytes"]
            lane["modeled_bytes_reduction_total"] = round(
                rep["reduction_total"], 4)
            lane["modeled_bytes_reduction_quantized_nodes"] = round(
                rep["reduction_quantized_nodes"], 4)
            lane["matrices_quantized"] = sum(
                1 for a in audit if a["decision"] == "quantized")
            lane["matrices_kept_fp"] = sum(
                1 for a in audit if a["decision"] != "quantized")
        result_lanes[name] = lane

    def ratio(a, b):
        return round(a / b, 4) if (a and b) else None

    speedup8 = ratio(result_lanes["int8"]["decode_tokens_per_sec"],
                     result_lanes["bf16"]["decode_tokens_per_sec"])
    result = {
        "metric": "weight_quant_decode_interleaved_ab",
        "value": speedup8 or 0.0,
        "unit": "speedup_x (int8 vs bf16 decode tokens/s)",
        "vs_baseline": 1.0,
        "platform": jax.devices()[0].platform,
        "device_count": jax.device_count(),
        "model": {"prompt": prompt, "gen": gen, "batch": batch,
                  "params": mk_cfg().num_params()},
        "lanes": result_lanes,
        "speedup": {"int8": speedup8,
                    "int4": ratio(result_lanes["int4"]["decode_tokens_per_sec"],
                                  result_lanes["bf16"]["decode_tokens_per_sec"])},
        "acceptance": {
            "int8_greedy_parity_ge_0.98":
                result_lanes["int8"]["greedy_parity_vs_bf16"] >= 0.98,
            "modeled_reduction_int8_ge_1.9x":
                result_lanes["int8"]
                ["modeled_bytes_reduction_quantized_nodes"] >= 1.9,
            "modeled_reduction_int4_ge_3.5x":
                result_lanes["int4"]
                ["modeled_bytes_reduction_quantized_nodes"] >= 3.5,
        },
        "method": ("sequential 7B lanes, shared init seed (engines do not "
                   "co-fit in HBM)" if seq_lanes else
                   "interleaved A/B/C in one process; medians over "
                   "alternating rounds"),
        "smoke": bool(smoke),
    }
    if seq_lanes:
        # the 1.4x criterion applies to the 7B weights-dominated lane only —
        # a tiny-model TPU smoke's differencing is noise, not a measurement
        result["acceptance"]["int8_decode_speedup_ge_1.4x"] = \
            bool(speedup8 and speedup8 >= 1.4)
    if not on_tpu:
        result["note"] = (
            "CPU smoke: decode runs the XLA path (hoisted whole-tree "
            "dequant), so tok/s ratios do NOT measure HBM weight streaming — "
            "only the modeled bytes-per-step reduction (kernel block "
            "accounting) means anything here")
    out_path = out_path or f"BENCH_WQ_{'smoke' if smoke else 'local'}.json"
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


def bench_qring(smoke: bool = False, out_path: str = None):
    """Interleaved A/B/C bench of the fused quantized collective-matmul ring
    (``--qring``) at tp=4: (A) monolithic-psum quantized decode — the ground
    truth the ring must match, (B) fp ring (comm_overlap on, fp weights),
    (C) fused quantized ring (int8 weights + int8 EF wire). Emits ONE JSON
    line and writes ``BENCH_QRING_*.json``.

    Gates (in-file): teacher-forced greedy parity of the quantized ring vs
    the monolithic-psum quantized engine >= 0.98 (the ``--wq`` method:
    per-step argmax over lane A's own context — free-running comparison
    would compound one near-tie flip and report the divergence POINT, not
    the per-token agreement rate; the free-running match bool is recorded
    honestly alongside); modeled ring bytes quantized/fp32 <= 0.3; and the
    modeled numbers are never hand-computed — the recorded span, the closed
    form ``analysis.collectives.qring_wire_bytes``, and the jaxpr
    ppermute-operand sum must agree to the byte (``crosscheck.exact``).

    Needs 4 TPU chips (the ring A/B runs at tp=4) and fails without them.
    ``--smoke`` runs a tiny model on whatever devices the environment gives
    (the tests pass ``JAX_PLATFORMS=cpu`` and eight virtual devices); off-TPU
    it FORCES the fused backend (``DS_TPU_WQ_FORCE_FUSED=1``) — otherwise the
    engine's hoisted whole-tree dequant means quant nodes never reach the
    ring at all. Kernels then run in Pallas interpret mode, so tok/s ratios
    check the harness, NOT ICI overlap or MXU throughput; the gated figures
    there are bytes-on-wire + parity (``platform`` says which you got).
    """
    import numpy as np

    if not smoke:
        require_tpu("--qring", 4)

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        os.environ["DS_TPU_WQ_FORCE_FUSED"] = "1"

    import deepspeed_tpu as ds
    from deepspeed_tpu.analysis.collectives import (crosscheck_findings,
                                                    qring_wire_bytes)
    from deepspeed_tpu.models import gpt2_cfg
    from deepspeed_tpu.ops.quantizer.quant import quantize_grouped
    from deepspeed_tpu.parallel import qring as qr
    from deepspeed_tpu.parallel.mesh import (AXIS_TENSOR, MeshSpec,
                                             set_global_mesh)
    from deepspeed_tpu.utils.comms_logging import collective_spans
    from deepspeed_tpu.utils.jax_compat import shard_map

    tp = 4
    if jax.device_count() < tp:
        print(json.dumps({"metric": "qring_interleaved_ab", "value": 0.0,
                          "unit": "error", "error": "needs >= 4 devices"}))
        return 1
    if smoke:
        n_embd, n_layer, n_head, vocab, gen, prompt, rounds = \
            64, 2, 4, 256, 8, 8, 2
    else:
        n_embd, n_layer, n_head, vocab, gen, prompt, rounds = \
            768, 12, 12, 50304, 64, 32, 5
    batch = 2 * tp          # >= tp rows per decode step or the ring is
    qblock = 64             # ineligible and the A/B compares identical loops
    wq = {"enabled": True, "bits": 8, "group": 16}
    dtype_key = "bfloat16" if on_tpu else "float32"
    cfg_kw = dict(vocab_size=vocab, max_seq_len=prompt + gen, n_embd=n_embd,
                  n_layer=n_layer, n_head=n_head)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(batch, prompt)).astype(np.int32)

    lane_cfgs = {
        "mono_quant": {"weight_quant": wq,
                       "comm_overlap": {"enabled": False}},
        "fp_ring": {"comm_overlap": {"enabled": True}},
        "qring": {"weight_quant": wq,
                  "comm_overlap": {"enabled": True, "chunk_bits": 8,
                                   "quant_block": qblock}},
    }
    engines, spans = {}, {}
    for name, extra in lane_cfgs.items():
        engines[name] = ds.init_inference(
            model=gpt2_cfg(**cfg_kw),
            config={"dtype": dtype_key, "max_out_tokens": prompt + gen,
                    "tensor_parallel": {"tp_size": tp}, **extra})
        # per-engine trace spans: blending lanes would make the byte ratio a
        # property of the harness mix, not of either config
        collective_spans.reset()
        engines[name].generate(ids, max_new_tokens=gen)      # compile
        spans[name] = collective_spans.summary()

    tps = {name: [] for name in engines}
    toks = {}
    for _ in range(rounds):                                  # interleaved
        for name, e in engines.items():
            toks[name] = e.generate(ids, max_new_tokens=gen)
            if e.decode_tps:
                tps[name].append(e.decode_tps)
    med = {name: (sorted(v)[len(v) // 2] if v else None)
           for name, v in tps.items()}
    greedy_match = bool(np.array_equal(toks["mono_quant"], toks["qring"]))

    # teacher-forced parity (the --wq method), quantized ring vs
    # monolithic-psum quantized ground truth
    full = np.concatenate([ids, np.asarray(toks["mono_quant"])], axis=1)

    def tf_argmax(e):
        return np.asarray(e(full))[:, prompt - 1:-1].argmax(-1)

    parity = float((tf_argmax(engines["qring"])
                    == tf_argmax(engines["mono_quant"])).mean())

    def ring_bytes(summary):
        # the overlapped ring legs only; the fp all-gather legs are byte-
        # identical across lanes and the monolithic lane has no ring at all
        return sum(rec["bytes_total"] for rec in summary.values()
                   if rec.get("op") == "reduce_scatter")

    rec_ratio = (ring_bytes(spans["qring"]) / ring_bytes(spans["fp_ring"])
                 if ring_bytes(spans["fp_ring"]) else None)

    # machine cross-check at the decode-step o_proj ring shape: the span,
    # the closed form, and the jaxpr must agree to the byte — only then do
    # the modeled numbers below count
    mesh = MeshSpec({"tensor": tp}, jax.devices()[:tp])
    xs = jnp.asarray(rng.standard_normal((batch, n_embd)), jnp.float32)
    qw, sw = quantize_grouped(
        jnp.asarray(rng.standard_normal((n_embd, n_embd)), jnp.float32),
        group_size=wq["group"], bits=8)

    def mk(wb, site):
        def body(a, b, c):
            out, _ = qr.fused_quant_matmul_reduce_scatter(
                a, b, c, AXIS_TENSOR, bits=8, wire_bits=wb,
                quant_block=qblock, site=site)
            return out
        return shard_map(body, mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                         in_specs=(P(None, AXIS_TENSOR),
                                   P(AXIS_TENSOR, None),
                                   P(AXIS_TENSOR, None)),
                         out_specs=P(AXIS_TENSOR, None), check_vma=False)

    crosscheck = {"exact": True}
    for wb, label in ((8, "int8_wire"), (None, "fp32_wire")):
        site = f"bench.qring_{label}"
        before = collective_spans.summary().get(site, {}).get(
            "bytes_total", 0)
        res = crosscheck_findings(mk(wb, site), (xs, qw, sw),
                                  site_prefixes=("bench.",), target=site)
        recorded = collective_spans.summary().get(site, {}).get(
            "bytes_total", 0) - before
        closed = qring_wire_bytes(batch, n_embd, tp, wire_bits=wb,
                                  block=qblock)
        n_err = sum(1 for f in res.findings if f.severity == "error")
        crosscheck[label] = {"recorded_span_bytes": int(recorded),
                             "closed_form_bytes": int(closed),
                             "jaxpr_error_findings": n_err}
        crosscheck["exact"] = bool(crosscheck["exact"]
                                   and recorded == closed and not n_err)
    modeled_ratio = (crosscheck["int8_wire"]["closed_form_bytes"]
                     / crosscheck["fp32_wire"]["closed_form_bytes"])

    def ratio(a, b):
        return round(a / b, 4) if (a and b) else None

    gates = {
        "tf_parity_qring_vs_mono_ge_0.98": parity >= 0.98,
        "modeled_ring_bytes_ratio_le_0.3": modeled_ratio <= 0.3,
        "recorded_engine_ring_bytes_ratio_le_0.3":
            rec_ratio is not None and rec_ratio <= 0.3,
        "crosscheck_exact": bool(crosscheck["exact"]),
    }
    result = {
        "metric": "qring_interleaved_ab",
        "value": round(modeled_ratio, 4),
        "unit": "ring bytes-on-wire, quantized/fp32 (gate <= 0.3)",
        "vs_baseline": 1.0,
        "platform": jax.devices()[0].platform,
        "device_count": jax.device_count(),
        "tp": tp,
        "model": {"prompt": prompt, "gen": gen, "batch": batch,
                  "n_embd": n_embd, "n_layer": n_layer},
        "wire": {"chunk_bits": 8, "quant_block": qblock,
                 "weight_bits": wq["bits"], "weight_group": wq["group"]},
        "decode_tokens_per_sec": {name: round(v, 2) if v else None
                                  for name, v in med.items()},
        "speedup_qring_vs_mono": ratio(med["qring"], med["mono_quant"]),
        "tf_greedy_parity_qring_vs_mono": round(parity, 4),
        "greedy_tokens_match_free_running": greedy_match,
        "ring_bytes_recorded": {name: ring_bytes(spans[name])
                                for name in spans},
        "ring_bytes_ratio_recorded": round(rec_ratio, 4)
        if rec_ratio is not None else None,
        "crosscheck": crosscheck,
        "qring_gates": gates,
        "collective_spans": spans,
        "method": "interleaved A/B/C in one process; medians over "
                  "alternating rounds; parity teacher-forced",
        "smoke": bool(smoke),
    }
    if not on_tpu:
        result["note"] = (
            "virtual CPU mesh, DS_TPU_WQ_FORCE_FUSED=1: interpret-mode "
            "kernels — tok/s ratios validate the harness, NOT ICI overlap "
            "or MXU throughput; the gated figures are parity and the "
            "cross-checked bytes-on-wire model")
    set_global_mesh(None)
    out_path = out_path or f"BENCH_QRING_{'smoke' if smoke else 'local'}.json"
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


def bench_trajectory(root: str = ".", out_json: str = "BENCH_TRAJECTORY.json",
                     out_md: str = "BENCH_TRAJECTORY.md") -> dict:
    """Scrape every ``BENCH_*.json`` headline + gate verdict into ONE
    machine-readable perf record (``--trajectory``).

    The per-PR bench artifacts carry a ``metric``/``value`` headline and, most
    of them, a ``*_gates`` (or ``acceptance``) dict of in-file booleans; this
    emits one row per artifact — file, PR round (from the ``_rNN`` suffix),
    headline metric, gate pass-count, and overall verdict — plus a markdown
    table, so "is the perf record still green, and what did each PR claim?"
    is one file instead of fifteen."""
    import glob
    import re as _re
    rows = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_*.json"))):
        name = os.path.basename(path)
        if name.startswith("BENCH_TRAJECTORY"):
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            rows.append({"file": name, "error": f"{type(e).__name__}: {e}"})
            continue
        m = _re.search(r"_r(\d+)", name)
        head = doc
        gates = None
        for key in sorted(doc):
            if (key.endswith("gates") or key == "acceptance") \
                    and isinstance(doc[key], dict):
                gates = doc[key]
                break
        n_true = n_bool = 0
        if gates is not None:
            for v in gates.values():
                if isinstance(v, bool):
                    n_bool += 1
                    n_true += int(v)
        gates_ok = doc.get("gates_ok")
        if gates_ok is None and n_bool:
            gates_ok = n_true == n_bool
        rows.append({
            "file": name,
            "round": int(m.group(1)) if m else None,
            "metric": head.get("metric"),
            "value": head.get("value"),
            "unit": head.get("unit"),
            "smoke": doc.get("smoke"),
            "gates_true": n_true if n_bool else None,
            "gates_total": n_bool if n_bool else None,
            "gates_ok": gates_ok,
        })
    rows.sort(key=lambda r: (r.get("round") is None, r.get("round") or 0,
                             r["file"]))
    md_lines = [
        "# Bench trajectory",
        "",
        "One row per committed `BENCH_*.json` artifact "
        "(regenerate with `python bench.py --trajectory`).",
        "",
        "| file | round | metric | value | unit | smoke | gates | ok |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if "error" in r:
            md_lines.append(f"| {r['file']} |  | (unreadable: {r['error']}) "
                            "|  |  |  |  |  |")
            continue
        val = r["value"]
        val = f"{val:.4g}" if isinstance(val, (int, float)) else (val or "")
        gates = (f"{r['gates_true']}/{r['gates_total']}"
                 if r["gates_total"] else "")
        ok = {True: "✓", False: "✗", None: ""}[r["gates_ok"]]
        md_lines.append(
            f"| {r['file']} | {r['round'] if r['round'] is not None else ''} "
            f"| {r['metric'] or ''} | {val} | {r['unit'] or ''} "
            f"| {'y' if r['smoke'] else ''} | {gates} | {ok} |")
    md = "\n".join(md_lines) + "\n"
    # the "## Tier-1 window" section is hand-maintained (one line per PR's
    # measured dots/870s — ROADMAP's carried maintenance item); carry it
    # across regenerations instead of clobbering it with the table
    md_path = os.path.join(root, out_md)
    if os.path.exists(md_path):
        with open(md_path) as f:
            prev = f.read()
        marker = prev.find("## Tier-1 window")
        if marker >= 0:
            md += "\n" + prev[marker:].rstrip() + "\n"
    out = {"metric": "bench_trajectory", "artifacts": len(rows),
           # an unreadable artifact is a broken perf record, not a pass;
           # gate-less old artifacts (gates_ok None) still count as ok
           "all_gates_ok": all(r.get("gates_ok") is not False
                               and "error" not in r for r in rows),
           "rows": rows}
    with open(os.path.join(root, out_json), "w") as f:
        json.dump(out, f, indent=1)
    with open(os.path.join(root, out_md), "w") as f:
        f.write(md)
    print(json.dumps({"metric": "bench_trajectory", "artifacts": len(rows),
                      "out": out_json, "md": out_md,
                      "all_gates_ok": out["all_gates_ok"]}))
    return out


_KERNEL_GATE = None


def _with_gate(out: dict) -> dict:
    if _KERNEL_GATE is not None:
        out["kernels_ok"] = True
        out["kernel_max_abs_err"] = {k: round(v, 5)
                                     for k, v in _KERNEL_GATE.items()}
    return out


def main():
    global _KERNEL_GATE
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=["train", "inference"], default=None,
                   help="defaults to the mode the chosen --model implies")
    p.add_argument("--model", choices=["default", "1.3b", "7b"], default="default",
                   help="north-star shapes: --model 1.3b (train, BASELINE config 3) "
                        "or --model 7b (inference, BASELINE config 5)")
    p.add_argument("--skip-kernel-gate", action="store_true",
                   help="skip the compiled-kernel pre-check (debugging only)")
    p.add_argument("--overlap", action="store_true",
                   help="interleaved A/B bench of the comm-overlap paths "
                        "(chunked collective matmuls vs monolithic); emits "
                        "BENCH_OVERLAP_*.json")
    p.add_argument("--wq", action="store_true",
                   help="interleaved A/B/C bench of weight-streaming "
                        "quantized decode (bf16 vs int8 vs int4: decode "
                        "tok/s, greedy parity, modeled bytes-per-step); "
                        "emits BENCH_WQ_*.json")
    p.add_argument("--qring", action="store_true",
                   help="interleaved A/B/C bench of the fused quantized "
                        "collective-matmul ring (monolithic-psum quantized vs "
                        "fp ring vs int8-wire quantized ring: teacher-forced "
                        "greedy parity, machine-cross-checked bytes-on-wire "
                        "ratio); emits BENCH_QRING_*.json")
    p.add_argument("--smoke", action="store_true",
                   help="with --overlap/--wq/--qring: tiny shapes, CPU-safe — "
                        "asserts the A/B harness runs and the JSON is valid")
    p.add_argument("--trajectory", action="store_true",
                   help="scrape every BENCH_*.json gate/headline into "
                        "BENCH_TRAJECTORY.json + a markdown table (the "
                        "machine-readable per-PR perf record); runs offline, "
                        "no model builds")
    p.add_argument("--out", default=None,
                   help="with --overlap/--wq/--qring: output JSON path")
    args = p.parse_args()
    if args.trajectory:
        bench_trajectory()
        return 0
    from deepspeed_tpu.utils.device import enable_compile_cache
    enable_compile_cache()
    if args.smoke and not (args.overlap or args.wq or args.qring):
        p.error("--smoke requires --overlap, --wq or --qring")
    if sum((args.overlap, args.wq, args.qring)) > 1:
        p.error("--overlap/--wq/--qring are separate lanes; pick one")
    if args.overlap:
        return bench_overlap(smoke=args.smoke, out_path=args.out)
    if args.wq:
        return bench_wq(smoke=args.smoke, out_path=args.out)
    if args.qring:
        return bench_qring(smoke=args.smoke, out_path=args.out)
    if args.model == "1.3b" and args.mode == "inference":
        p.error("--model 1.3b is a training benchmark")
    if args.model == "7b" and args.mode == "train":
        p.error("--model 7b is an inference benchmark")
    mode = "inference" if args.model == "7b" or args.mode == "inference" \
        else "train"
    require_tpu(f"--model {args.model} --mode {mode}")
    if not args.skip_kernel_gate:
        try:
            _KERNEL_GATE = kernel_gate(mode)
        except Exception as e:
            print(json.dumps({"metric": "kernel_gate", "value": 0.0, "unit": "ok",
                              "vs_baseline": 0.0, "kernels_ok": False,
                              "error": str(e)}))
            return 1
    if args.model == "1.3b":
        bench_train_13b()
    elif args.model == "7b":
        bench_inference_7b()
    elif mode == "train":
        bench_train()
    else:
        bench_inference()


if __name__ == "__main__":
    sys.exit(main())
