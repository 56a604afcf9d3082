"""Roofline sweep for the 125M training bench shape.

Separates "the bench shape is MXU-shape-bound" from "the kernels leave perf on the
table" by measuring, on the attached chip:

1. the MATMUL-ONLY floor — the transformer's six projections chained at the bench's
   token count, for d_head 64 (n_head 12) and 128 (n_head 6) — i.e. what the MXU
   delivers on these K/N dims with zero attention/softmax/optimizer work;
2. the flash-attention kernel's standalone TFLOP/s at both head dims;
3. the FULL train step's model-FLOPs TFLOP/s across d_head ∈ {64, 128} and
   seq ∈ {1024, 2048, 4096} (per-microbatch tokens held at 24576).

Writes one JSON blob to stdout (the driver-readable artifact).
"""

import json
import time

import numpy as np

def _sync(x):
    return np.asarray(x)


def peak_tflops():
    from deepspeed_tpu.utils.device import device_peaks
    return device_peaks()["bf16_tflops"]


def timed_chain(f, args, x, ks=(16, 128), reps=5):
    """Per-iteration time via chain-length differencing: dispatch and the result
    fetch cost the same for both chain lengths and cancel. Paired short/long runs
    are differenced individually and the MEDIAN difference taken (min-per-length
    then differencing can go negative under jitter)."""
    import jax

    jf = {}
    for k in ks:
        def chain(a, x0, k=k):
            y = x0
            for _ in range(k):
                y = f(a, y)
            return y
        jf[k] = jax.jit(chain)
        _sync(jf[k](args, x).reshape(-1)[0])       # compile + warm
    diffs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _sync(jf[ks[0]](args, x).reshape(-1)[0])
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        _sync(jf[ks[1]](args, x).reshape(-1)[0])
        t_long = time.perf_counter() - t0
        diffs.append((t_long - t_short) / (ks[1] - ks[0]))
    return sorted(diffs)[len(diffs) // 2]


def matmul_floor(tokens=24576, d=768):
    """Six-projection chain: qkv (fused), attn-out, fc-in, fc-out + 2 residual-ish
    matmuls to keep the chain square — reports TFLOP/s over the exact matmul FLOPs."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    W = {
        "qkv": jax.random.normal(key, (d, 3 * d), jnp.bfloat16),
        "o": jax.random.normal(key, (d, d), jnp.bfloat16),
        "f1": jax.random.normal(key, (d, 4 * d), jnp.bfloat16),
        "f2": jax.random.normal(key, (4 * d, d), jnp.bfloat16),
    }
    x = jax.random.normal(key, (tokens, d), jnp.bfloat16)

    def step(W, y):
        qkv = y @ W["qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        o = (q + k + v) @ W["o"]
        h = o @ W["f1"]
        return y + h @ W["f2"]

    dt = timed_chain(step, W, x)
    flops = 2 * tokens * d * (3 * d + d + 4 * d + 4 * d)
    return flops / dt / 1e12


def flash_tflops(seq, n_head, d_head, batch_tokens=24576):
    """Standalone flash kernel fwd TFLOP/s (attention matmul FLOPs, causal-halved)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention.flash import flash_attention

    b = max(1, batch_tokens // seq)
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (b, seq, n_head, d_head), jnp.bfloat16)

    def step(qq, y):
        return flash_attention(y, y, qq, causal=True)

    dt = timed_chain(step, q, q)
    flops = 2 * 2 * b * n_head * seq * seq * d_head / 2   # qk + pv, causal half
    return flops / dt / 1e12


def full_step_tflops(seq, n_head, micro):
    """Model-FLOPs TFLOP/s of the fused train step (bench_train's methodology)."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, gpt2_model

    cfg = GPT2Config(vocab_size=50304, n_positions=seq, n_embd=768, n_layer=12,
                     n_head=n_head, dropout=0.0, remat=True, remat_policy="dots",
                     scan_layers=True)
    model = gpt2_model(cfg, sample_seq_len=seq)
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_batch_size": micro,
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "AdamW", "params": {"lr": 2e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "gradient_clipping": 1.0,
        "steps_per_print": 10**9,
    })
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 50304, size=(micro, seq),
                                       dtype=np.int32)}
    for _ in range(3):
        loss = engine.train_batch(batch)
    _sync(loss)
    steps = 10
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    _sync(loss)
    dt = (time.perf_counter() - t0) / steps
    tok_s = micro * seq / dt
    return tok_s * cfg.flops_per_token() / 1e12, tok_s


def main():
    from deepspeed_tpu.utils.device import enable_compile_cache
    enable_compile_cache()
    peak = peak_tflops()        # raises off-TPU: no published peak, no roofline
    out = {"peak_bf16_tflops": peak, "results": {}}

    out["results"]["matmul_floor_768"] = round(matmul_floor(), 1)

    for d_head, n_head in ((64, 12), (128, 6)):
        for seq in (1024, 2048, 4096):
            key = f"flash_fwd_seq{seq}_dh{d_head}"
            out["results"][key] = round(flash_tflops(seq, n_head, d_head), 1)

    for d_head, n_head in ((64, 12), (128, 6)):
        for seq, micro in ((1024, 24), (2048, 12), (4096, 6)):
            tf, tok = full_step_tflops(seq, n_head, micro)
            out["results"][f"train_seq{seq}_dh{d_head}"] = {
                "tflops": round(tf, 1), "tokens_per_sec": round(tok, 0),
                "mfu": round(tf / peak, 4)}

    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
