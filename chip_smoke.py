#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the train and serve paths still
start on the chip.

Drives both pillars once, through the entry points a user calls, at the full
width of models the repo supports (depth may be cut; weights are random, from
a seed), in ONE process — a chip belongs to one process at a time:

1. the whole kernel gate (``ops/kernel_checks.py``): every Pallas kernel
   compiled by Mosaic against its XLA reference, and the served XLA decode
   attention (live rows, in blocks) against the plain whole-cap form;
2. train — ``ds.initialize`` -> ``engine.train_batch`` on GPT-2 125M at the
   benchmark's shape (seq 1024, micro-batch 24, bf16, ZeRO-1, dots remat,
   scanned layers, ``attention_impl="auto"``): loss finite at every step and
   lower at the last than the first on a fixed batch;
3. serve — ``ds.init_inference(bloom_cfg)`` at BLOOM-7B1 widths ->
   ``ContinuousBatchingScheduler`` on the paged pool with the prefix cache on
   -> requests of mixed prompt lengths submitted while others decode: every
   request ends ``finished`` with exactly the tokens asked for, one prefix
   hit, and one greedy request token-identical to ``engine.generate``;
4. the decode kernel in situ — the same scheduler on a rotary model at the
   same widths cut to 4 layers, whose decode chunk must hold the Mosaic
   ``decode_attention`` call inside its loop, once a layer, and no other;
5. with four chips: GPT-2 1.3B ZeRO-3 over fsdp=4 (no offload) and BLOOM-7B at
   tp=4 through the same scheduler, with per-device placement evidence.

Per phase it prints the compile seconds (trace + lower + backend compile, from
jax's own monitoring events, with persistent-cache hits and misses), the
remaining run seconds, and which implementation each dispatch site traced —
read from the lowered StableHLO jax dumps for every compile (a Mosaic kernel
is a ``tpu_custom_call`` there), not from a flag the code keeps about itself.

It finds a TPU or exits 2 without running anything. ``--rehearse-cpu`` is the
explicit tiny-width CPU rehearsal of the same control flow (the command to run
here before spending chip time); its output is labelled REHEARSAL and carries
no device number. Exit code 0 and a last-line JSON object only when every
phase passed.
"""

import argparse
import gc
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

# Shapes. Widths are the published ones; see PERF.md "Cells" for the cuts.
TRAIN_125M = dict(vocab=50304, seq=1024, micro=24, d=768, layers=12, heads=12,
                  zero=1, steps=6, lr=2e-4)
TRAIN_1P3B = dict(vocab=50304, seq=1024, micro=4, d=2048, layers=24, heads=16,
                  zero=3, steps=6, lr=1e-4)
# prompts: `short` < 128 tokens, `long` >= 512, and two that share `prefix`
# (prefix + tail_a is also the engine.generate parity request; its length is
# exactly a prompt bucket so both paths prefill the same padded shape)
PROMPTS = dict(short=40, long=512, prefix=48, tail_a=16, tail_b=12,
               new_short=40, new_long=16, new_shared=16)
BLOOM_7B = dict(family="bloom", vocab=250880, d=4096, layers=30, heads=32,
                slots=2, cap=576, chunk=8, tp=1)
NEOX_4L = dict(family="gptneox", vocab=50432, d=4096, layers=4, heads=32,
               slots=4, cap=576, chunk=8, tp=1)
BLOOM_7B_TP4 = dict(BLOOM_7B, slots=4, tp=4)

REHEARSAL = dict(
    train=dict(vocab=512, seq=64, micro=2, d=64, layers=2, heads=4, zero=1,
               steps=4, lr=1e-3),
    prompts=dict(short=10, long=64, prefix=16, tail_a=16, tail_b=12,
                 new_short=20, new_long=4, new_shared=5),
    bloom=dict(family="bloom", vocab=512, d=128, layers=2, heads=1, slots=2,
               cap=96, chunk=4, tp=1),
    neox=dict(family="gptneox", vocab=512, d=128, layers=2, heads=1, slots=3,
              cap=96, chunk=4, tp=1),
)

PAGE = 16                       # ServingConfig.kv_page_size default
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class PhaseFailed(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


class Probe:
    """Compile seconds, persistent-cache traffic and lowered modules, by phase."""

    def __init__(self, dump_dir):
        import jax
        self.dump_dir = dump_dir
        self.spans = []                 # (start, end) of every compile event
        self.hits = self.misses = 0
        self._seen = set()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:     # reported at its end
            now = time.perf_counter()
            self.spans.append((now - secs, now))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (time.perf_counter(), self.hits, self.misses)

    def since(self, mark):
        """Compile seconds are the UNION of the event intervals: an inner
        jit traced while an outer one traces reports both, nested."""
        t0, h0, m0 = mark
        wall = time.perf_counter() - t0
        comp, edge = 0.0, t0
        for a, b in sorted(sp for sp in self.spans if sp[1] > t0):
            a = max(a, edge)
            if b > a:
                comp += b - a
                edge = b
        return dict(compile_s=round(comp, 1), run_s=round(wall - comp, 1),
                    cache_hits=self.hits - h0, cache_misses=self.misses - m0)

    def new_modules(self):
        """``[(module name, StableHLO text)]`` lowered since the last call."""
        out = []
        for path in sorted(glob.glob(os.path.join(self.dump_dir, "*.mlir"))):
            if path in self._seen:
                continue
            self._seen.add(path)
            name = os.path.basename(path).split("_", 2)[2]
            name = name.rsplit("_compile", 1)[0].removeprefix("jit_")
            with open(path) as f:
                out.append((name, f.read()))
        return out


def report_sites(tag, modules, sites):
    """Print, per named dispatch site, what its lowered module holds. Returns
    ``{(module name, int arg shapes): [MosaicCall]}`` for the assertions."""
    from deepspeed_tpu.analysis.lowered import main_int_arg_shapes, mosaic_calls
    found = {}
    for name, text in modules:
        calls = mosaic_calls(text)
        if name not in sites and not calls:
            continue
        shapes = tuple(main_int_arg_shapes(text))
        found[(name, shapes)] = calls
        what = ", ".join(
            f"Mosaic {c.kernel} x{c.count}"
            + (" inside the loop" if c.in_loop else "") for c in calls) \
            or "XLA only (no Mosaic call)"
        ids = f" ids={shapes[0]}" if shapes and "prefill" in name else ""
        print(f"[{tag}]   {name}{ids}: {what}", flush=True)
    return found


def kernels_of(found, module, ids=None):
    for (name, shapes), calls in found.items():
        if name == module and (ids is None or (shapes and shapes[0] == ids)):
            return {c.kernel: c for c in calls}
    raise PhaseFailed(f"no lowered module {module!r} (ids={ids}) among "
                      f"{sorted(found)}")


def hbm(tag):
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    if not stats[0]:
        return []
    use = [s.get("bytes_in_use", 0) for s in stats]
    print(f"[{tag}]   HBM in use per device (GB): "
          f"{[round(u / 1e9, 2) for u in use]}; peak "
          f"{[round(s.get('peak_bytes_in_use', 0) / 1e9, 2) for s in stats]}; "
          f"limit {round(stats[0].get('bytes_limit', 0) / 1e9, 2)}", flush=True)
    return use


def placement(tag, params, n, split):
    """Four-chip evidence: every device holds a comparable share, and the
    largest parameter lives on ``n`` devices (``split``: as 1/n shards)."""
    import jax
    big = max(jax.tree_util.tree_leaves(params), key=lambda l: l.size)
    shard = big.addressable_shards[0].data.shape
    use = hbm(tag)
    print(f"[{tag}]   largest param {big.shape} lives as {shard} shards on "
          f"{len(big.sharding.device_set)} devices", flush=True)
    check(len(big.sharding.device_set) == n, f"param not placed on {n} devices")
    if split:
        check(int(np.prod(shard)) * n == big.size,
              f"param not split {n} ways: {big.shape} -> {shard}")
    check(not use or min(use) > 0.5 * max(use),
          f"devices hold uneven shares: {use}")


def phase_kernel_gate(tag, probe):
    from deepspeed_tpu.analysis.lowered import mosaic_calls
    from deepspeed_tpu.ops.kernel_checks import (KERNEL_CHECKS, XLA_FORMS,
                                                 run_kernel_checks)
    bad = []
    for name in KERNEL_CHECKS:
        try:
            err = run_kernel_checks([name])[name]
            kernels = sorted({c.kernel for _, text in probe.new_modules()
                              for c in mosaic_calls(text)})
            check(bool(kernels) != (name in XLA_FORMS),
                  "the check compiled no Mosaic kernel" if not kernels
                  else f"a served XLA form compiled {kernels}")
            print(f"[{tag}]   {name}: max abs err {err:.2e} "
                  f"(tol {KERNEL_CHECKS[name][1]}) Mosaic {kernels}",
                  flush=True)
        except Exception as e:
            bad.append(name)
            print(f"[{tag}]   {name}: FAILED {type(e).__name__}: "
                  f"{str(e)[:2000]}", flush=True)
    check(not bad, f"kernel checks failed: {bad}")


# --------------------------------------------------------------------- train
def phase_train(tag, shape, on_tpu, probe):
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, gpt2_model

    n = jax.device_count()
    cfg = GPT2Config(vocab_size=shape["vocab"], n_positions=shape["seq"],
                     n_embd=shape["d"], n_layer=shape["layers"],
                     n_head=shape["heads"], dropout=0.0, remat=True,
                     remat_policy="dots", scan_layers=True)
    model = gpt2_model(cfg, sample_seq_len=shape["seq"])
    config = {
        "train_batch_size": shape["micro"] * n,
        "train_micro_batch_size_per_gpu": shape["micro"],
        "optimizer": {"type": "AdamW",
                      "params": {"lr": shape["lr"], "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": shape["zero"]},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    leaves = jax.tree_util.tree_leaves(engine.state.params)
    n_params = sum(int(np.prod(l.shape)) for l in leaves)
    print(f"[{tag}] GPT-2 d={shape['d']} L={shape['layers']} "
          f"h={shape['heads']} vocab={shape['vocab']} params={n_params:,} "
          f"seq={shape['seq']} micro={shape['micro']} x {n} device(s) bf16 "
          f"ZeRO-{shape['zero']} mesh={engine.mesh_spec.axis_sizes}", flush=True)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, shape["vocab"], size=(shape["micro"] * n, shape["seq"]),
        dtype=np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(shape["steps"])]
    print(f"[{tag}]   loss by step: {[round(l, 4) for l in losses]}",
          flush=True)
    if n > 1:
        placement(tag, engine.state.params, n, split=shape["zero"] == 3)
    check(all(math.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a fixed batch: {losses}")
    found = report_sites(tag, probe.new_modules(), {"train_step"})
    if on_tpu:
        ks = kernels_of(found, "train_step")
        for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            check(k in ks, f"train_step traced no Mosaic {k}: {sorted(ks)}")


# --------------------------------------------------------------------- serve
def make_prompts(vocab, p, seed):
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(1, vocab, size=n).astype(np.int32)

    prefix = toks(p["prefix"])
    return dict(short=toks(p["short"]), long=toks(p["long"]),
                shared_a=np.concatenate([prefix, toks(p["tail_a"])]),
                shared_b=np.concatenate([prefix, toks(p["tail_b"])]))


def phase_serve(tag, shape, prompts_cfg, on_tpu, probe):
    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.serving.executor import prompt_buckets
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, RequestState, ServingConfig)
    from deepspeed_tpu.models import causal_lm

    mk = getattr(causal_lm, shape["family"] + "_cfg")
    cfg = mk(vocab_size=shape["vocab"], max_seq_len=shape["cap"],
             n_embd=shape["d"], n_layer=shape["layers"], n_head=shape["heads"])
    slots, cap, tp = shape["slots"], shape["cap"], shape["tp"]
    kv_tok = 2 * cfg.n_layer * cfg.kv_heads * cfg.head_dim * 2    # bf16 K+V
    pages = shape.get("total_pages") or slots * math.ceil(cap / PAGE) + 1
    print(f"[{tag}] {cfg.name} d={cfg.n_embd} L={cfg.n_layer} h={cfg.n_head} "
          f"d_head={cfg.head_dim} vocab={cfg.vocab_size} "
          f"params={cfg.num_params():,} bf16 tp={tp}", flush=True)
    print(f"[{tag}]   memory: weights {cfg.num_params() * 2 / 1e9:.2f} GB + "
          f"pool {pages} pages x {PAGE} tok x {kv_tok} B = "
          f"{pages * PAGE * kv_tok / 1e9:.2f} GB (slots {slots} x cap {cap}) + "
          f"the chunk's dense view {slots * cap * kv_tok / 1e9:.2f} GB; "
          f"/ {tp} device(s)", flush=True)

    config = {"dtype": "bfloat16", "max_out_tokens": cap}
    if tp > 1:
        config["tensor_parallel"] = {"tp_size": tp}
    engine = ds.init_inference(model=cfg, config=config)
    if tp > 1:
        placement(tag, engine.params, tp, split=True)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=slots, chunk_size=shape["chunk"], max_seq_len=cap,
        kv_total_pages=shape.get("total_pages"),
        prefix_cache=PrefixCacheConfig(insert_on="prefill")))

    pc = prompts_cfg
    pr = make_prompts(cfg.vocab_size, pc, seed=1)
    # two requests first; the others arrive while these decode
    handles = {
        "short": sched.submit(pr["short"], max_new_tokens=pc["new_short"]),
        "long": sched.submit(pr["long"], max_new_tokens=pc["new_long"]),
    }
    sched.step()
    sched.step()
    handles["shared_a"] = sched.submit(pr["shared_a"],
                                       max_new_tokens=pc["new_shared"])
    while handles["shared_a"].state == RequestState.QUEUED:
        sched.step()
    decoding = [k for k, h in handles.items()
                if h.state == RequestState.RUNNING]
    handles["shared_b"] = sched.submit(pr["shared_b"],
                                       max_new_tokens=pc["new_shared"])
    snap = sched.run()
    want = dict(short=pc["new_short"], long=pc["new_long"],
                shared_a=pc["new_shared"], shared_b=pc["new_shared"])
    buckets = prompt_buckets(cap - 1)
    for k, h in handles.items():
        bucket = next(b for b in buckets if h.prompt.size <= b)
        print(f"[{tag}]   request {k}: prompt {h.prompt.size} (bucket "
              f"{bucket}) -> {h.state.value}/{h.finish_reason} "
              f"{len(h.tokens)}/{want[k]} tokens, prefix hit "
              f"{h.prefix_hit_tokens}", flush=True)
    for k, h in handles.items():
        check(h.state == RequestState.FINISHED,
              f"request {k} ended {h.state.value} ({h.finish_reason})")
        check(len(h.tokens) == want[k],
              f"request {k}: {len(h.tokens)} tokens, asked {want[k]}")
        check(all(0 <= t < cfg.vocab_size for t in h.tokens),
              f"request {k}: token out of vocabulary")
    check(decoding, "shared_b was not submitted while others decoded")
    check(handles["shared_b"].prefix_hit_tokens >= pc["prefix"] - PAGE,
          "the second shared-prefix request missed the prefix cache")
    print(f"[{tag}]   scheduler: tokens {snap.get('tokens_total')}, "
          f"prefix hits {sched.telemetry.prefix_hits} "
          f"misses {sched.telemetry.prefix_misses}", flush=True)
    served = list(handles["shared_a"].tokens)
    del sched, handles
    gc.collect()

    # greedy parity: the same prompt through the single-call generate path
    ref = engine.generate(pr["shared_a"][None, :],
                          max_new_tokens=pc["new_shared"])
    ref = [int(t) for t in ref[0, pr["shared_a"].size:]]
    same = ref == served
    print(f"[{tag}]   greedy parity vs engine.generate on shared_a: "
          f"{'identical' if same else 'DIFFERENT'} ({pc['new_shared']} tokens)",
          flush=True)
    check(same, f"scheduler {served} != generate {ref}")

    sites = {"prefill", "suffix_prefill", "decode_chunk", "decode_loop"}
    found = report_sites(tag, probe.new_modules(), sites)
    if on_tpu:
        long_ids = f"1x{next(b for b in buckets if pc['long'] <= b)}"
        short_ids = f"1x{next(b for b in buckets if pc['short'] <= b)}"
        check("flash_fwd" in kernels_of(found, "prefill", long_ids),
              f"prefill {long_ids} did not trace the flash kernel")
        check(not kernels_of(found, "prefill", short_ids),
              f"prefill {short_ids} traced a Mosaic kernel; sub-256 buckets "
              "are routed to XLA attention")
        chunk = kernels_of(found, "decode_chunk")
        # every chunk steps on the dense view: ALiBi's decode is XLA's (the
        # kernel has no bias), any other model's the Mosaic decode kernel
        want = set() if cfg.pos_emb == "alibi" else {"decode_attention"}
        check(set(chunk) == want
              and all(k.in_loop and k.count == cfg.n_layer
                      for k in chunk.values()),
              f"decode chunk should hold {sorted(want) or 'no Mosaic kernel'} "
              f"inside its loop, once per layer: {chunk}")
    hbm(tag)


# ---------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny widths on the CPU backend: rehearses the "
                         "control flow only, output labelled REHEARSAL")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"devices={device['count']}", flush=True)
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print("chip_smoke: no TPU visible to JAX — nothing was run "
              "(--rehearse-cpu is the explicit CPU rehearsal)",
              file=sys.stderr)
        return 2
    if on_tpu and args.rehearse_cpu:
        print("chip_smoke: --rehearse-cpu on a TPU host; run without it",
              file=sys.stderr)
        return 2
    if not on_tpu:
        print("REHEARSAL: tiny widths on the CPU backend, Pallas in interpret "
              "mode; no line below is a device number", flush=True)

    from deepspeed_tpu.utils.device import device_peaks, enable_compile_cache
    cache_dir = enable_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({n_cached} entries at start)",
          flush=True)
    if on_tpu:
        print(f"published peaks: {device_peaks()}", flush=True)

    dump_dir = tempfile.mkdtemp(prefix="chip_smoke_ir_")
    jax.config.update("jax_dump_ir_to", dump_dir)
    probe = Probe(dump_dir)
    shapes = dict(train=TRAIN_125M, prompts=PROMPTS, bloom=BLOOM_7B,
                  neox=NEOX_4L) if on_tpu else REHEARSAL
    phases = []
    if on_tpu:
        phases.append(("kernel-gate",
                       lambda tag: phase_kernel_gate(tag, probe)))
    phases += [
        ("train-125m", lambda tag: phase_train(
            tag, shapes["train"], on_tpu, probe)),
        ("serve-bloom-7b", lambda tag: phase_serve(
            tag, shapes["bloom"], shapes["prompts"], on_tpu, probe)),
        ("serve-neox-4l", lambda tag: phase_serve(
            tag, shapes["neox"], shapes["prompts"], on_tpu, probe)),
    ]
    if on_tpu and device["count"] >= 4:
        phases += [
            ("train-1.3b-zero3-fsdp4", lambda tag: phase_train(
                tag, TRAIN_1P3B, on_tpu, probe)),
            ("serve-bloom-7b-tp4", lambda tag: phase_serve(
                tag, BLOOM_7B_TP4, PROMPTS, on_tpu, probe)),
        ]

    summary, failed = {}, []
    t_all = time.perf_counter()
    try:
        for tag, fn in phases:
            mark = probe.mark()
            try:
                fn(tag)
                ok = True
            except Exception:
                ok = False
                failed.append(tag)
                traceback.print_exc()
            probe.new_modules()          # a failed phase's dumps are its own
            gc.collect()
            summary[tag] = dict(ok=ok, **probe.since(mark))
            s = summary[tag]
            print(f"[{tag}] {'ok' if ok else 'FAILED'}: compile "
                  f"{s['compile_s']} s, run {s['run_s']} s, cache hits "
                  f"{s['cache_hits']} misses {s['cache_misses']}", flush=True)
    finally:
        shutil.rmtree(dump_dir, ignore_errors=True)
    total = dict(ok=not failed, **probe.since((t_all, 0, 0)))
    print(f"total: compile {total['compile_s']} s, run {total['run_s']} s, "
          f"cache hits {total['cache_hits']} misses {total['cache_misses']}",
          flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print("phases: " + json.dumps(summary), flush=True)
    result = {"ok": True, "device": device}
    if not on_tpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
