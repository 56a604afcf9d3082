"""Traffic kind ``train``: a training job's inner loop, as a real job runs it.

The next batches are built on the host (numpy, never a ``jax.Array``:
``engine.train_batch`` runs ``np.asarray`` on every leaf and places it itself)
by one producer thread while earlier steps run; steps go to the device in
blocks of K ``train_batch`` calls; block i+1 is dispatched before the harness
waits for the last loss of block i, and nothing is fetched from the device
inside a block. ``train_tokens_per_s_per_chip`` is ALL the window's tokens
over ALL its time: the window opens when the block before it becomes ready and
closes when its last block does, so a stall anywhere inside it counts. One
reading per block,

    K * tokens per step / (time block i became ready - time block i-1 did) / chips

is printed with the quartiles, and their median is a per-layer metric beside
the rate (it says what the pipeline does when nothing stalls). The first step
of the set-up is compared with the plain reference the configuration names:
its loss and the norm of its gradient. The program's own tracer stays off (enabled, ``train_batch`` blocks on every
loss); a traced run arms ``jax.profiler`` only, for ``traced_blocks`` blocks.
"""

import collections
import math
import queue
import statistics
import threading
import time

import numpy as np

from benchmarks.chipbench import registry, stats
from benchmarks.chipbench.harness import Result, say, seed31
from benchmarks.chipbench.probe import kernel_names


class BatchStream:
    """A seeded synthetic token stream with structure a model can learn: each
    sequence repeats a motif of ``motif_tokens`` tokens drawn from the first
    ``motif_vocab`` entries of the vocabulary, so the loss has to fall. Every
    seed gives batches of the same shape; the seed draws the tokens."""

    def __init__(self, seed: int, batch: int, seq: int, motif_tokens: int,
                 motif_vocab: int):
        self.rng = np.random.default_rng(seed)
        self.batch, self.seq = batch, seq
        self.motif_tokens, self.motif_vocab = motif_tokens, motif_vocab

    def next(self) -> dict:
        motif = self.rng.integers(0, self.motif_vocab,
                                  size=(self.batch, self.motif_tokens),
                                  dtype=np.int32)
        reps = math.ceil(self.seq / self.motif_tokens)
        ids = np.tile(motif, (1, reps))[:, :self.seq]
        return {"input_ids": np.ascontiguousarray(ids)}


class Prefetcher:
    """One thread that keeps ``depth`` blocks of host batches ready."""

    def __init__(self, stream: BatchStream, steps_per_block: int, depth: int):
        self._q = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._stream, self._k = stream, steps_per_block
        self._thread = threading.Thread(target=self._fill, daemon=True,
                                        name="chipbench-prefetch")
        self._thread.start()

    def _fill(self):
        while not self._stop.is_set():
            block = [self._stream.next() for _ in range(self._k)]
            while not self._stop.is_set():
                try:
                    self._q.put(block, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def get(self) -> list:
        return self._q.get()

    def close(self):
        self._stop.set()
        self._thread.join()


def build_engine(ctx):
    """``ds.initialize``'s own steps, with the model's weights drawn from the
    run's seed (``ds.initialize`` passes none on)."""
    from deepspeed_tpu.parallel.mesh import MeshSpec
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.utils.device import enable_compile_cache
    m, t = ctx.config["model"], ctx.config["train"]
    seq = int(ctx.traffic["sequence_length"])
    # the configuration names the program's builders: ``model`` and
    # ``train.model_options`` are the keywords of the model's config class
    cfg = registry.resolve(ctx.config["model_builder"])(
        **{**m, "n_positions": max(seq, m["n_positions"])}, **t["model_options"])
    model = registry.resolve(ctx.config["model_factory"])(cfg, sample_seq_len=seq)
    micro = int(t["micro_batch_per_chip"])
    config = {
        "train_batch_size": micro * ctx.chips,
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": t["optimizer"],
        "bf16": {"enabled": t["dtype"] == "bf16"},
        "zero_optimization": {"stage": int(t["zero_stage"])},
        "gradient_clipping": t["gradient_clipping"],
        "steps_per_print": 10 ** 9,
    }
    enable_compile_cache()
    # the mesh the engine would build itself from the machine's devices, over
    # exactly the cell's chips: ZeRO shards over fsdp, plain data parallel else
    axis = "fsdp" if int(t["zero_stage"]) > 0 else "data"
    mesh = MeshSpec({axis: ctx.chips}, devices=ctx.devices)
    return DeepSpeedEngine(model=model, config=config, mesh_spec=mesh,
                           seed=seed31(ctx.seed))


def check_numpy_only(batch: dict) -> None:
    for k, v in batch.items():
        if not isinstance(v, np.ndarray):
            raise TypeError(f"batch leaf {k!r} is {type(v).__name__}: the "
                            "driver hands train_batch numpy only")


def run(ctx) -> Result:
    import jax
    tr, cfg = ctx.traffic, ctx.config
    k = int(tr["steps_per_block"])
    seq = int(tr["sequence_length"])
    micro = int(cfg["train"]["micro_batch_per_chip"])
    tokens_per_step = micro * ctx.chips * seq
    in_flight = int(tr["blocks_in_flight"])

    engine = build_engine(ctx)
    stream = BatchStream(seed31(ctx.seed, 1), micro * ctx.chips, seq,
                         tr["stream"]["motif_tokens"], tr["stream"]["motif_vocab"])
    feed = Prefetcher(stream, k, depth=in_flight + 1)
    losses, pending = [], collections.deque()
    order = []                  # ("dispatch" | "wait", block index), for the tests
    step0 = {}                  # the very first step, for the reference
    has_reference = ctx.reference()[0] is not None

    def dispatch(i):
        with ctx.span("chipbench.make_batch"):
            block = feed.get()
        with ctx.span("chipbench.step"):
            for batch in block:
                check_numpy_only(batch)
                snapshot = has_reference and not step0
                if snapshot:        # the weights this step starts from, on the host
                    step0.update(params=jax.device_get(engine.state.params),
                                 ids=batch["input_ids"])
                losses.append(engine.train_batch(batch))
                if snapshot:
                    step0.update(loss=float(losses[-1]),
                                 grad_norm=engine.get_global_grad_norm())
        pending.append((i, losses[-1]))
        order.append(("dispatch", i))

    def wait():
        i, last = pending.popleft()
        with ctx.span("chipbench.block_wait"):
            jax.block_until_ready(last)
        order.append(("wait", i))
        return time.monotonic()

    try:
        # set-up: the first block compiles the step (or loads it); the block
        # before the window keeps the pipeline full when the window opens
        for i in range(int(tr["warmup_blocks"])):
            dispatch(-2 - i)
            wait()
        dispatch(-1)
        for j in range(in_flight - 1):
            dispatch(j)
        nxt = in_flight - 1
        ready = [wait()]                    # block -1 ready: the window opens
        t_open = ready[0]
        trace_from = 2 if ctx.trace else None
        last_block = None
        while pending or last_block is None:
            i = len(ready) - 1              # the block about to be waited for
            if last_block is None:
                per_block = (ready[-1] - t_open) / i if i else None
                ahead = nxt - i + 1         # blocks not ready yet, with nxt
                if per_block is None or \
                        ready[-1] - t_open + ahead * per_block \
                        <= ctx.seconds + per_block / 2:
                    dispatch(nxt)
                    nxt += 1
                else:
                    last_block = nxt - 1
            if ctx.trace and i == trace_from:
                ctx.start_trace()
            ready.append(wait())
            if ctx.tracing and i + 1 >= trace_from + int(tr["traced_blocks"]):
                ctx.stop_trace()
        ctx.stop_trace()
        t_close = ready[-1]
        ctx.note_memory()
    finally:
        feed.close()

    readings = stats.block_readings(ready, k * tokens_per_step, ctx.chips)
    steps = k * len(readings)
    loss_values = [float(x) for x in np.asarray(jax.device_get(losses))]
    median = statistics.median(readings)
    rate = steps * tokens_per_step / (t_close - t_open) / ctx.chips
    q = stats.quartiles(readings)
    if not ctx.rehearse:
        say(f"block readings (tokens/s/chip, {k} steps each): "
            + " ".join(f"{r:.1f}" for r in readings))
        say(f"{len(readings)} blocks; quartiles {q[0]:.1f} {q[1]:.1f} {q[2]:.1f}; "
            f"median {median:.1f}; all tokens over the whole window "
            f"{rate:.1f} tokens/s/chip over {t_close - t_open:.3f} s")
    else:
        say(f"{len(readings)} blocks of {k} steps (rehearsal: no rate is printed)")
    n_warm = len(loss_values) - steps
    first, last = loss_values[:10], loss_values[-10:]
    say(f"steps: {n_warm} before the window, {steps} inside; loss first 10 mean "
        f"{statistics.fmean(first):.4f}, last 10 mean {statistics.fmean(last):.4f}")

    reasons = []
    if not all(math.isfinite(x) for x in loss_values):
        reasons.append("a loss is not finite")
    drop_min = 0.0 if ctx.rehearse else float(cfg.get("loss_drop_min", 0.0))
    if not statistics.fmean(last) < statistics.fmean(first) - drop_min:
        reasons.append(f"loss did not fall by {drop_min}: first 10 mean "
                       f"{statistics.fmean(first):.4f}, last 10 mean "
                       f"{statistics.fmean(last):.4f}")
    # a traced run loses blocks to the profiler's stop and reports no rate
    need = 3 if ctx.rehearse or ctx.trace else min(int(tr["min_blocks"]),
                                                   int(ctx.seconds // 3))
    if len(readings) < need:
        reasons.append(f"only {len(readings)} block readings in the window")
    reasons += check_pipeline(order)
    reasons += check_reference(ctx, step0)
    found = {}
    for name, text in ctx.probe.new_modules():
        if name == "train_step":
            found = kernel_names(text)
    say(f"train_step holds Mosaic kernels: {found or 'none'}")
    if ctx.on_tpu:
        for kern in cfg["routes"]["train_step"]:
            if kern not in found:
                reasons.append(f"train_step traced no Mosaic {kern}")
    if ctx.chips > 1:
        # at the rehearsal's widths every leaf is under ZeRO-3's persistence
        # threshold and stays whole, so only placement is checked there
        reasons += check_sharded(engine, ctx.chips,
                                 0 if ctx.rehearse else int(cfg["train"]["zero_stage"]))

    counters = {"tokens_per_step": tokens_per_step, "steps": steps,
                "blocks": len(readings), "steps_per_block": k,
                "micro_batch_per_chip": micro, "sequence_length": seq,
                "block_median_tokens_per_s_per_chip": median,
                "dispatch_order": order}
    return Result(window=(t_open, t_close), attempted=steps,
                  failed=sum(1 for x in loss_values[n_warm:] if not math.isfinite(x)),
                  end_to_end={"train_tokens_per_s_per_chip": rate},
                  counters=counters, reasons=reasons)


def check_pipeline(order) -> list:
    """Block i+1 must have been dispatched before the wait for block i."""
    pos = {ev: n for n, ev in enumerate(order)}
    bad = [i for (what, i) in order if what == "wait"
           and ("dispatch", i + 1) in pos
           and pos[("dispatch", i + 1)] > pos[("wait", i)] and i >= -1]
    return [f"blocks {bad} were waited for before the next was dispatched"] if bad else []


def check_reference(ctx, first: dict) -> list:
    """Outside the window: the loss of the run's very first step and the
    global norm of its gradient, as the engine reported them, against the
    plain float32 reference on the same batch and the same initial weights."""
    ref, spec = ctx.reference()
    if ref is None:
        say("reference: the configuration names none; the step is NOT compared "
            "with a reference")
        return []
    loss, norm = ref.loss_and_grad_norm(
        first["params"], ctx.config["model"], first["ids"],
        rows=int(spec.get("rows_per_slice", 2)), ln_eps=float(spec["ln_eps"]))
    out = []
    for what, got, want, tol in (
            ("loss", first["loss"], loss, float(spec["loss_rel_tol"])),
            ("gradient norm", first["grad_norm"], norm, float(spec["grad_norm_rel_tol"]))):
        err = abs(got - want) / abs(want)
        say(f"reference {ctx.config['reference']['module']} (float32), first step: "
            f"{what} {got:.6f} against {want:.6f}, relative error {err:.2e} "
            f"(tolerance {tol})")
        if not err <= tol:
            out.append(f"first step's {what} {got} is {err:.3e} off the "
                       f"reference's {want}")
    return out


def check_sharded(engine, n: int, zero_stage: int) -> list:
    """Across chips every device holds its shard and not the whole."""
    import jax
    big = max(jax.tree_util.tree_leaves(engine.state.params), key=lambda l: l.size)
    shard = big.addressable_shards[0].data.shape
    say(f"largest parameter {big.shape} lives as {shard} shards on "
        f"{len(big.sharding.device_set)} devices")
    out = []
    if len(big.sharding.device_set) != n:
        out.append(f"largest parameter is on {len(big.sharding.device_set)} devices, not {n}")
    if zero_stage == 3 and int(np.prod(shard)) * n != big.size:
        out.append(f"largest parameter is not split {n} ways: {big.shape} -> {shard}")
    return out
