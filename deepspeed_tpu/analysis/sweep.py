"""The ``bin/ds-tpu-lint`` whole-repo sweep: canonical traces + AST rules.

Runs every contract pass against the repo's *real* programs — not toys:

- **serving lane** — a tiny ``InferenceEngine`` (fp32 + int8-quantized) under
  a real :class:`ChunkedDecodeExecutor`: donation audit on the chunk /
  suffix-prefill / KV-pool movers, retrace lint across a repeated
  mixed-length workload (the documented one-compile-per-key property: page
  growth rides the page table), the dequant-hoist loop-invariance pin on
  BOTH decode bodies (while-loop generate and scan-lowered chunk), and the
  trace-time host-sync guard;
- **spec lane** — the speculative-decoding verify step under a speculating
  scheduler: one-compile-per-(slots, pages, page, cap, k, sampling) key
  across a grown-k workload (draft length is runtime data), donation audit
  on the verify fn's donated pool caches, dequant-hoist pin on the verify
  body's paged-writeback loop;
- **kvecon lane** — the tiered prefix cache's spill/promote movers under a
  real scheduler forced through device-evict→spill→promote traffic: a second
  identical workload must mint zero new mover compile keys (promote width is
  page-bounded, never per-request), the promote restore must actually donate
  the pool, and the spill gather must not donate it;
- **train lane** — a quantized-DP ``DeepSpeedEngine`` on the virtual CPU
  mesh: donation audit on the real ``train_step`` (state + EF residual),
  retrace lint across repeated steps;
- **overlap lane** — the ppermute-ring and monolithic collective matmuls:
  jaxpr-accounted bytes-on-wire cross-checked against ``CollectiveSpans``
  (including a deliberately twice-calling trace that pins per-site
  accumulation — the PR 3 overwrite class);
- **qring lane** — the fused quantized collective-matmul ring: intN payload
  bytes cross-checked three ways (span == closed form == jaxpr ppermute sum),
  the dequant-hoist structural pin (per-group scales dequant stays OUT of the
  ring step body), EF-residual donation, and a retrace pin on a forced-fused
  int8 tp=4 overlap engine;
- **AST lane** — bare-assert ban, emission-tag schema, hot-path host-sync
  rule over every library file (or only changed files in ``--changed-only``
  mode).

Everything runs offline on CPU (``JAX_PLATFORMS=cpu``, virtual 8-device
mesh); the report serializes to the JSON schema in :mod:`.report`.
"""

import os
import subprocess
from typing import List, Optional, Sequence

from .report import Finding, PassResult, Report, SEVERITY_ERROR

_TINY = dict(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2, n_head=4)
_CAP = 32


def _infra_result(name: str, target: str, exc: Exception) -> PassResult:
    r = PassResult(name, target, checked=0)
    r.findings.append(Finding(
        name, SEVERITY_ERROR, target,
        f"sweep lane crashed: {type(exc).__name__}: {exc}",
        {"exception": type(exc).__name__}))
    return r


# ------------------------------------------------------------- serving lane
def serving_lane(report: Report) -> None:
    """Serving contracts on the executor the cells run: the
    one-compile-per-(slots, pages, page, cap, chunk, sampling)-key property
    across a MIXED-LENGTH workload — page-count growth must ride the page
    table (runtime data), never mint a new compile key; donation on the
    chunk, both prefills and the pool's movers; the dequant-hoist
    loop-invariance pin on BOTH decode bodies (while-loop generate and
    scan-lowered chunk, int8 engine); and the trace-time host-sync guard."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..inference.config import DeepSpeedInferenceConfig
    from ..inference.decode_fns import (build_decode_loop,
                                        build_paged_decode_chunk,
                                        make_select_fn, make_slot_select_fn)
    from ..inference.engine import InferenceEngine
    from ..inference.serving import kv_pool as kvp
    from ..inference.serving.executor import CTL_COLS, ChunkedDecodeExecutor
    from ..models.causal_lm import gpt2_cfg, init_cache
    from ..parallel.mesh import set_global_mesh
    from .donation import donation_findings
    from .host_sync import trace_sync_findings
    from .jaxpr_passes import loop_body_findings
    from .retrace import CompileCacheLint

    cfg = gpt2_cfg(**_TINY, dtype=jnp.float32)
    engine = InferenceEngine(cfg, DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=_CAP))
    raw = jax.tree_util.tree_map(np.asarray, engine.params)
    engine_q = InferenceEngine((cfg, raw), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=_CAP,
        weight_quant={"enabled": True, "bits": 8}))

    ex = ChunkedDecodeExecutor(engine, slots=2, cap=_CAP, chunk_size=3,
                               kv_page_size=8)
    lint = CompileCacheLint(engine._fns, target="serving-engine")
    rng = np.random.default_rng(0)

    def one_request(plen, new):
        prompt = rng.integers(0, _TINY["vocab_size"],
                              size=plen).astype(np.int32)
        slot = ex.pool.acquire(tokens=plen + new)
        tok0, _ = ex.prefill_into_slot(slot, prompt, seed=0)
        S = ex.slots
        active = np.zeros(S, bool)
        active[slot] = True
        lens = np.full((S,), plen, np.int32)
        r = ex.run_chunk(np.full((S,), tok0, np.int32), lens, active,
                         np.full((S,), new, np.int32),
                         np.full((S,), -1, np.int32), np.zeros(S, np.int32),
                         np.zeros(S, np.int32))
        ex.run_chunk(r.toks[:, 0], r.lens, r.active, r.remaining,
                     np.full((S,), -1, np.int32), np.zeros(S, np.int32),
                     r.steps)
        ex.pool.release(slot)

    def workload():
        one_request(8, 5)     # 2 pages
        one_request(20, 8)    # 4 pages: page growth, same chunk key

    workload()                # warmup: every key compiles exactly once
    lint.snapshot()
    workload()                # mixed lengths again: zero new compiles allowed
    report.add(lint.findings())

    # donation: the real chunk fn, the two prefills (a miss's, whose batch-1
    # cache is written over the last one's, and the prefix-cache hit's, which
    # is handed the pool) and the pool's donated movers
    S, mp = ex.slots, ex.pool.max_pages
    chunk_args = (engine.params, jnp.zeros((S, CTL_COLS + mp), jnp.int32),
                  ex.pool.caches, ex._base_key)
    report.add(donation_findings(ex._chunk_fn(), chunk_args,
                                 target="serve_chunk"))
    sargs = (engine.params, ex.pool.caches, jnp.zeros((1, 8), jnp.int32),
             jnp.asarray([4, 4, 0] + [0] * mp, jnp.int32), ex._base_key)
    report.add(donation_findings(ex._suffix_prefill_fn_paged(8), sargs,
                                 target="serve_suffix_prefill"))
    one = init_cache(cfg, 1, _CAP, dtype=engine.dtype)
    report.add(donation_findings(
        ex._prefill_fn(8), (engine.params, one, jnp.zeros((1, 8), jnp.int32),
                            jnp.asarray([4, 0], jnp.int32), ex._base_key),
        target="serve_prefill"))
    report.add(donation_findings(ex.pool._scatter_fn,
                                 (ex.pool.caches, one,
                                  jnp.zeros((mp,), jnp.int32), 0),
                                 target="kv_pool.scatter"))
    report.add(donation_findings(ex.pool._cow_fn, (ex.pool.caches, 1, 2),
                                 target="kv_pool.cow"))
    report.add(donation_findings(kvp._state_zero_jit(ex.pool.keeps), (ex.pool.caches, 0),
                                 target="kv_pool.state_zero_fill"))

    # loop-invariance: dequant hoisted out of BOTH decode bodies (int8 engine)
    int8_invar = lambda a: getattr(a, "dtype", None) == jnp.int8  # noqa: E731

    def loop_pin(fn, args, site):
        findings, n_loops = loop_body_findings(
            fn, args, invar_predicate=int8_invar, what="dequant-hoist",
            site=site)
        res = PassResult("loop_invariance", site, findings, n_loops)
        if n_loops == 0:
            res.findings.append(Finding(
                "loop_invariance", SEVERITY_ERROR, site,
                "no loop found — the dequant-hoist pin target vanished"))
        report.add(res)

    select = make_select_fn(False, 1.0, 0, 1.0)
    caches = init_cache(cfg, 2, _CAP, dtype=engine_q.dtype)
    loop = build_decode_loop(engine_q.module, engine_q._dequant, select, _CAP,
                             overlap=engine_q.comm_overlap)
    largs = (engine_q.params, jnp.zeros((2, 1), jnp.int32), caches,
             jnp.full((2,), 8, jnp.int32), np.int32(8), np.int32(-1),
             jax.random.PRNGKey(0))
    loop_pin(loop, largs, "decode_loop")

    slot_select = make_slot_select_fn(False, 1.0, 0, 1.0)
    chunk = build_paged_decode_chunk(engine_q.module, engine_q._dequant,
                                     slot_select, 3, kv_cap=_CAP,
                                     overlap=engine_q.comm_overlap)
    cargs = (engine_q.params, jnp.zeros((2, 1), jnp.int32), ex.pool.caches,
             jnp.asarray(ex.pool.page_table),
             jnp.full((2,), 8, jnp.int32), jnp.ones((2,), bool),
             jnp.full((2,), 5, jnp.int32), jnp.full((2,), -1, jnp.int32),
             jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
             jax.random.PRNGKey(0))
    loop_pin(chunk, cargs, "decode_chunk")

    # host-sync runtime guard: the traced chunk body performs zero transfers
    report.add(trace_sync_findings(chunk, cargs, target="decode_chunk"))
    set_global_mesh(None)


# ----------------------------------------------------------------- spec lane
def spec_lane(report: Report) -> None:
    """Speculative-decoding contracts: the one-compile-per-(slots, pages,
    page, cap, k, sampling)-key property across a GROWN-k workload (per-slot
    draft length is runtime data — a dry proposer, a cap-edge slot and a
    full-k window all ride the same compiled verify) and the donation audit
    on the verify fn's donated pool caches. The verify round holds no loop
    (its rows go back to the pages as slab writes), so no dequant-hoist pin."""
    import jax.numpy as jnp
    import numpy as np
    from ..inference.config import DeepSpeedInferenceConfig
    from ..inference.engine import InferenceEngine
    from ..inference.serving.scheduler import (ContinuousBatchingScheduler,
                                               ServingConfig)
    from ..parallel.mesh import set_global_mesh
    from ..models.causal_lm import gpt2_cfg
    from .donation import donation_findings
    from .retrace import CompileCacheLint

    cfg = gpt2_cfg(**_TINY, dtype=jnp.float32)
    engine = InferenceEngine(cfg, DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=_CAP))
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=3, max_seq_len=_CAP, kv_page_size=8,
        speculate=True, spec_k=4))
    lint = CompileCacheLint(engine._fns, target="spec-serving-engine")
    rng = np.random.default_rng(0)

    def workload():
        # a repetitive-suffix prompt (n-gram drafts fill the window) and a
        # random prompt (dry proposer, spec_len 0) through the SAME verify:
        # draft-length growth is runtime data, never a compile key
        rep = np.tile(rng.integers(0, _TINY["vocab_size"], size=4), 4) \
            .astype(np.int32)
        rnd = rng.integers(0, _TINY["vocab_size"], size=12).astype(np.int32)
        hs = [sched.submit(rep, max_new_tokens=6),
              sched.submit(rnd, max_new_tokens=6)]
        sched.run()
        if any(h.finish_reason != "length" for h in hs):
            raise RuntimeError("spec_lane workload did not complete")

    workload()                # warmup: every key compiles exactly once
    lint.snapshot()
    workload()                # grown/shrunk drafts: zero new compiles allowed
    report.add(lint.findings())

    ex = sched.executor
    vkey = next(k for k in engine._fns if k[0] == "serve_spec_verify_paged")
    k = vkey[5]
    S, mp = ex.slots, ex.pool.max_pages
    vargs = (engine.params, jnp.zeros((S, k + 1), jnp.int32), ex.pool.caches,
             jnp.zeros((S, mp), jnp.int32), jnp.zeros((S,), jnp.int32),
             jnp.ones((S,), jnp.int32), jnp.zeros((S,), bool))
    report.add(donation_findings(engine._fns[vkey], vargs,
                                 target="serve_spec_verify_paged"))
    set_global_mesh(None)


# --------------------------------------------------------------- kvecon lane
def kvecon_lane(report: Report) -> None:
    """Tiered prefix-cache contracts (PR 19): the spill/promote movers —
    ``gather_pages`` at device-LRU eviction, ``promote_prefix``'s restore at
    host→device promote — are module-level jit singletons keyed only by row
    count, so a second identical spill→promote workload must mint ZERO new
    compile entries (no per-promote keys); the restore side must actually
    donate the pool (no silent copy-fallback), and the gather side must NOT
    donate it (the spilled entry's source pages stay live for readers)."""
    import jax.numpy as jnp
    import numpy as np
    from ..inference.config import DeepSpeedInferenceConfig
    from ..inference.engine import InferenceEngine
    from ..inference.serving import kv_pool as kvp
    from ..inference.serving.prefix_cache import PrefixCacheConfig
    from ..inference.serving.scheduler import (ContinuousBatchingScheduler,
                                               ServingConfig)
    from ..models.causal_lm import gpt2_cfg
    from ..parallel.mesh import set_global_mesh
    from .donation import _flat_args_info, donation_findings

    cfg = gpt2_cfg(**_TINY, dtype=jnp.float32)
    engine = InferenceEngine(cfg, DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=_CAP))
    # HBM budget sized for exactly ONE prompt-length entry: the second insert
    # evicts the first, which spills to the (generous) host rung; re-serving
    # the first prefix then promotes it back — the canonical tier traffic
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=2, max_seq_len=_CAP, kv_page_size=4,
        prefix_cache=PrefixCacheConfig(
            max_bytes=12 * 1024, host_tier_bytes=1 << 20,
            min_hit_tokens=4, min_insert_tokens=4, insert_on="prefill")))
    rng = np.random.default_rng(7)
    pa = rng.integers(0, _TINY["vocab_size"], size=16).astype(np.int32)
    pb = rng.integers(0, _TINY["vocab_size"], size=16).astype(np.int32)

    def serve(prompt):
        h = sched.submit(prompt, max_new_tokens=2)
        sched.run()
        if h.finish_reason != "length":
            raise RuntimeError("kvecon_lane workload did not complete")

    def workload():
        serve(pa)               # insert A (fills the device budget)
        serve(pb)               # insert B -> A evicts -> spills (gather)
        serve(pa)               # A: host hit -> promote (restore)

    workload()
    pc = sched.prefix_cache
    s = pc.stats()
    wired = PassResult("retrace", "tiered-prefix-movers", checked=2)
    if s["spills"] < 1 or s["promotions"] < 1:
        wired.findings.append(Finding(
            "retrace", SEVERITY_ERROR, "tiered-prefix-movers",
            f"spill/promote workload exercised neither mover "
            f"(spills={s['spills']} promotions={s['promotions']}) — the "
            "lane's pin targets vanished"))
    g0 = kvp._paged_gather_jit.cache_info().currsize
    r0 = kvp._paged_restore_jit.cache_info().currsize
    workload()                  # identical traffic: zero new compile keys
    g1 = kvp._paged_gather_jit.cache_info().currsize
    r1 = kvp._paged_restore_jit.cache_info().currsize
    if (g1, r1) != (g0, r0):
        wired.findings.append(Finding(
            "retrace", SEVERITY_ERROR, "tiered-prefix-movers",
            f"a second identical spill/promote workload minted new mover "
            f"compile keys (gather {g0}->{g1}, restore {r0}->{r1}) — "
            "promote width must stay page-bounded, never per-request"))
    report.add(wired)

    # donation: the promote restore donates the pool; the spill gather must
    # not (it reads pages the trie may still share with in-flight slots)
    pool = sched.executor.pool
    slot = pool.acquire(tokens=8)
    n = pool.pages_for(8)
    tbl = jnp.asarray(np.asarray(pool.page_table[slot, :n], np.int32))
    R = n * pool.page_size
    slab = pool.gather_pages(np.asarray(pool.page_table[slot, :n]), R)
    report.add(donation_findings(kvp._paged_restore_jit(R),
                                 (pool.caches, slab, tbl),
                                 target="paged_restore(promote)"))
    gres = PassResult("donation", "paged_gather(spill)", checked=1)
    lowered = kvp._paged_gather_jit(R).lower(pool.caches, tbl)
    donated = [p for p, info in _flat_args_info(lowered) if info.donated]
    if donated:
        gres.findings.append(Finding(
            "donation", SEVERITY_ERROR, "paged_gather(spill)",
            f"spill gather donates {donated[:4]} — the gathered pages stay "
            "referenced by live slots and the trie; donation here would "
            "poison the pool at eviction time"))
    report.add(gres)
    pool.release(slot)
    set_global_mesh(None)


# --------------------------------------------------------------- train lane
def train_lane(report: Report) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..models import GPT2Config, gpt2_model
    from ..parallel.mesh import MeshSpec, set_global_mesh
    from ..runtime.engine import DeepSpeedEngine
    from .donation import donation_findings
    from .retrace import CompileCacheLint

    devices = jax.devices()
    if len(devices) < 8:
        r = PassResult("retrace", "train-engine", checked=0)
        r.findings.append(Finding(
            "retrace", SEVERITY_ERROR, "train-engine",
            f"virtual mesh needs 8 devices, found {len(devices)} — run via "
            "bin/ds-tpu-lint (it sets xla_force_host_platform_device_count)"))
        report.add(r)
        return
    set_global_mesh(None)
    cfg = GPT2Config(vocab_size=128, n_positions=32, n_embd=32, n_layer=2,
                     n_head=4, dropout=0.0, dtype=jnp.float32,
                     scan_layers=True)
    engine = DeepSpeedEngine(
        model=gpt2_model(cfg, sample_seq_len=32),
        config={"train_batch_size": 16, "gradient_accumulation_steps": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 0},
                "comm_overlap": {"enabled": True,
                                 "quantized_allreduce": True},
                "steps_per_print": 10**9},
        mesh_spec=MeshSpec({"data": 8}, devices))
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, size=(16, 32),
                                       dtype=np.int32)}
    lint = CompileCacheLint(engine._fns, target="train-engine")
    engine.train_batch(batch)
    lint.snapshot()
    engine.train_batch(batch)
    report.add(lint.findings())

    gbatch = engine._globalize(engine._reshape_for_gas(batch),
                               leading_gas=True)
    args = (engine.state, gbatch, np.float32(1e-2), np.float32(1.0),
            engine._qar_residual)
    report.add(donation_findings(engine._fns["train_step"], args,
                                 target="train_step_quantized"))
    set_global_mesh(None)


# ------------------------------------------------------------- overlap lane
def overlap_lane(report: Report) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..parallel import overlap as ov
    from ..parallel.mesh import AXIS_TENSOR, MeshSpec
    from ..utils.jax_compat import shard_map
    from .collectives import crosscheck_findings

    devices = jax.devices()
    if len(devices) < 4:
        r = PassResult("collective_schema", "overlap-ring", checked=0)
        r.findings.append(Finding(
            "collective_schema", SEVERITY_ERROR, "overlap-ring",
            f"need 4 devices for the ring lane, found {len(devices)}"))
        report.add(r)
        return
    mesh = MeshSpec({"tensor": 4}, devices[:4])
    ag_specs = dict(mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                    in_specs=(P(AXIS_TENSOR, None), P(None, None)),
                    out_specs=P(None, None), check_vma=False)
    rs_specs = dict(mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                    in_specs=(P(None, AXIS_TENSOR), P(AXIS_TENSOR, None)),
                    out_specs=P(AXIS_TENSOR, None), check_vma=False)
    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((16, 6), jnp.float32)

    lanes = [
        ("ring_allgather_matmul", ag_specs, (x, w),
         lambda a, b: ov.chunked_allgather_matmul(
             a, b, AXIS_TENSOR, site="lint.ring_ag")),
        ("ring_matmul_reduce_scatter", rs_specs, (x, w),
         lambda a, b: ov.chunked_matmul_reduce_scatter(
             a, b, AXIS_TENSOR, site="lint.ring_rs")),
        ("monolithic_allgather_matmul", ag_specs, (x, w),
         lambda a, b: ov.allgather_matmul_monolithic(
             a, b, AXIS_TENSOR, site="lint.mono_ag")),
        ("monolithic_matmul_reduce_scatter", rs_specs, (x, w),
         lambda a, b: ov.matmul_reduce_scatter_monolithic(
             a, b, AXIS_TENSOR, site="lint.mono_rs")),
        # one site traced twice in a single program: pins ACCUMULATION of
        # bytes_total across traces (the PR 3 last-call-overwrite class)
        ("ring_site_accumulation", ag_specs, (x, w),
         lambda a, b: ov.chunked_allgather_matmul(
             a, b, AXIS_TENSOR, site="lint.ring_twice")
         + ov.chunked_allgather_matmul(
             a, b, AXIS_TENSOR, site="lint.ring_twice")),
    ]
    for name, specs, args, body in lanes:
        fn = shard_map(body, **specs)
        report.add(crosscheck_findings(fn, args, site_prefixes=("lint.",),
                                       target=name))


# ------------------------------------------------------------------ qring lane
def qring_lane(report: Report) -> None:
    """Fused-quantized-ring contracts (``parallel/qring.py``):

    - **collective schema** — the intN ring payload at wire widths 8 and 4:
      the recorded span, the closed form
      :func:`collectives.qring_wire_bytes`, and the jaxpr ppermute-operand
      sum must agree to the byte (bytes-on-wire claims are never
      hand-computed);
    - **dequant hoist** — on the XLA (unfused) ring path the per-group-scales
      weight dequant happens once per column direction OUTSIDE the ring
      steps. The ring is python-unrolled (no ``lax`` loop for
      ``loop_body_findings`` to inspect), so the pin is structural: count
      the weight-slab int8→f32 converts in the jaxpr — ``dequantize_grouped``
      converts the 3-D ``(groups, g, n)`` regrouped slab, while the wire
      decompress converts 2-D ``(blocks, block)`` payloads, so the two are
      shape-distinguishable. Hoisted = one per direction; ``W`` per
      direction = the dequant leaked into the step body;
    - **EF-residual donation** — a caller threading the residual across
      dispatches (the cumulative-EF regime) gets in-place buffer reuse, read
      off the executable's ``input_output_alias`` table;
    - **retrace** — a forced-fused int8 tp=4 overlap engine (the deployable
      qring decode config): two identical generates mint zero new compile
      keys on the fused ring movers.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from ..inference.config import DeepSpeedInferenceConfig
    from ..inference.engine import InferenceEngine
    from ..models.causal_lm import gpt2_cfg
    from ..ops.quantizer.quant import quantize_grouped
    from ..parallel import qring
    from ..parallel.mesh import AXIS_TENSOR, MeshSpec, set_global_mesh
    from ..utils.comms_logging import collective_spans
    from ..utils.jax_compat import shard_map
    from .collectives import crosscheck_findings, qring_wire_bytes
    from .donation import donation_findings
    from .jaxpr_passes import subjaxprs
    from .retrace import CompileCacheLint

    devices = jax.devices()
    if len(devices) < 4:
        r = PassResult("collective_schema", "qring", checked=0)
        r.findings.append(Finding(
            "collective_schema", SEVERITY_ERROR, "qring",
            f"need 4 devices for the qring lane, found {len(devices)}"))
        report.add(r)
        return
    W = 4
    mesh = MeshSpec({"tensor": W}, devices[:W])
    m, k, n, blk = 8, 32, 12, 16
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    q, s = quantize_grouped(
        jnp.asarray(rng.standard_normal((k, n)), jnp.float32),
        group_size=8, bits=8)

    def ring(wire_bits, site):
        def body(xl, ql, sl):
            out, _ = qring.fused_quant_matmul_reduce_scatter(
                xl, ql, sl, AXIS_TENSOR, bits=8, wire_bits=wire_bits,
                quant_block=blk, site=site)
            return out
        return shard_map(body, mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                         in_specs=(P(None, AXIS_TENSOR),
                                   P(AXIS_TENSOR, None),
                                   P(AXIS_TENSOR, None)),
                         out_specs=P(AXIS_TENSOR, None), check_vma=False)

    # wire-bytes cross-check: span == closed form == jaxpr, to the byte
    for wb in (8, 4):
        site = f"lint.qring_w{wb}"
        before = collective_spans.summary().get(site, {}).get(
            "bytes_total", 0)
        res = crosscheck_findings(ring(wb, site), (x, q, s),
                                  site_prefixes=("lint.",),
                                  target=f"qring-wire{wb}")
        recorded = collective_spans.summary().get(site, {}).get(
            "bytes_total", 0) - before
        closed = qring_wire_bytes(m, n, W, wire_bits=wb, block=blk,
                                  bidirectional=True)
        if recorded != closed:
            res.findings.append(Finding(
                "collective_schema", SEVERITY_ERROR, f"qring-wire{wb}",
                f"recorded ring span {recorded} B != closed-form "
                f"qring_wire_bytes {closed} B — the wire-bytes model and "
                "the ring's recording drifted apart",
                {"recorded": int(recorded), "closed_form": int(closed)}))
        report.add(res)

    # dequant-hoist pin (structural; see docstring for the shape argument)
    def n_weight_dequants(jx) -> int:
        cnt = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "convert_element_type":
                av = getattr(eqn.invars[0], "aval", None)
                if av is not None and av.dtype == jnp.int8 and av.ndim == 3:
                    cnt += 1
            for sub in subjaxprs(eqn):
                cnt += n_weight_dequants(sub)
        return cnt

    n_deq = n_weight_dequants(jax.make_jaxpr(ring(8, None))(x, q, s).jaxpr)
    res = PassResult("loop_invariance", "qring-dequant-hoist", checked=1)
    if n_deq == 0:
        res.findings.append(Finding(
            "loop_invariance", SEVERITY_ERROR, "qring-dequant-hoist",
            "no weight-slab int8->f32 convert in the ring trace — the "
            "dequant-hoist pin target vanished (fused backend forced under "
            "the lint sweep, or dequantize_grouped restructured?)"))
    elif n_deq > 2:
        res.findings.append(Finding(
            "loop_invariance", SEVERITY_ERROR, "qring-dequant-hoist",
            f"{n_deq} weight-slab dequant converts in the ring trace — "
            "expected one per column direction (2, bidirectional): the "
            "per-group-scales dequant leaked into the ring step body and "
            "re-materialises the fp weight every hop",
            {"converts": int(n_deq)}))
    report.add(res)

    # EF-residual donation: threading callers reuse the buffer in place
    res0 = jnp.zeros((m // W * n * W,), jnp.float32)

    def body_res(xl, ql, sl, rl):
        return qring.fused_quant_matmul_reduce_scatter(
            xl, ql, sl, AXIS_TENSOR, bits=8, wire_bits=8, quant_block=blk,
            residual=rl)

    ring_res = shard_map(body_res, mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                         in_specs=(P(None, AXIS_TENSOR), P(AXIS_TENSOR, None),
                                   P(AXIS_TENSOR, None), P(AXIS_TENSOR)),
                         out_specs=(P(AXIS_TENSOR, None), P(AXIS_TENSOR)),
                         check_vma=False)
    report.add(donation_findings(ring_res, (x, q, s, res0),
                                 donate_argnums=(3,),
                                 target="qring.residual"))

    # forced-fused int8 tp=4 overlap engine: retrace pin on the ring movers
    prev = os.environ.get("DS_TPU_WQ_FORCE_FUSED")
    os.environ["DS_TPU_WQ_FORCE_FUSED"] = "1"
    try:
        cfg = gpt2_cfg(**_TINY, dtype=jnp.float32)
        engine = InferenceEngine(cfg, DeepSpeedInferenceConfig(
            dtype="float32", max_out_tokens=_CAP,
            weight_quant={"enabled": True, "bits": 8, "group": 8},
            tensor_parallel={"tp_size": 4},
            comm_overlap={"enabled": True, "chunk_bits": 8,
                          "quant_block": 16}))
        ids = np.asarray(
            rng.integers(0, _TINY["vocab_size"], size=(8, 8)), np.int32)
        lint = CompileCacheLint(engine._fns, target="qring-engine")
        engine.generate(ids, max_new_tokens=4)
        lint.snapshot()
        engine.generate(ids, max_new_tokens=4)
        report.add(lint.findings())
    finally:
        if prev is None:
            os.environ.pop("DS_TPU_WQ_FORCE_FUSED", None)
        else:
            os.environ["DS_TPU_WQ_FORCE_FUSED"] = prev
        set_global_mesh(None)


# ------------------------------------------------------------------ AST lane
def ast_lane(report: Report, repo_root: str,
             paths: Optional[Sequence[str]] = None) -> None:
    from ..observability.schema import emission_tag_rule
    from .ast_rules import BareAssertRule, run_ast_rules
    from .host_sync import HOT_PATH_SPECS, hot_path_sync_findings
    report.add(run_ast_rules(repo_root,
                             [BareAssertRule(), emission_tag_rule()],
                             paths=paths))
    if paths is None:
        report.add(hot_path_sync_findings(repo_root))
    else:
        specs = [s for s in HOT_PATH_SPECS if s.path in set(paths)]
        if specs:
            report.add(hot_path_sync_findings(repo_root, specs))


# -------------------------------------------------------------------- driver
def changed_files(repo_root: str, base: str = "HEAD") -> List[str]:
    """Repo-relative changed ``deepspeed_tpu/*.py`` paths vs ``base`` —
    including UNTRACKED files (a brand-new module is exactly what a
    pre-commit lint run must check); empty when git is unavailable.
    NUL-separated so paths with whitespace survive."""
    cmds = (
        ["git", "diff", "--name-only", "-z", base, "--", "deepspeed_tpu"],
        ["git", "ls-files", "--others", "--exclude-standard", "-z", "--",
         "deepspeed_tpu"],
    )
    paths: List[str] = []
    for cmd in cmds:
        try:
            out = subprocess.run(cmd, cwd=repo_root, capture_output=True,
                                 text=True, timeout=30)
        except (OSError, subprocess.SubprocessError):
            return []
        if out.returncode != 0:
            continue
        paths.extend(p for p in out.stdout.split("\0")
                     if p.endswith(".py") and p not in paths)
    return paths


def run_sweep(repo_root: str, *, ast_only: bool = False,
              paths: Optional[Sequence[str]] = None) -> Report:
    report = Report()
    ast_lane(report, repo_root, paths=paths)
    if not ast_only:
        for lane in (serving_lane, spec_lane, kvecon_lane,
                     train_lane, overlap_lane, qring_lane):
            try:
                lane(report)
            except Exception as e:  # a crashed lane is a failed sweep
                report.add(_infra_result(lane.__name__, "sweep", e))
    return report


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI body for ``bin/ds-tpu-lint`` (env already prepared there)."""
    import argparse
    parser = argparse.ArgumentParser(
        prog="ds-tpu-lint",
        description="Program-contract analyzer: donation / retrace / "
                    "host-sync / loop-invariance / collective-schema passes "
                    "over the repo's canonical traces, plus AST rules.")
    parser.add_argument("--json", metavar="PATH",
                        help="write the JSON report to PATH ('-' = stdout)")
    parser.add_argument("--ast-only", action="store_true",
                        help="skip the traced lanes (fast source-only mode)")
    parser.add_argument("--changed-only", nargs="?", const="HEAD",
                        metavar="BASE",
                        help="AST rules on files changed vs BASE "
                             "(default HEAD); implies --ast-only")
    parser.add_argument("--repo-root", default=None)
    args = parser.parse_args(argv)

    repo_root = args.repo_root or os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    paths = None
    ast_only = args.ast_only
    if args.changed_only is not None:
        paths = changed_files(repo_root, args.changed_only)
        ast_only = True
        if not paths:
            print("ds-tpu-lint: no changed deepspeed_tpu/*.py files vs "
                  f"{args.changed_only}")
    import sys
    if args.json == "-":
        # stdout must carry ONLY the report so `--json -` pipes cleanly:
        # the traced lanes' engine logs default to stdout — move them
        from ..utils.logging import logger as ds_logger
        for handler in ds_logger.handlers:
            if getattr(handler, "stream", None) is sys.stdout:
                handler.stream = sys.stderr
    report = run_sweep(repo_root, ast_only=ast_only, paths=paths)
    if args.json == "-":
        print(report.to_json())
        print(report.summary(), file=sys.stderr)
    else:
        if args.json:
            with open(args.json, "w") as f:
                f.write(report.to_json())
            print(f"ds-tpu-lint: report written to {args.json}")
        print(report.summary())
    return 0 if report.ok else 1
