"""The ONE declared metric-tag schema.

Every ``write_events`` / registry emission in the tree publishes tags declared
here — ``serving/*`` (scheduler telemetry), ``router/*`` (multi-replica
router), ``Train/*`` (training engine + collective spans), ``inference/*``
(single-call generate + weight-quant audit). The registry consults this table
for each tag's instrument kind (counter / gauge / histogram) and the tag-lint
test (``tests/unit/observability``) walks every emission site in the source
tree and asserts each literal tag resolves to exactly one declaration —
the guard against the pre-PR-10 drift where ``serving/``, ``router/`` and
``Train/Comm/`` each invented tag names privately.

Templated tags use ``{i}`` for a per-replica integer segment
(``router/replica{i}/health`` matches ``router/replica3/health``); emission
sites that build them with f-strings lint as ``*`` wildcards against the same
pattern.
"""

import re
from typing import Dict, Iterator, List, Optional, Tuple

COUNTER = "counter"      # cumulative total; emissions carry the running value
GAUGE = "gauge"          # last-write-wins sampled value
HISTOGRAM = "histogram"  # per-event observation into fixed log buckets

#: tag pattern -> (kind, help text). THE schema: one entry per published tag.
TAGS: Dict[str, Tuple[str, str]] = {
    # ------------------------------------------------- serving (per scheduler)
    "serving/ttft_ms": (HISTOGRAM, "queue wait + prefill per finished request"),
    "serving/tpot_ms": (HISTOGRAM, "seconds-per-token (ms) per finished request"),
    "serving/tokens_per_sec": (GAUGE, "decode throughput per chunk"),
    "serving/queue_depth": (GAUGE, "admission queue depth per scheduler tick"),
    "serving/slot_occupancy": (GAUGE, "fraction of KV slots in use per tick"),
    "serving/completed_total": (COUNTER, "requests finished"),
    "serving/rejected_total": (COUNTER, "requests rejected (backpressure)"),
    "serving/prefix_hit_rate": (GAUGE, "admission-level prefix-cache hit rate"),
    "serving/prefix_cached_bytes": (GAUGE, "resident prefix-slab bytes"),
    "serving/prefix_evicted_total": (COUNTER, "prefix-cache LRU evictions"),
    # ---------------------------------------- tiered prefix cache (PR 19)
    "serving/prefix_spilled_bytes": (GAUGE, "host-RAM rung residency: bytes "
                                            "of spilled prefix slabs"),
    "serving/prefix_spills_total": (COUNTER, "device->host spills at LRU "
                                             "eviction"),
    "serving/prefix_promotions_total": (COUNTER, "host->device promotes at "
                                                 "lookup (slab copy instead "
                                                 "of re-prefill)"),
    # ------------------------------------------------- paged KV pool (PR 13)
    "serving/pages_in_use": (GAUGE, "allocated KV pages per scheduler tick"),
    "serving/page_fragmentation": (GAUGE, "allocation-granularity waste: "
                                          "fraction of allocated page rows "
                                          "beyond slot reservations"),
    "serving/prefix_shared_pages": (GAUGE, "pages referenced more than once "
                                           "(zero-copy prefix sharing)"),
    "serving/cow_copies_total": (COUNTER, "copy-on-write boundary-page "
                                          "copies at prefix bind"),
    # ---------------------------------------------- speculative decoding (PR 18)
    "serving/spec_acceptance_rate": (GAUGE, "cumulative draft-token "
                                            "acceptance rate per verify round"),
    "serving/spec_proposed_total": (COUNTER, "draft tokens offered to the "
                                             "verifier"),
    "serving/spec_accepted_total": (COUNTER, "draft tokens accepted by the "
                                             "verify pass"),
    "serving/spec_draft_ms": (GAUGE, "proposer wall time of the last round"),
    # ------------------------------- decode waste and stalled deliveries (PR 25)
    "serving/decode_slot_steps_total": (COUNTER, "decode steps run times the "
                                                 "slots active at dispatch"),
    "serving/decode_tokens_kept_total": (COUNTER, "tokens of those steps "
                                                  "handed to a stream"),
    "serving/deliveries_total": (COUNTER, "deliveries of a chunk's tokens to "
                                          "a stream"),
    "serving/deliveries_stalled_total": (COUNTER, "deliveries held up by a "
                                                  "prefill of another request "
                                                  "since the stream's last one"),
    # ------------------------------ expert and state-space layers (PR 27)
    "serving/moe_assignments_total": (COUNTER, "(token, expert) assignments "
                                               "that fell on experts this "
                                               "program holds, over prefills "
                                               "and decode chunks"),
    "serving/moe_plan_rows_total": (COUNTER, "rows the expert layers' dispatch "
                                             "plans laid out (a program's "
                                             "static worst case x its expert "
                                             "layers and forwards, counted on "
                                             "the host): moe_assignments_total "
                                             "over this is the live share"),
    "serving/moe_experts_touched_total": (COUNTER, "distinct held experts "
                                                   "read, summed over expert "
                                                   "layers and steps: the "
                                                   "bytes the grouped expert "
                                                   "kernel had to read"),
    "serving/ssm_state_bytes": (GAUGE, "bytes of the per-slot state of the "
                                       "state-space, short-convolution and "
                                       "windowed-attention layers (recurrent "
                                       "state, windows, rings)"),
    "serving/kv_ring_bytes": (GAUGE, "kv.ring_bytes: the part of the per-slot "
                                     "state that is windowed layers' rings "
                                     "of keys and values; absent from a "
                                     "model without such layers"),
    "serving/kv_latent_row_bytes": (GAUGE, "kv.latent_row_bytes: bytes a token "
                                           "a layer as a latent-attention "
                                           "layer stores them (one row for "
                                           "all heads, zero lanes counted); "
                                           "absent from a per-head pool"),
    # ------------------------- generation by diffusion over blocks (PR 31)
    "serving/block_forwards_total": (COUNTER, "forwards run by decode chunks "
                                              "of a model that generates by "
                                              "blocks (each denoises or "
                                              "commits a slot's block)"),
    "serving/blocks_committed_total": (COUNTER, "blocks whose keys and "
                                                "values were committed to a "
                                                "slot's cache"),
    "serving/positions_unmasked_total": (COUNTER, "block positions unmasked "
                                                  "by denoise forwards"),
    "serving/blocks_merged_total": (COUNTER, "commits that opened the slot's "
                                             "next block in the same forward "
                                             "(PR 32): of blocks_committed, "
                                             "all but a request's last"),
    # ------------------------------------------------------------------ router
    "router/queue_depth": (GAUGE, "router admission queue depth per tick"),
    "router/retried_total": (COUNTER, "checkpointless retries (re-enqueues)"),
    "router/evicted_total": (COUNTER, "request evictions (replica death/drain)"),
    "router/completed_total": (COUNTER, "routed requests finished"),
    "router/rejected_total": (COUNTER, "routed requests rejected"),
    "router/handed_off_total": (COUNTER, "requests handed off at drain"),
    "router/drain_ms": (GAUGE, "graceful-drain wall time"),
    "router/ttft_ms": (HISTOGRAM, "end-to-end TTFT across retry attempts"),
    "router/tpot_ms": (HISTOGRAM, "end-to-end TPOT across retry attempts"),
    "router/replica{i}/health": (GAUGE, "replica state code (0 live .. 4 retiring)"),
    "router/replica{i}/outstanding": (GAUGE, "running + queued at the replica"),
    "router/replica{i}/prefix_hit_rate": (GAUGE, "per-replica prefix hit rate"),
    # --------------------------------------- fleet KV economy (PR 19)
    "router/fleet_prefix_hit_rate": (GAUGE, "admission-level hit rate summed "
                                            "across all replicas (in-process "
                                            "counters + hosted heartbeat "
                                            "gossip)"),
    "router/prefix_routed_total": (COUNTER, "dispatches won on a non-zero "
                                            "expected-prefix-saved score"),
    "router/prefix_saved_tokens_total": (COUNTER, "cumulative predicted "
                                                  "prefill tokens saved by "
                                                  "prefix-aware dispatch"),
    # --------------------------------------------- elastic control plane (PR 12)
    "router/live_replicas": (GAUGE, "attached non-DEAD replicas per tick"),
    "router/target_replicas": (GAUGE, "autoscaler's desired replica count"),
    "router/shed_total": (COUNTER, "requests shed at admission (infeasible "
                                   "deadline under SLO-aware admission)"),
    "router/deferred_total": (COUNTER, "low-priority requests deferred under "
                                       "the degradation ladder"),
    "router/deadline_miss_total": (COUNTER, "post-admission deadline expiries"),
    "router/degradation_rung": (GAUGE, "degradation ladder rung (0 healthy, "
                                       "1 defer-low, 2 shed-infeasible, "
                                       "3 admission-closed)"),
    "autoscale/scale_up_total": (COUNTER, "replicas added by the autoscaler"),
    "autoscale/scale_down_total": (COUNTER, "replicas retired by the autoscaler"),
    "autoscale/replica_seconds": (COUNTER, "integrated attached-replica "
                                           "seconds (provisioned capacity)"),
    # --------------------------------------- hosted replica supervision (PR 15)
    "host/restarts_total": (COUNTER, "supervised child-process respawns "
                                     "across hosted replicas"),
    "host/backoff_s": (GAUGE, "longest pending respawn backoff (0 = none)"),
    "host/child_rss_bytes": (GAUGE, "max child RSS across hosted replicas"),
    "host/pipe_lag_ms": (GAUGE, "max heartbeat pipe transit+age across "
                                "hosted replicas"),
    # ------------------------- host pauses and the chunk cycle (PR 55)
    "host/gc_pause_ms": (HISTOGRAM, "pause of one collection of python's "
                                    "garbage collector, every generation "
                                    "(the gc.callbacks hook)"),
    "host/gc_collections_total": (COUNTER, "collections of python's garbage "
                                           "collector since the hook went in"),
    "host/stalls_total": (COUNTER, "decode chunks whose fetch wait or "
                                   "turnaround ran far over its running "
                                   "median (kept as host.stall)"),
    "host/stall_ms_total": (COUNTER, "milliseconds those chunks' stalled "
                                     "phase ran over its running median"),
    "serving/chunk_fetch_wait_ms": (HISTOGRAM, "host wait in a decode "
                                               "chunk's serving.fetch (the "
                                               "device's chunk, as the host "
                                               "sees it)"),
    "serving/chunk_turnaround_ms": (HISTOGRAM, "host time from a chunk's "
                                               "fetch return to the next "
                                               "chunk's dispatch return, "
                                               "admissions left out"),
    # ------------------------------------------ socket replica transport (PR 16)
    "net/frames_total": (COUNTER, "wire frames moved (sent + decoded) per "
                                  "socket link"),
    "net/reconnects_total": (COUNTER, "successful redials by the reconnect "
                                      "state machine"),
    "net/quarantined_frames_total": (COUNTER, "frame-level quarantine "
                                              "events (bad magic/CRC/length "
                                              "-> resync)"),
    "net/partition_trips_total": (COUNTER, "connection severs observed "
                                           "(RST/FIN/partition aging out)"),
    "net/rtt_ms": (HISTOGRAM, "ping/pong round-trip per socket link"),
    # ---------------------------------------------------------------- training
    "Train/Samples/train_loss": (GAUGE, "loss at each optimizer step"),
    "Train/Samples/lr": (GAUGE, "learning rate at each optimizer step"),
    "Train/Samples/loss_scale": (GAUGE, "fp16 dynamic loss scale"),
    "Train/Comm/bytes_on_wire": (GAUGE, "modeled collective bytes per step "
                                        "(trace-time CollectiveSpans)"),
    "Train/Comm/overlap_ratio": (GAUGE, "fraction of wire bytes moved by "
                                        "overlap-scheduled collectives"),
    "Train/step_time_ms": (HISTOGRAM, "host wall time per optimizer step"),
    "Train/tokens_per_sec": (GAUGE, "global batch tokens / step time"),
    "Train/mfu": (GAUGE, "modeled model-flops utilization "
                         "(profiled flops / step time / peak)"),
    # ------------------------------------------ latency attribution (PR 14)
    "latency/e2e_ms": (HISTOGRAM, "end-to-end request latency (root span)"),
    "latency/phase/queue_ms": (HISTOGRAM, "admission-queue wait per request"),
    "latency/phase/admission_ms": (HISTOGRAM, "admission work (prefix "
                                              "lookup) per request"),
    "latency/phase/kv_restore_ms": (HISTOGRAM, "prefix-slab restore / page "
                                               "bind per request"),
    "latency/phase/prefill_ms": (HISTOGRAM, "prefill compute per request"),
    "latency/phase/decode_ms": (HISTOGRAM, "decode-chunk compute per request"),
    "latency/phase/gap_ms": (HISTOGRAM, "inter-chunk scheduling gap per "
                                        "request"),
    "latency/phase/retry_lost_ms": (HISTOGRAM, "time lost to abandoned lanes "
                                               "(evicted attempts) per "
                                               "request"),
    # ------------------------------------------- flight recorder (PR 14)
    "flight/retained_traces": (GAUGE, "span trees retained by tail sampling"),
    "flight/retained_spans": (GAUGE, "total spans across retained trees"),
    "flight/dumps_total": (COUNTER, "flight bundles written"),
    # ------------------------------------------- anomaly detector (PR 14)
    "anomaly/trips_total": (COUNTER, "anomaly-detector trips (rate-limited)"),
    "anomaly/last_score": (GAUGE, "robust-z score of the last trip"),
    # --------------------------------------------------------------- inference
    "inference/ttft_ms": (HISTOGRAM, "prefill latency per generate call"),
    "inference/tpot_ms": (HISTOGRAM, "decode seconds-per-token per generate"),
    "inference/decode_tokens_per_sec": (GAUGE, "batch-aggregate decode tok/s"),
    "inference/weight_quant/bits": (GAUGE, "quantized weight width"),
    "inference/weight_quant/matrices_quantized": (GAUGE, "matrices quantized"),
    "inference/weight_quant/matrices_kept_fp": (GAUGE, "matrices kept fp"),
    "inference/weight_quant/modeled_step_bytes": (GAUGE,
                                                  "modeled weight bytes/step"),
    "inference/weight_quant/reduction_vs_bf16": (GAUGE,
                                                 "modeled stream reduction"),
}

#: sinks of a span: the profiler's xplane and the ring (a scoped
#: ``tracer.span``), the ring alone (request roots and retroactive spans,
#: which no TraceMe can hold), or both plus ``tracer.phases`` (set-up).
BOTH = "xplane+ring"
RING = "ring"
PHASE = "xplane+ring+phases"
#: a host pause: kept in ``tracer.pauses`` with the tracer on or off. A
#: collection reaches the xplane (generation 2 only) and never the ring; a
#: stall is known only when it is over, so the ring and never the xplane.
PAUSE_GC = "xplane+pauses"
PAUSE_STALL = "ring+pauses"

#: span name -> (sinks, layer, attributes, what reads it). THE span schema:
#: every name a ``span`` / ``phase`` / ``begin`` / ``start_span`` /
#: ``record_span`` / ``record_pause`` / ``instant`` call in
#: :data:`SPAN_MODULES` uses. ``reads``
#: names the benchmark metric (``benchmarks/chipbench/layer_metrics/``) or
#: the operator feature the span exists for.
SPANS: Dict[str, Tuple[str, str, Tuple[str, ...], str]] = {
    # ---------------------------------------------------------- serve scheduler
    "serving.step": (BOTH, "serve scheduler",
                     ("step", "queue_depth", "active_slots"),
                     "frames the phases of one step() in the ring; "
                     "sched_fetch_idle_ms_per_step's earlier lines"),
    "serving.sweep": (BOTH, "serve scheduler", (),
                      "breakdown idle gaps (deadline and cancellation sweeps)"),
    "serving.admit": (BOTH, "serve scheduler",
                      ("request_id", "queue_wait_ms", "prompt_tokens",
                       "prefix_len", "slot", "outcome", "programs"),
                      "sched_admit_host_ms; attribution phase admission "
                      "(programs: the compiled programs the admission "
                      "dispatched, 2 on a miss: prefill and scatter)"),
    "serving.prefix_lookup": (BOTH, "serve scheduler",
                              ("hit", "matched_tokens"),
                              "sched_admit_host_ms lines; attribution phase "
                              "admission"),
    "serving.page_table": (BOTH, "serve scheduler",
                           ("op", "pages_fresh", "pages_shared", "cow"),
                           "sched_admit_host_ms lines; attribution phase "
                           "kv_restore"),
    "serving.restore_prefix": (BOTH, "serve scheduler",
                               ("slot", "prefix_len", "promoted"),
                               "attribution phase kv_restore (the host "
                               "tier's slab restored into fresh pages)"),
    "serving.clear_state": (BOTH, "serve scheduler", ("slot", "state_bytes"),
                            "breakdown idle gaps (a released slot's per-slot "
                            "state zero-filled: state_bytes are the slot's own, "
                            "one write a state array)"),
    "serving.prefill": (BOTH, "compiled steps",
                        ("request_id", "bucket", "tokens", "prefix_len",
                         "moe_assignments", "moe_experts_touched",
                         "moe_tile_rows",
                         "blocks_committed", "positions_self",
                         "positions_cross"),
                        "sched_admit_host_ms by bucket; attribution phase "
                        "prefill; moe_experts_touched_per_step lines; "
                        "block_tokens_per_forward lines; "
                        "prefill_cross_decoder_dev_ms lines (a prefill that "
                        "stops early: positions the layers up to the stop "
                        "ran at, and the layers after it)"),
    "serving.suffix_prefill": (BOTH, "compiled steps",
                               ("request_id", "bucket", "tokens",
                                "prefix_len"),
                               "sched_admit_host_ms by bucket; attribution "
                               "phase prefill"),
    "serving.scatter_prefill": (BOTH, "serve scheduler", (),
                                "breakdown idle gaps (prefill KV into pages)"),
    "serving.prefix_insert": (BOTH, "serve scheduler", (),
                              "breakdown idle gaps (share pages with the "
                              "prefix cache)"),
    "serving.decode_chunk": (BOTH, "compiled steps",
                             ("chunk", "active_slots", "request_ids",
                              "slot_steps_run", "attn_rows", "tokens_kept",
                              "deliveries", "stalled_deliveries",
                              "moe_assignments", "moe_experts_touched",
                              "forwards",
                              "blocks_committed", "positions_unmasked",
                              "block_length", "blocks_merged",
                              "fetch_wait_ms", "turnaround_ms", "admit_ms"),
                             "decode_wasted_step_pct, delivery_stalled_pct, "
                             "sched_fetch_idle_ms_per_step, "
                             "sched_chunk_turnaround_host_ms (fetch_wait_ms, "
                             "turnaround_ms, admit_ms: the chunk cycle on "
                             "the host's clock), "
                             "moe_experts_touched_per_step, "
                             "moe_ffn_roofline_pct, block_tokens_per_forward, "
                             "block_forward_hbm_roofline_pct, "
                             "moe_gated_ffn_roofline_pct"),
    "serving.spec_verify": (BOTH, "compiled steps",
                            ("chunk", "active_slots", "request_ids",
                             "slot_steps_run", "tokens_kept", "deliveries",
                             "stalled_deliveries", "fetch_wait_ms",
                             "turnaround_ms", "admit_ms"),
                            "as serving.decode_chunk, for a speculative "
                            "verify round"),
    # arrays: the device arrays that crossed the host-device boundary in the
    # span (puts under place_inputs, copies back under fetch): 1 and 1 for a
    # decode chunk, 2 and 1 for a prefill
    "serving.place_inputs": (BOTH, "serve scheduler", ("program", "arrays"),
                             "sched_fetch_idle_ms_per_step and "
                             "sched_admit_host_ms lines"),
    # seq: the executor's running count of dispatched programs
    # (``pool.programs``), so that a reader pairs dispatches with the
    # device's executions by ORDER and never across the two planes' clocks
    "serving.dispatch": (BOTH, "serve scheduler", ("program", "seq"),
                         "sched_chunk_gap_dev_ms lines (chunk_cycles: the "
                         "causal bounds on the host plane's offset); "
                         "sched_fetch_idle_ms_per_step lines"),
    "serving.fetch": (BOTH, "serve scheduler", ("program", "arrays"),
                      "sched_chunk_gap_dev_ms lines (chunk_cycles: the "
                      "fetch's return bounds the offset from above); "
                      "sched_fetch_idle_ms_per_step; sched_admit_host_ms "
                      "lines"),
    "serving.harvest": (BOTH, "serve scheduler", ("finished",),
                        "sched_fetch_idle_ms_per_step lines"),
    "serving.telemetry": (BOTH, "serve scheduler", (),
                          "sched_fetch_idle_ms_per_step lines"),
    # request roots and retroactive spans of a request's own trace
    "replica_request": (RING, "serve scheduler",
                        ("request_id", "prompt_tokens", "max_new_tokens",
                         "state", "reason", "tokens"),
                        "flight recorder and attribution root of a request"),
    "queue_wait": (RING, "serve scheduler", (),
                   "attribution phase queue (the xplane has queue_wait_ms on "
                   "serving.admit)"),
    "decode_chunk": (RING, "serve scheduler",
                     ("request_id", "chunk", "slot", "tokens"),
                     "attribution phase decode: a serving.decode_chunk as "
                     "one of its requests saw it"),
    "retire": (RING, "serve scheduler", ("state", "reason"),
               "flight recorder (why a request left its slot)"),
    # host pauses (process-wide: a training process keeps host.gc too)
    "host.gc": (PAUSE_GC, "serve scheduler", ("generation", "collected"),
                "host_pause_pct; breakdown idle gaps (generation 2 is an "
                "annotation: the innermost host event over its gap)"),
    "host.stall": (PAUSE_STALL, "serve scheduler",
                   ("phase", "ms", "typical_ms", "gc_ms"),
                   "host_pause_pct (phase fetch | turnaround; gc_ms: the "
                   "collector's part of it)"),
    # ------------------------------------------------------------- train engine
    "train_step": (BOTH, "train engine",
                   ("step", "bytes_on_wire", "overlap_ratio", "offload"),
                   "train_host_ms_per_step"),
    "train.host_batch": (BOTH, "train engine", (),
                         "train_host_ms_per_step lines"),
    "train.dispatch": (BOTH, "train engine", (),
                       "train_host_ms_per_step lines"),
    "train.bookkeeping": (BOTH, "train engine", (),
                          "train_host_ms_per_step lines"),
    "checkpoint_commit": (BOTH, "train engine", ("tag", "step"),
                          "operator: the commit stall of a checkpoint save "
                          "(docs/OBSERVABILITY.md)"),
    # ------------------------------------------------------------ device set-up
    "setup.engine_init": (PHASE, "device set-up", (), "setup_engine_init_s"),
    "setup.mesh": (PHASE, "device set-up", (), "setup_engine_init_s lines"),
    "setup.init_params": (PHASE, "device set-up", (),
                          "setup_engine_init_s lines"),
    "setup.init_optimizer": (PHASE, "device set-up", (),
                             "setup_engine_init_s lines"),
    "setup.place_state": (PHASE, "device set-up", (),
                          "setup_engine_init_s lines"),
    # layer_loop, layers: which way ``models/gpt2.py: unroll_layer_loop`` sent the
    # scan over layers ("unrolled" | "scan") in the step traced under the phase
    "setup.build_train_step": (PHASE, "device set-up", ("layer_loop", "layers"),
                               "setup_engine_init_s (the attributes: its lines)"),
    "setup.inference_engine_init": (PHASE, "device set-up", (),
                                    "setup_engine_init_s"),
    "setup.place_params": (PHASE, "device set-up", (),
                           "setup_engine_init_s lines"),
    "setup.balance_experts": (PHASE, "device set-up", (),
                              "setup_engine_init_s lines (random weights of a "
                              "configuration with level_random_experts: the "
                              "selection bias levelled on random tokens; or "
                              "with home_random_routers: home experts a token id)"),
    "setup.kv_pool": (PHASE, "device set-up",
                      ("pool", "pages", "slots", "state_bytes",
                       "heads_per_row", "ring_bytes"),
                      "setup_engine_init_s"),
    "setup.program": (PHASE, "device set-up",
                      ("program", "bucket", "moe_tile_rows"),
                      "setup_engine_init_s lines, beside setup_compile_s "
                      "(moe_tile_rows: the height of the expert kernel's "
                      "tiles the program was built with)"),
}

#: modules whose span call sites the lint walks (repo-relative paths)
SPAN_MODULES = (
    "deepspeed_tpu/inference/serving/scheduler.py",
    "deepspeed_tpu/inference/serving/executor.py",
    "deepspeed_tpu/inference/serving/kv_pool.py",
    "deepspeed_tpu/inference/serving/telemetry.py",
    "deepspeed_tpu/observability/trace.py",
    "deepspeed_tpu/inference/engine.py",
    "deepspeed_tpu/runtime/engine.py",
)


#: what ``observability.scope(name)`` puts in front of ``name`` on jax's
#: name stack: a reader finds the declared scopes of an op by this prefix
#: alone, whatever the program's version
SCOPE_PREFIX = "ds."

#: scope name -> (layer, what it holds, what reads it). THE schema of the
#: DEVICE's regions: every name an ``observability.scope`` call in
#: :data:`SCOPE_MODULES` opens INSIDE a compiled program. A scope is a
#: ``jax.named_scope``: it exists while jax traces, lands in each op's
#: ``op_name`` metadata (the ``tf_op`` stat of the op's event in a profiler
#: trace) and changes nothing in the executed program, so there is no switch.
#: The names say what the ops DO, the same in every model; the phase
#: (forward, ``transpose(`` backward, ``rematted_computation`` recomputed) is
#: jax's own and is not declared again. ``act.stack``, a scan's stack of saved
#: activations, is no scope: jax makes those slices outside any function of
#: the program, and the reader (``benchmarks/chipbench/device_scopes.py``)
#: names ``while/body/dynamic_slice`` and ``dynamic_update_slice`` so.
_STEP = "compiled steps"
_TRAIN = "train engine"
_COMM = "zero and collectives"
_TABLE = "the traced run's table of device time by scope"
SCOPES: Dict[str, Tuple[str, str, str]] = {
    # ------------------------------------------------ a model's forward
    "embed": (_STEP, "token and position embedding lookups, embedding norm",
              "train_outside_layers_dev_ms"),
    "norm": (_STEP, "a layer's input norms", _TABLE),
    "residual": (_STEP, "residual adds and dropout between a layer's parts",
                 _TABLE),
    "attn.qkv": (_STEP, "q/k/v projections, bias, split",
                 "train_matmul_roofline_pct"),
    "attn.heads": (_STEP, "reshapes and transposes between (b, t, h, d) and "
                          "the attention's layout; rotary, q/k norm",
                   "decode_attn_dev_ms_per_step"),
    "attn.core": (_STEP, "flash_*, decode_attention, the XLA products and "
                         "softmax (and the layout changes the kernels' own "
                         "wrappers make)",
                  "decode_attn_dev_ms_per_step"),
    "attn.out": (_STEP, "the attention's output projection",
                 "train_matmul_roofline_pct"),
    "attn.latent": (_STEP, "a latent layer's absorbed one-token attention: "
                           "queries into the latent, scores and values over "
                           "the cached rows, the value expansion",
                    "latent_attn_dev_ms_per_step"),
    "attn.latent_expand": (_STEP, "a latent layer's keys and values expanded "
                                  "from the prompt's latents (prefill, forward)",
                           _TABLE),
    "attn.shared": (_STEP, "differential attention over the ONE cache that a "
                           "full layer keeps and the cross layers after it "
                           "re-read (a decode step's eight reads of it; a "
                           "whole sequence's causal flash or products)",
                    "shared_kv_attn_dev_ms_per_step, shared_kv_attn_roofline_pct"),
    "attn.window": (_STEP, "a windowed layer's ring: the token's row written "
                           "at t mod window, the attention over the ring; in "
                           "a prefill the banded flash call and the ring the "
                           "prompt leaves",
                    "window_attn_dev_ms_per_step"),
    "attn.diff": (_STEP, "differential attention's combination: lambda, the "
                         "subtraction of a pair's two maps, the norm over "
                         "the pair", _TABLE),
    "kv.append": (_STEP, "new keys and values written into the cache",
                  "decode_attn_dev_ms_per_step"),
    "mlp.up": (_STEP, "the MLP's first projection, with the gate",
               "train_matmul_roofline_pct"),
    "mlp.act": (_STEP, "the MLP's activation (and gate product)", _TABLE),
    "mlp.down": (_STEP, "the MLP's second projection",
                 "train_matmul_roofline_pct"),
    "moe.router": (_STEP, "router scores and the top-k", _TABLE),
    "moe.plan": (_STEP, "the dispatch plan's sorts and comparisons", _TABLE),
    "moe.rows": (_STEP, "rows gathered into plan order, gathered back, the "
                        "weighted sum", _TABLE),
    "moe.experts": (_STEP, "the expert FFNs (moe_grouped_ffn; the classic "
                           "layer's dispatch einsums)",
                    _TABLE + ", beside moe_decode_dev_ms_per_step"),
    "moe.shared": (_STEP, "the shared expert beside the routed ones: in the "
                          "latent mixture with the projections to and from "
                          "the latent space, in the gated mixture its three "
                          "matmuls on the layer's own normed input", _TABLE),
    "ssm.in": (_STEP, "a state-space mixer's (Mamba-2, Mamba-1) input "
                      "projection and its split", _TABLE),
    "ssm.conv": (_STEP, "the causal convolution and its window", _TABLE),
    "ssm.select": (_STEP, "Mamba-1's selection: x_proj, dt_proj, the softplus "
                          "(B, C and dt of a token from its own input)",
                   _TABLE),
    "ssm.update": (_STEP, "the one-token state update; a prefill's scan "
                          "(Mamba-2's chunked form, Mamba-1's selective_scan "
                          "kernel)",
                   _TABLE + ", beside ssm_decode_dev_ms_per_step; "
                            "selective_scan_roofline_pct"),
    "ssm.out": (_STEP, "the gate (Mamba-2: and its norm) and the output "
                       "projection", _TABLE),
    "gmu.gate": (_STEP, "a gated memory unit's input projection and the gate "
                        "silu(W_in u) * m on the memory an earlier Mamba-1 "
                        "layer handed on", _TABLE),
    "gmu.out": (_STEP, "a gated memory unit's output projection", _TABLE),
    "sconv.in": (_STEP, "a gated short convolution's input projection and "
                        "the product B * u",
                 _TABLE + ", shortconv_decode_dev_ms_per_step"),
    "sconv.conv": (_STEP, "the causal taps over the window and the window's "
                          "roll (or the state a prompt leaves)",
                   _TABLE + ", shortconv_decode_dev_ms_per_step"),
    "sconv.out": (_STEP, "the gate C * and the output projection",
                  _TABLE + ", shortconv_decode_dev_ms_per_step"),
    "head": (_STEP, "final norm, the rows the logits are read at, the "
                    "vocabulary matmul",
             "decode_head_dev_ms_per_step, train_outside_layers_dev_ms, "
             "train_matmul_roofline_pct"),
    "sample": (_STEP, "logits_transform, argmax or categorical, block_unmask",
               "decode_head_dev_ms_per_step"),
    # ------------------------------------------------- a decode chunk
    "chunk.state": (_STEP, "a step's per-slot bookkeeping (lens, active, "
                           "remaining, the token buffer, a block's commit)",
                    _TABLE),
    "kv.gather": (_STEP, "pages gathered into the dense view, once a chunk "
                         "or a suffix prefill", "decode_per_chunk_dev_ms"),
    "kv.copy_back": (_STEP, "the chunk's new rows mirrored or copied back "
                            "into the pages", "decode_per_chunk_dev_ms"),
    "chunk.pack": (_STEP, "the packed operand unpacked, the result packed",
                   "decode_per_chunk_dev_ms"),
    # ----------------------------------------------------- a train step
    "param.cast": (_TRAIN, "the compute-dtype copy of the master parameters",
                   _TABLE),
    "loss": (_TRAIN, "the cross entropy and the loss scale",
             "train_outside_layers_dev_ms"),
    "grad.accum": (_TRAIN, "micro-batch gradients added to the accumulator",
                   _TABLE),
    "grad.norm_clip": (_TRAIN, "unscale, global norm, overflow check, clip",
                       "train_outside_layers_dev_ms"),
    "optimizer.update": (_TRAIN, "the optimizer's update and the overflow "
                                 "guard", "train_outside_layers_dev_ms"),
    # ------------------------------------------------------ collectives
    "comm.allgather_matmul_monolithic": (_COMM, "all-gather then matmul", _TABLE),
    "comm.matmul_reduce_scatter_monolithic": (_COMM, "matmul then "
                                              "reduce-scatter", _TABLE),
    "comm.chunked_allgather_matmul": (_COMM, "the all-gather ring "
                                      "overlapped with its matmul", _TABLE),
    "comm.chunked_matmul_reduce_scatter": (_COMM, "the matmul overlapped "
                                           "with its reduce-scatter ring",
                                           _TABLE),
    "comm.chunked_expert_exchange": (_COMM, "the capacity-chunked expert "
                                     "all-to-all", _TABLE),
    "comm.fused_quant_matmul_reduce_scatter": (_COMM, "the intN-wire "
                                               "reduce-scatter ring", _TABLE),
    "comm.fused_quant_allgather_matmul": (_COMM, "the intN-wire all-gather "
                                          "ring", _TABLE),
    "comm.quantized_allreduce": (_COMM, "the error-compensated intN "
                                 "gradient mean", _TABLE),
}

#: modules whose ``scope(...)`` call sites the lint walks (repo-relative)
SCOPE_MODULES = (
    "deepspeed_tpu/models/gpt2.py",
    "deepspeed_tpu/models/causal_lm.py",
    "deepspeed_tpu/models/mamba2.py",
    "deepspeed_tpu/models/mamba1.py",
    "deepspeed_tpu/models/short_conv.py",
    "deepspeed_tpu/moe/gated_moe.py",
    "deepspeed_tpu/moe/latent_moe.py",
    "deepspeed_tpu/ops/moe/grouped_ffn.py",
    "deepspeed_tpu/inference/decode_fns.py",
    "deepspeed_tpu/inference/serving/executor.py",
    "deepspeed_tpu/runtime/engine.py",
    "deepspeed_tpu/parallel/overlap.py",
    "deepspeed_tpu/parallel/qring.py",
    "deepspeed_tpu/comm/compressed.py",
)


def scope_sites_digest() -> str:
    """A digest of what the compiled programs' ops are named: the prefix, and
    for every file of :data:`SCOPE_MODULES` the scopes its call sites open,
    in source order (no line numbers: an edit elsewhere in a file moves
    nothing here). ``utils.device.enable_compile_cache`` adds it to jax's
    persistent-cache key, which leaves op names out: without it a program
    that differs from a cached one only in its scopes is LOADED with the
    cached one's op metadata, and a profiler trace shows names the source
    does not have (a kernel-free serving program read 0 % scoped on a machine
    whose cache held its parent's build; PERF.md, PR 36)."""
    import hashlib
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    h = hashlib.sha256(SCOPE_PREFIX.encode())
    for rel, names in scope_sites(root).items():
        h.update(("\n" + rel + ":" + ",".join(str(n) for n in names)).encode())
    return h.hexdigest()[:16]


def scope_sites(repo_root: str) -> Dict[str, List[Optional[str]]]:
    """``{file of SCOPE_MODULES: the scope names its call sites open, in
    source order}`` (``None`` for a name that is no literal; a file that
    cannot be read or parsed is left out: the lint's runner reports it)."""
    import ast
    import os
    from ..analysis.ast_rules import iter_scope_names_from_tree
    out = {}
    for rel in SCOPE_MODULES:
        try:
            with open(os.path.join(repo_root, rel)) as f:
                tree = ast.parse(f.read(), filename=rel)
        except (OSError, SyntaxError):
            continue
        sites = sorted(iter_scope_names_from_tree(tree), key=lambda site: site[1])
        out[rel] = [name for name, _ in sites]
    return out


def resolve_scope(name: str) -> Optional[str]:
    """The declared scope a call site's literal name is, or None."""
    return name if name in SCOPES else None


def resolve_span(name: str) -> Optional[str]:
    """The declared span a call site's literal name is, or None."""
    return name if name in SPANS else None


_TEMPLATE_SEG = re.compile(r"\{[A-Za-z_][A-Za-z0-9_]*\}")


def _pattern_regex(pattern: str) -> "re.Pattern":
    parts = _TEMPLATE_SEG.split(pattern)
    return re.compile(r"\d+".join(re.escape(p) for p in parts) + r"$")


_COMPILED: List[Tuple[str, "re.Pattern"]] = [
    (p, _pattern_regex(p)) for p in TAGS
]


def resolve(tag: str) -> Optional[str]:
    """The schema pattern a concrete tag matches, or None if undeclared.
    ``tag`` may itself be a wildcard form (``router/replica*/health``, the
    lint's rendering of an f-string) — a ``*`` segment matches ``{i}``."""
    if tag in TAGS:
        return tag
    if "*" in tag:
        want = re.escape(tag).replace(r"\*", r"\{[A-Za-z_][A-Za-z0-9_]*\}")
        rx = re.compile(want + "$")
        matches = [p for p in TAGS if rx.match(p)]
        return matches[0] if len(matches) == 1 else None
    for pattern, rx in _COMPILED:
        if rx.match(tag):
            return pattern
    return None


def kind_of(tag: str) -> str:
    """Instrument kind for a concrete tag. Raises ``KeyError`` on an
    undeclared tag — the runtime face of the lint."""
    pattern = resolve(tag)
    if pattern is None:
        raise KeyError(
            f"metric tag {tag!r} is not declared in observability.schema.TAGS "
            "— declare it (kind + help) before emitting it")
    return TAGS[pattern][0]


def is_declared(tag: str) -> bool:
    return resolve(tag) is not None


# --------------------------------------------------------------------- linting
#: modules whose emission sites the tag lint walks (repo-relative paths)
EMITTER_MODULES = (
    "deepspeed_tpu/inference/serving/telemetry.py",
    "deepspeed_tpu/inference/speculative.py",
    "deepspeed_tpu/inference/serving/router.py",
    "deepspeed_tpu/inference/serving/autoscale.py",
    "deepspeed_tpu/inference/serving/host.py",
    "deepspeed_tpu/inference/serving/net.py",
    "deepspeed_tpu/runtime/engine.py",
    "deepspeed_tpu/inference/engine.py",
    "deepspeed_tpu/observability/metrics.py",
    "deepspeed_tpu/observability/trace.py",
    "deepspeed_tpu/observability/attribution.py",
    "deepspeed_tpu/observability/flight.py",
    "deepspeed_tpu/observability/anomaly.py",
)


def iter_emission_tags(path: str) -> Iterator[Tuple[str, int]]:
    """Yield ``(tag_literal, lineno)`` for every tag-shaped string that feeds
    a metric emission in ``path``. The walker itself lives in the shared AST
    lint framework (``analysis.ast_rules.iter_emission_tags``) — this module
    keeps the schema-facing API and the declaration table."""
    from ..analysis.ast_rules import iter_emission_tags as _iter
    yield from _iter(path)


def emission_tag_rule():
    """The schema lint as an :class:`~deepspeed_tpu.analysis.ast_rules.AstRule`
    — the form ``bin/ds-tpu-lint`` runs it in, next to the bare-assert and
    hot-path-sync rules."""
    from ..analysis.ast_rules import EmissionTagRule
    return EmissionTagRule(resolve, EMITTER_MODULES,
                           resolve_span=resolve_span,
                           span_modules=SPAN_MODULES,
                           resolve_scope=resolve_scope,
                           scope_modules=SCOPE_MODULES)


def lint_emission_sites(repo_root: str) -> List[str]:
    """Every undeclared tag across :data:`EMITTER_MODULES`, every undeclared
    span name across :data:`SPAN_MODULES` and every undeclared device scope
    across :data:`SCOPE_MODULES`, as ``"path:line: tag"`` strings, and every
    declared scope that no call site opens (empty list = clean). Runs under
    the shared AST rule runner (one framework for every source-level rule)."""
    from ..analysis.ast_rules import run_ast_rules
    paths = tuple(dict.fromkeys(EMITTER_MODULES + SPAN_MODULES + SCOPE_MODULES))
    result = run_ast_rules(repo_root, [emission_tag_rule()], paths=paths)
    # a syntax error in an emitter module surfaces as a runner finding with
    # no 'tag' detail — report it as a problem, don't crash on it
    problems = [f"{f.site}: {f.details.get('tag', f.message)}"
                for f in result.findings]
    opened = {name for names in scope_sites(repo_root).values() for name in names}
    problems += [f"observability/schema.py: device scope {name!r} is declared "
                 "in SCOPES and opened nowhere in SCOPE_MODULES"
                 for name in SCOPES if name not in opened]
    return problems
