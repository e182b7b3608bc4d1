"""Prometheus metrics with a hierarchical label scheme.

The reference builds a DRT -> Namespace -> Component -> Endpoint metrics
hierarchy with a canonical name registry (ref: lib/runtime/src/metrics.rs,
metrics/prometheus_names.rs) exposed on the system status server /metrics.
We use prometheus_client with the same hierarchy expressed as labels, and a
single process registry so every subsystem lands on one scrape page.
"""

from __future__ import annotations

import time

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)
from prometheus_client.openmetrics.exposition import (
    CONTENT_TYPE_LATEST as OPENMETRICS_CONTENT_TYPE,
)
from prometheus_client.openmetrics.exposition import (
    generate_latest as _generate_openmetrics,
)

# One registry per process — mirrors the reference's DRT-rooted hierarchy.
REGISTRY = CollectorRegistry()

_HIER = ["namespace", "component", "endpoint"]

# Canonical metric families (ref: metrics/prometheus_names.rs naming scheme)
REQUESTS_TOTAL = Counter(
    "dynamo_requests_total", "Requests handled", _HIER + ["status"], registry=REGISTRY
)
REQUEST_DURATION = Histogram(
    "dynamo_request_duration_seconds", "End-to-end request duration", _HIER,
    registry=REGISTRY,
    buckets=(0.005, 0.025, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 120.0),
)
INFLIGHT = Gauge(
    "dynamo_inflight_requests", "In-flight requests", _HIER, registry=REGISTRY
)
# Frontend service metrics that feed the Planner (ref: http/service/metrics.rs
# TTFT/ITL histograms)
TTFT_SECONDS = Histogram(
    "dynamo_time_to_first_token_seconds", "Time to first token", ["model"],
    registry=REGISTRY,
    buckets=(0.01, 0.025, 0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 3.2, 6.4, 12.8),
)
ITL_SECONDS = Histogram(
    "dynamo_inter_token_latency_seconds", "Inter-token latency", ["model"],
    registry=REGISTRY,
    buckets=(0.002, 0.005, 0.01, 0.02, 0.04, 0.08, 0.16, 0.32, 0.64),
)
# Per-pipeline-stage latency (ref: STAGE_DURATION_SECONDS histograms at
# pipeline/network/egress/push_router.rs:21 — which stage is eating the
# request budget)
STAGE_DURATION = Histogram(
    "dynamo_stage_duration_seconds", "Pipeline stage duration",
    ["stage", "model"], registry=REGISTRY,
    buckets=(0.0005, 0.002, 0.01, 0.05, 0.2, 1.0, 5.0),
)
INPUT_TOKENS = Histogram(
    "dynamo_input_sequence_tokens", "Input sequence length", ["model"],
    registry=REGISTRY, buckets=(32, 128, 512, 1024, 2048, 4096, 8192, 16384, 32768),
)
OUTPUT_TOKENS = Histogram(
    "dynamo_output_sequence_tokens", "Output sequence length", ["model"],
    registry=REGISTRY, buckets=(1, 16, 64, 128, 256, 512, 1024, 2048, 4096),
)
# A frame's egress (llm/tokenizer.py IncrementalDetokenizer,
# llm/http_service.py _write_events). decoded / tokens = ids re-decoded for a
# token emitted (about 1 for a byte tokenizer, under 24 behind a merging
# one); chunks / writes = SSE events a socket write (the tokens a frame).
# Both detokeniser counters grow where a request ends, by that request.
DETOK_TOKENS = Counter(
    "dynamo_frontend_detok_tokens_total",
    "Token ids pushed through the incremental detokeniser", registry=REGISTRY,
)
DETOK_DECODED_TOKENS = Counter(
    "dynamo_frontend_detok_decoded_tokens_total",
    "Token ids handed to Tokenizer.decode by the incremental detokeniser",
    registry=REGISTRY,
)
SSE_CHUNKS = Counter(
    "dynamo_frontend_sse_chunks_total",
    "SSE events written to streaming responses", registry=REGISTRY,
)
SSE_WRITES = Counter(
    "dynamo_frontend_sse_writes_total",
    "Writes that carried those events to the transport", registry=REGISTRY,
)
KV_USAGE = Gauge(
    "dynamo_kv_usage_ratio", "Paged-KV pool usage fraction", ["worker"],
    registry=REGISTRY,
)
ROUTER_DECISIONS = Counter(
    "dynamo_router_decisions_total", "Routing decisions", ["mode"], registry=REGISTRY
)
# Resilience plane (runtime/resilience.py): deadlines, retry budgets,
# circuit breakers — the bounded-degradation signals dashboards alarm on
# during a brownout (docs/fault-tolerance.md).
RETRIES_TOTAL = Counter(
    "dynamo_retries_total", "Request-plane retry attempts by outcome "
    "(allowed = dispatched, denied = retry budget exhausted)",
    ["endpoint", "outcome"], registry=REGISTRY,
)
RETRY_BUDGET_BALANCE = Gauge(
    "dynamo_retry_budget_balance", "Retry-budget tokens currently available",
    ["endpoint"], registry=REGISTRY,
)
BREAKER_STATE = Gauge(
    "dynamo_circuit_breaker_state",
    "Circuit breaker state per instance (0=closed 1=open 2=half_open)",
    ["endpoint", "instance"], registry=REGISTRY,
)
BREAKER_TRANSITIONS = Counter(
    "dynamo_circuit_breaker_transitions_total",
    "Circuit breaker state transitions, by state entered",
    ["endpoint", "state"], registry=REGISTRY,
)
DEADLINE_EXCEEDED = Counter(
    "dynamo_deadline_exceeded_total",
    "Requests whose end-to-end deadline budget expired, by component",
    ["component"], registry=REGISTRY,
)
REQUESTS_SHED = Counter(
    "dynamo_requests_shed_total",
    "Requests shed at admission with 503, by reason",
    ["reason"], registry=REGISTRY,
)
# Deadline-aware admission (runtime/admission.py): the queue-wait
# estimate each admission edge checks deadlines against. A rising gauge
# with flat shed counts means budgets still cover the backlog; shed
# counts rising with a flat gauge means budgets got shorter.
ADMISSION_WAIT_MS = Gauge(
    "dynamo_admission_queue_wait_ms",
    "Estimated queue wait (ms) at an admission edge's last decision, "
    "per pool (inf collapses to the Retry-After cap)",
    ["pool"], registry=REGISTRY,
)
# Planner observability (planner/core.py + global_planner): every
# adjustment interval publishes its targets and the reason for the last
# decision, so chaos assertions and operators read planner behavior from
# /metrics instead of log-scraping (docs/metrics.md).
PLANNER_TARGET_REPLICAS = Gauge(
    "dynamo_planner_target_replicas",
    "Replica target the planner last decided, per pool "
    "(prefill / decode, or the pool namespace under the global planner)",
    ["pool"], registry=REGISTRY,
)
PLANNER_CORRECTION = Gauge(
    "dynamo_planner_correction_factor",
    "SLA planner correction factor (observed latency / interpolated "
    "expectation), per phase (prefill / decode)",
    ["phase"], registry=REGISTRY,
)
PLANNER_GOODPUT_RATIO = Gauge(
    "dynamo_planner_goodput_ratio",
    "SLO-good / total request ratio the planner observed in its last "
    "adjustment interval (from the frontend dynamo_slo_* counters)",
    registry=REGISTRY,
)
PLANNER_DECISIONS = Counter(
    "dynamo_planner_decisions_total",
    "Planner decisions by pool and reason (scale_up / scale_down / "
    "hold / rebalance / hysteresis_hold)",
    ["pool", "reason"], registry=REGISTRY,
)
PLANNER_LAST_DECISION_TS = Gauge(
    "dynamo_planner_last_decision_unixtime",
    "Wall-clock time of the planner's most recent applied decision",
    registry=REGISTRY,
)
# SLO goodput layer (docs/observability.md): the planner consumes
# good/total ratios per model instead of re-deriving them from latency
# histograms ("goodput, not throughput" — the serving-SLO literature).
SLO_REQUESTS = Counter(
    "dynamo_slo_requests_total",
    "Finished frontend requests considered for the SLO goodput ratio, "
    "by model, priority class and tenant (untagged requests count "
    "priority=standard tenant=untagged)",
    ["model", "priority", "tenant"], registry=REGISTRY,
)
SLO_GOOD = Counter(
    "dynamo_slo_good_total",
    "Requests that finished OK within the DYNT_SLO_TTFT_MS / "
    "DYNT_SLO_ITL_MS targets (an unset target always passes), by "
    "model, priority class and tenant — per-class goodput is the "
    "multi-tenant QoS headline (docs/multi-tenancy.md)",
    ["model", "priority", "tenant"], registry=REGISTRY,
)
# Multi-tenant QoS plane (docs/multi-tenancy.md): who absorbed the
# shed, and how often batch decode slots were preempted for
# interactive pressure.
TENANT_SHED = Counter(
    "dynamo_tenant_shed_total",
    "Requests shed at an admission edge attributed to a tenant, by "
    "reason: quota (over weighted fair share under contention) or "
    "queue (deadline-aware admission). Untagged requests count under "
    "tenant=untagged only when quota-shed",
    ["tenant", "reason"], registry=REGISTRY,
)
PREEMPT_TOTAL = Counter(
    "dynamo_preempt_total",
    "Scheduler preemption events, by kind: park (batch decode slot "
    "offloaded to the KVBM park store under interactive pressure), "
    "migrate (cooperative preempt-and-migrate fallback — the worker "
    "emitted finish_reason=migrate), resume (parked sequence restored "
    "and decoding again); and by what admission had run out of: a slot, "
    "the full page group, the window page group (a resume counts under "
    "full)",
    ["kind", "group"], registry=REGISTRY,
)
# Runtime protocol conformance (runtime/conformance.py): lifecycle
# events the ProtocolMonitor observed that the dynastate spec machines
# (tools/dynastate/protocols/) forbid. Rules keep the static ids:
# DS101 = no transition for the event in the current state, DS201 =
# event after a terminal state. Chaos scenarios assert this stays 0.
PROTOCOL_VIOLATIONS = Counter(
    "dynamo_protocol_violations_total",
    "Observed lifecycle events forbidden by the dynastate protocol "
    "specs, by protocol and rule (DS101 unhandled-event-in-state, "
    "DS201 post-terminal-event). Nonzero means a live code path "
    "diverged from the machine-checked protocol contract",
    ["protocol", "rule"], registry=REGISTRY,
)
# Graceful drain plane (engine/drain.py; docs/fault-tolerance.md
# departure ladder): how a departing worker vacated its live streams.
DRAIN_STATE = Gauge(
    "dynamo_drain_state",
    "Worker drain state (0=serving 1=draining 2=drained)",
    ["worker"], registry=REGISTRY,
)
DRAIN_SEQUENCES = Counter(
    "dynamo_drain_sequences_total",
    "Live sequences vacated during graceful drains, by the ladder rung "
    "that moved them: handoff (KV-state handoff, peer resumes "
    "bit-identically), replay (cooperative replay-migrate, peer "
    "re-prefills), error (deadline expired — honest in-band error)",
    ["outcome"], registry=REGISTRY,
)
DRAIN_DURATION_MS = Gauge(
    "dynamo_drain_duration_ms",
    "Wall time of this worker's last graceful drain, start to "
    "deregistration-ready", ["worker"], registry=REGISTRY,
)
# Durable journal integrity (runtime/events.py): corrupt/torn frames
# the subscriber skipped via CRC resync instead of wedging replay.
JOURNAL_BAD_FRAMES = Counter(
    "dynamo_journal_bad_frames_total",
    "Corrupt journal frames (CRC mismatch / implausible length) skipped "
    "by the skip-to-next-valid-frame resync, per namespace. Each skip "
    "also emits a journal-resync event so routers re-dump affected "
    "workers instead of silently diverging",
    ["namespace"], registry=REGISTRY,
)
# Speculative decoding plane (engine/spec.py + scheduler): where
# speculated tokens are won or wasted. acceptance = accepted/proposed;
# every accepted token is a decode step the engine never ran.
SPEC_PROPOSED = Counter(
    "dynamo_spec_proposed_tokens_total",
    "Draft tokens proposed by the speculative decoder",
    ["worker"], registry=REGISTRY,
)
SPEC_ACCEPTED = Counter(
    "dynamo_spec_accepted_tokens_total",
    "Proposed draft tokens that matched the target sample and committed",
    ["worker"], registry=REGISTRY,
)
SPEC_ACCEPTANCE = Gauge(
    "dynamo_spec_acceptance_rate",
    "Acceptance-rate EMA across a worker's speculating slots",
    ["worker"], registry=REGISTRY,
)
SPEC_K = Gauge(
    "dynamo_spec_k",
    "Draft tokens per slot in the most recent speculative step "
    "(0 = speculation idle or auto-disabled)",
    ["worker"], registry=REGISTRY,
)
# KVBM offload overlap plane (block_manager/offload.py): queue pressure
# and bandwidth-budget behavior of the D2H offload path (docs/kvbm.md).
KVBM_OFFLOAD_DROPPED = Counter(
    "dynamo_kvbm_offload_dropped_total",
    "Blocks dropped from the KVBM offload queue (store burst past "
    "DYNT_OFFLOAD_QUEUE_CAP; oldest first — offload is best-effort)",
    registry=REGISTRY,
)
KVBM_OFFLOAD_QUEUE_DEPTH = Gauge(
    "dynamo_kvbm_offload_queue_depth",
    "Blocks currently queued for KVBM D2H offload",
    registry=REGISTRY,
)
KVBM_OFFLOAD_DEFERRED = Counter(
    "dynamo_kvbm_offload_deferred_seconds_total",
    "Seconds the offload worker spent deferring device gathers to honor "
    "the DYNT_OFFLOAD_BW_FRAC bandwidth budget",
    registry=REGISTRY,
)
# Disaggregated prefill pipeline (engine/worker.py): KV pages streamed
# to the decode pool while the prefill pass was still computing — the
# overlap the chunked handoff buys (docs/disaggregation.md).
DISAGG_STREAMED_PAGES = Counter(
    "dynamo_disagg_streamed_pages_total",
    "KV pages parked for transfer before their prompt finished "
    "prefilling (chunked disagg handoff; serial handoffs count 0 here)",
    ["worker"], registry=REGISTRY,
)
# Device-plane compile counter (engine/model_runner.py jax.monitoring
# listener): every XLA backend compile, labelled by the runner entry
# point that triggered it. Steady-state serving must hold this flat —
# a counter that keeps rising under stable traffic is an unbounded
# retrace (the dynajit DJ1xx hazard class, observed at runtime); the
# retrace-canary tier-1 test pins the bound against the jit-signature
# registry (tools/dynajit/signatures/).
JIT_COMPILES = Counter(
    "dynamo_jit_compiles_total",
    "XLA backend compiles, by the ModelRunner entry point in scope "
    "when the compile fired (unscoped = outside any runner entry)",
    ["fn"], registry=REGISTRY,
)
JIT_COMPILE_SECONDS = Counter(
    "dynamo_jit_compile_seconds_total",
    "Seconds spent in the XLA backend compiles that "
    "dynamo_jit_compiles_total counts, by the same entry point — what "
    "a cold start (or a retrace under traffic) costs in time",
    ["fn"], registry=REGISTRY,
)
# Where a build's seconds went, from the same listener: jax reports a
# compile's tracing, its jaxpr -> MLIR lowering and its backend step on
# the compiling thread; the backend step is a persistent-cache load
# when the cache reported a hit there, a compile otherwise, so
# compile + cache_load sums to dynamo_jit_compile_seconds_total per fn.
JIT_STAGE_SECONDS = Counter(
    "dynamo_jit_stage_seconds_total",
    "Seconds of building compiled programs, by ModelRunner entry point "
    "and stage: trace (a jitted function traced inside another counts "
    "once), lower (jaxpr to MLIR), compile (backend compile), "
    "cache_load (backend step served by the persistent compile cache). "
    "compile + cache_load = dynamo_jit_compile_seconds_total",
    ["fn", "stage"], registry=REGISTRY,
)
COMPILE_CACHE = Counter(
    "dynamo_compile_cache_total",
    "Persistent compile cache lookups as jax reports them, by outcome "
    "(hit | miss); none where no cache directory is set",
    ["outcome"], registry=REGISTRY,
)
# The same key a program is built under counts its launches
# (ModelRunner.program_launches; warm-up launches list a key at 0): a
# key that never grows was built for nothing, and tokens over launches x
# the key's rows x bucket is the padding by shape.
PROGRAM_LAUNCHES = Gauge(
    "dynamo_program_launches",
    "Launches of each compiled program by served traffic since start, "
    "by ModelRunner entry point and program key (entry[static shape], "
    "as /debug/programs lists builds); a key a warm-up pass built and "
    "no request launched reads 0",
    ["worker", "fn", "key"], registry=REGISTRY,
)
PROGRAM_TOKENS = Gauge(
    "dynamo_program_tokens",
    "Useful prompt tokens the launches of dynamo_program_launches "
    "carried (prefill entries only): over launches x the key's rows x "
    "bucket, the share of a program's positions that was not padding",
    ["worker", "fn", "key"], registry=REGISTRY,
)
# What the engine behind a worker actually runs on (engine/worker.py
# start-up report): the device, the kernel path each hot-path slot took
# and whether the native extension loaded — so a reference kernel, the
# Pallas interpreter or a CPU can never serve unnoticed — plus the two
# per-replica readings a multi-replica host is checked by.
ENGINE_INFO = Gauge(
    "dynamo_engine_info",
    "Constant 1; the labels name this worker's devices and the path "
    "(pallas | interpret | xla | einsum | custom) decode attention, "
    "spec attention and the weight matmul resolved to, where the "
    "attention layers of its prefill programs run (prefill_attention: "
    "kernel | xla | mixed over the buckets, the full page group's then "
    "+ the window group's; the geometry decides, program by program; "
    "custom; none; one decode kernel serves both groups, the window's "
    "lower edge an argument), how a block of the model joins its mixers "
    "(block: sequential | parallel, all off one norm and added "
    "together) and through which norm (norm: rms | layer), and whether "
    "dynamo_tpu._native loaded",
    ["worker", "platform", "device_kind", "devices", "decode_attention",
     "spec_attention", "prefill_attention", "weight_matmul", "block",
     "norm", "native"],
    registry=REGISTRY,
)
ENGINE_TOKENS = Gauge(
    "dynamo_engine_tokens",
    "Tokens this worker's engine has processed since start, by kind "
    "(prefill | decode)",
    ["worker", "kind"], registry=REGISTRY,
)
ENGINE_LAUNCHES = Gauge(
    "dynamo_engine_launches",
    "Device programs this worker's engine has launched since start, by "
    "kind: prefill (prefill programs), decode_block (fused decode "
    "blocks), decode_step (device decode steps: a fused block of k "
    "counts k). With dynamo_engine_tokens: tokens per prefill launch "
    "and useful rows per decode step",
    ["worker", "kind"], registry=REGISTRY,
)
ENGINE_POSITIONS = Gauge(
    "dynamo_engine_positions",
    "Positions this worker's prefill programs were launched over since "
    "start, padding included (kind=prefill): rows padded to a power of "
    "two x the bucket of the longest chunk. Growth of "
    "dynamo_engine_tokens{kind=prefill} over its growth is the share "
    "of launched positions that held a prompt token",
    ["worker", "kind"], registry=REGISTRY,
)
EMIT_FRAMES = Gauge(
    "dynamo_engine_emit_frames_total",
    "Token frames this worker's scheduler has emitted since start: one "
    "a sequence an emitting section (a drained decode block, a spec "
    "step, a first token). Growth of dynamo_engine_tokens{kind=decode} "
    "over its growth is the tokens a frame",
    ["worker"], registry=REGISTRY,
)
EMIT_HANDOVERS = Gauge(
    "dynamo_engine_emit_handovers_total",
    "Times the scheduler thread has woken the event loop to hand over "
    "what it emitted (one call_soon_threadsafe for everything an "
    "emitting section produced). Growth of "
    "dynamo_engine_emit_frames_total over its growth is the frames a "
    "hand-over",
    ["worker"], registry=REGISTRY,
)
PREFILL_ROW_BLOCKS = Gauge(
    "dynamo_prefill_row_blocks_total",
    "int4 weights: 256-row blocks of launched prefill positions since "
    "start, by state: live (the matmul ran it) | skipped (padding only, "
    "in a launch of 1024-position rows or longer: the matmul is told so "
    "and does no work for it)",
    ["worker", "state"], registry=REGISTRY,
)
PREFILL_ATTN_LAUNCHES = Gauge(
    "dynamo_prefill_attn_launches_total",
    "Prefill launches since start by the path their attention layers "
    "took: kernel (the blocked Pallas prefill kernel over the paged "
    "pool, in every page group; a latent stack's over its latent rows) "
    "| xla (gathered pages, a full float32 score tensor)",
    ["worker", "path"], registry=REGISTRY,
)
PREFILL_ATTN_BLOCKS = Gauge(
    "dynamo_prefill_attn_blocks_total",
    "Kernel-path prefill launches: (query block, key chunk) pairs of one "
    "attention layer since start, by state: live (fetched and scored) | "
    "skipped (above the causal diagonal, past the row's keys, below a "
    "window's lower edge, padding: a dense rows x bucket x table grid "
    "holds both), and by page group: full (a full-attention layer over "
    "the sequence's table: every model's but for its window layers) | "
    "window (a window layer over its own group's table, a model with "
    "window layers only) | latent (a latent layer over the single stack "
    "of latent rows, in place of full)",
    ["worker", "state", "group"], registry=REGISTRY,
)
LATENT_DECODE_TOKENS = Gauge(
    "dynamo_latent_decode_tokens_total",
    "Model with latent attention: cached positions its decode kernel "
    "(paged_decode_attention_latent) has been asked to read since start: "
    "over decode steps, the active rows' history lengths x latent "
    "layers. Times the bytes of a cached row it is what the kernel's "
    "roofline reads",
    ["worker"], registry=REGISTRY,
)
LATENT_PREFILL_EXPAND_TOKENS = Gauge(
    "dynamo_latent_prefill_expand_tokens_total",
    "Model with latent attention: cached positions whose keys and "
    "values prefill launches have rebuilt from their latents since "
    "start (the XLA form: every row's context up to its chunk's end, "
    "once a launch; the kernel: a key chunk for every query block that "
    "sees it; x latent layers). Over the growth of "
    "dynamo_engine_tokens{kind=prefill} x "
    "latent layers it is what chunked prefill that does not absorb "
    "pays again: 1 for a prompt prefilled in one launch",
    ["worker"], registry=REGISTRY,
)
KV_PAGE_LAYER_READS = Gauge(
    "dynamo_kv_page_layer_reads_total",
    "Model with layers that read pages they do not own (cross-attention "
    "onto another layer's keys and values): active rows x layers that "
    "read a page group's pages, summed over decode steps since start, "
    "by: owner (the layer wrote the pages it reads: the full and window "
    "groups' cache layers) | shared (the layer reads another layer's "
    "pages and caches nothing). Times a row's live tokens and the bytes "
    "of a cached token a layer, owner + shared is what a step's "
    "attention kernels move; owner alone what the pools hold",
    ["worker", "by"], registry=REGISTRY,
)
PREFILL_CROSS_DECODER_ROWS = Gauge(
    "dynamo_prefill_cross_decoder_rows_total",
    "Model whose tail of layers caches nothing and carries nothing in "
    "time (gated memory units, cross-attention): rows of prefill "
    "launches since start, on each of which the tail ran at ONE "
    "position, by chunk: last (the row's chunk ended its prompt: the "
    "logits are read) | earlier (they are not: what running the tail "
    "for every row of every launch, one program a launch shape, "
    "spends). earlier over both is the share wasted",
    ["worker", "chunk"], registry=REGISTRY,
)
KV_RESERVED_PAGE_MS = Gauge(
    "dynamo_kv_reserved_page_ms",
    "Sum over committed steps of (pages allocated to sequences that "
    "hold a slot, prefix-cache residue left out) x the step's wall ms. "
    "Over the growth of dynamo_step_part_ms_total{part=wall} it is the "
    "time-weighted mean of pages reserved by live sequences",
    ["worker"], registry=REGISTRY,
)
KV_WINDOW_RESERVED_PAGE_MS = Gauge(
    "dynamo_kv_window_reserved_page_ms",
    "Model with window and full attention layers: the twin of "
    "dynamo_kv_reserved_page_ms (which keeps counting the full page "
    "group) for the window group: pages of the second pool held by "
    "sequences in a slot x the step's wall ms. Over the growth of "
    "dynamo_step_part_ms_total{part=wall} and --window-pages it is the "
    "share of the window group that is held",
    ["worker"], registry=REGISTRY,
)
KV_WINDOW_PAGES_FREED = Gauge(
    "dynamo_kv_window_pages_freed_total",
    "Window page group: pages returned to the pool because every window "
    "layer's reach had passed them while their sequence lived (not at "
    "release), by the phase whose launch moved the window",
    ["worker", "phase"], registry=REGISTRY,
)
KV_WINDOW_EDGE_TOKENS = Gauge(
    "dynamo_kv_window_edge_tokens_total",
    "Window page group: positions by which sequences' windows' lower "
    "edges moved, by phase. A page goes back for every --page-size of "
    "them when the allocator is sound "
    "(dynamo_kv_window_pages_freed_total over this)",
    ["worker", "phase"], registry=REGISTRY,
)
KV_WINDOW_ALLOC_FAIL = Gauge(
    "dynamo_kv_window_alloc_fail_total",
    "Window page group: reservations (admission) and prefill-chunk "
    "allocations the pool could not give; the sequence waited",
    ["worker"], registry=REGISTRY,
)
SSM_STATE_SLOT_MS = Gauge(
    "dynamo_ssm_state_slot_ms",
    "Model with recurrent state: sum over committed steps of (scheduler "
    "slots held, each with one fixed-size state: a Mamba-2 or Mamba-1 "
    "layer's conv carry and SSM state, a gated short-convolution "
    "layer's conv carry alone) x the step's wall ms. Over the growth of "
    "dynamo_step_part_ms_total{part=wall} and --max-batch it is the "
    "share of the state cache that is live",
    ["worker"], registry=REGISTRY,
)
SSM_PREFILL_POSITIONS = Gauge(
    "dynamo_ssm_prefill_positions_total",
    "Model with recurrent state: valid positions x state layers (Mamba-2, "
    "Mamba-1 and short-convolution alike) that prefill launches have "
    "carried a "
    "state over since start, by carry: fresh (the row began at position "
    "0, from zero state) | continued (the row took up the state its "
    "slot kept from the launch before). continued over both is the "
    "share that ran on a carried state",
    ["worker", "carry"], registry=REGISTRY,
)
SSM_PREFILL_LAUNCH_ROWS = Gauge(
    "dynamo_ssm_prefill_launch_rows_total",
    "Model with recurrent state: rows of prefill launches since start, "
    "by carry (as dynamo_ssm_prefill_positions_total). Both over the "
    "fresh rows is the launches a prompt took",
    ["worker", "carry"], registry=REGISTRY,
)
SSM_SCAN_LAUNCHES = Gauge(
    "dynamo_ssm_scan_launches_total",
    "Model with recurrent state: prefill launches since start by the "
    "path their Mamba layers' chunked scan took: kernel (the Pallas "
    "kernel ssm_chunk_scan, x and y in the projection's own layout) | "
    "xla (the XLA form: [chunk, chunk] products and relaid copies in "
    "HBM; a Mamba-1 layer's selective scan, a loop over positions, is "
    "always this)",
    ["worker", "path"], registry=REGISTRY,
)
MOE_EXPERT_TOKENS = Gauge(
    "dynamo_moe_expert_tokens_total",
    "Dropless expert layer: token-slots each held expert has computed "
    "since start, summed over the expert layers (expert = its PUBLISHED "
    "index; counted on the device, folded in when a launch's results "
    "land). Busiest over mean is the load imbalance",
    ["worker", "expert"], registry=REGISTRY,
)
MOE_DROPPED_SLOTS = Gauge(
    "dynamo_moe_dropped_slots_total",
    "Dropless expert layer: assignments to a held expert that fell "
    "outside the sorted buffer and were not computed. 0 when sound",
    ["worker"], registry=REGISTRY,
)
MOE_EXPERTS_TOUCHED = Gauge(
    "dynamo_moe_experts_touched_total",
    "Dropless expert layer: held experts that at least one token was "
    "routed to, summed over expert-layer calls (phase = prefill | decode: "
    "a prefill launch of thousands of tokens touches every expert, a "
    "decode step of a token a row few). Over "
    "dynamo_moe_expert_layer_calls_total it is the experts whose weights "
    "a call had to read",
    ["worker", "phase"], registry=REGISTRY,
)
MOE_EXPERT_CALLS = Gauge(
    "dynamo_moe_expert_layer_calls_total",
    "Dropless expert layer: calls of the layer (one for each expert "
    "layer of each launch or fused decode step) since start",
    ["worker", "phase"], registry=REGISTRY,
)
DEVICE_HBM_BYTES = Gauge(
    "dynamo_device_hbm_bytes",
    "Device memory of each chip in this worker's mesh as the backend "
    "reports it (in_use | peak | limit); absent where it reports none",
    ["worker", "device", "kind"], registry=REGISTRY,
)
# Session tier (dynamo_tpu/session/): prompt-cache pins and
# session-affinity routing at planet scale — the gauges prove the store
# stays bounded under millions of sessions, the counters show whether
# cached turns actually land on their resident worker
# (docs/prompt-caching.md).
SESSION_ACTIVE = Gauge(
    "dynamo_session_active",
    "Live session-affinity entries in the SessionStore (all shards), "
    "per served model",
    ["model"], registry=REGISTRY,
)
SESSION_EVICTED = Counter(
    "dynamo_session_evicted_total",
    "Session entries dropped, by cause: ttl (idle expiry), cap (shard "
    "at budget — LRU victim), rejected (TinyLFU doorkeeper refused "
    "admission at the cap)",
    ["cause"], registry=REGISTRY,
)
SESSION_AFFINITY = Counter(
    "dynamo_session_affinity_total",
    "Session-affinity routing outcomes: hit (routed to the resident "
    "worker), miss (resident worker lost the selection or left), "
    "none (first turn — no residency yet)",
    ["outcome"], registry=REGISTRY,
)
PIN_LEASES = Gauge(
    "dynamo_pin_leases_active",
    "Live prompt-cache pin leases in the PinLedger, per served model",
    ["model"], registry=REGISTRY,
)
PIN_BLOCKS = Gauge(
    "dynamo_pin_blocks_active",
    "Distinct blocks currently protected by at least one pin lease, "
    "per served model",
    ["model"], registry=REGISTRY,
)
PIN_OPS = Counter(
    "dynamo_pin_ops_total",
    "Pin-ledger operations: pin (new lease), refresh (idempotent "
    "re-pin extended an existing lease), unpin, expire (lease died at "
    "TTL), refuse (DYNT_PIN_MAX_BLOCKS cap)",
    ["op"], registry=REGISTRY,
)
SESSION_EVENT_DUPLICATES = Counter(
    "dynamo_session_event_duplicates_total",
    "Peer session pin/route/touch events dropped by the bounded "
    "per-origin dedupe window (at-least-once reconciliation delivery "
    "replaying a frame already applied) — duplicates are expected "
    "under redelivery, never an error",
    registry=REGISTRY,
)
# Federation plane (dynamo_tpu/federation/, docs/federation.md): one
# logical service over N cells — residency-first global routing with
# pressure spill, cross-cell journal reconciliation with a measured lag
# contract, and the evacuation/cell-loss ladder.
FEDERATION_SPILL = Counter(
    "dynamo_federation_spill_total",
    "Sessions routed away from their resident (or home-preferred) cell: "
    "pressure (home past DYNT_FED_SPILL_PRESSURE and a neighbor wins "
    "the cost model), evacuating (home draining onto neighbors), "
    "lost (home failed — rerouted after residency was cleared)",
    ["from", "to", "reason"], registry=REGISTRY,
)
FEDERATION_LAG_SECONDS = Gauge(
    "dynamo_federation_lag_seconds",
    "Measured cross-cell reconciliation lag: age (emit wall-clock to "
    "apply wall-clock) of the most recently applied session-event "
    "frame on the from->to stream. Sustained values past "
    "DYNT_FED_MAX_LAG_SECS trip the resync rung",
    ["from", "to"], registry=REGISTRY,
)
FEDERATION_RESIDENCY = Counter(
    "dynamo_federation_residency_total",
    "Residency-first global routing outcomes: hit (returning session "
    "landed on its resident cell), miss (resident cell refused — "
    "pressured, evacuating, or lost), none (first turn — no residency "
    "learned yet)",
    ["outcome"], registry=REGISTRY,
)
FEDERATION_CELL_STATE = Gauge(
    "dynamo_federation_cell_state",
    "Cell lifecycle state in the federation directory: 0=serving, "
    "1=evacuating, 2=evacuated, 3=lost (heartbeat expired)",
    ["cell"], registry=REGISTRY,
)
FEDERATION_RESYNCS = Counter(
    "dynamo_federation_resyncs_total",
    "Cross-cell reconciliation resyncs: the from->to stream's measured "
    "lag exceeded DYNT_FED_MAX_LAG_SECS, so the destination replaced "
    "its view from a full source snapshot instead of replaying the "
    "backlog event-by-event",
    ["from", "to"], registry=REGISTRY,
)
FEDERATION_EVAC_SESSIONS = Counter(
    "dynamo_federation_evacuated_sessions_total",
    "Sessions moved off a cell by the evacuation ladder, by rung: "
    "handoff (KV handoff to a mesh-reachable neighbor — resident "
    "state moves, no re-prefill), replay (cooperative replay on the "
    "new home), error (deadline expired — honest in-band error)",
    ["outcome"], registry=REGISTRY,
)
# Device-time attribution plane (perf/steptrace.py, "dynaprof"): every
# scheduler step decomposed into host vs device burn, the per-request
# device-time TTFT, and the live roofline comparison against the
# analytical model (profiler/timing_model.py) — the metrics that say
# whether a step's time was the host's or the device's
# (docs/observability.md).
STEP_DEVICE_MS = Histogram(
    "dynamo_step_device_ms",
    "Per-step device window (dispatch submitted -> drain complete) in "
    "ms, by engine phase (decode / prefill / spec)",
    ["phase"], registry=REGISTRY,
    buckets=(0.05, 0.2, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
             500.0, 2000.0),
)
STEP_HOST_MS = Histogram(
    "dynamo_step_host_ms",
    "Per-step host residual (wall - device window) in ms, labelled by "
    "the step's dominant device phase ('host' = no device work)",
    ["phase"], registry=REGISTRY,
    buckets=(0.05, 0.2, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
             500.0, 2000.0),
)
STEP_PART_MS = Counter(
    "dynamo_step_part_ms_total",
    "The committed steps' wall time in ms, summed (part=wall), and its "
    "measured parts: prep (step start -> first dispatch submit), "
    "dispatch (host time inside runner submit calls), drain_wait (the "
    "blocked readback slice of the device window), emit (committing "
    "drained tokens, closing their frames and handing them to the "
    "event loop). A part's share of a window is its growth over that "
    "of wall",
    ["part"], registry=REGISTRY,
)
TTFT_DEVICE_MS = Histogram(
    "dynamo_ttft_device_ms",
    "Device-stream burn (ms) of the prefill phase behind each first "
    "token — the device-time TTFT next to the host wall-clock "
    "dynamo_time_to_first_token_seconds",
    ["model"], registry=REGISTRY,
    buckets=(1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 200.0, 400.0,
             800.0, 1600.0, 3200.0, 6400.0, 12800.0),
)
MFU_GAUGE = Gauge(
    "dynamo_mfu",
    "Achieved fraction of peak matmul FLOPs over the last metrics "
    "interval (2 * params * tokens / device-time * peak), from the "
    "live step decomposition and the model geometry",
    ["worker"], registry=REGISTRY,
)
ROOFLINE_FRACTION = Gauge(
    "dynamo_roofline_fraction",
    "Ideal device time at the analytical roofline "
    "(profiler/timing_model.py) for the interval's executed steps, "
    "divided by the measured device time — 1.0 means the engine runs "
    "at the hardware ceiling",
    ["worker"], registry=REGISTRY,
)
HOST_BOUND = Gauge(
    "dynamo_host_bound",
    "Host-bound verdict: 1 once the per-step host residual has "
    "exceeded the device window for 8 consecutive steps (scaling "
    "chips will not move this pool's latency), else 0",
    ["worker"], registry=REGISTRY,
)
# Fast-start arrival plane (docs/elasticity.md): the cold-start ladder
# a joining worker walks (fetch -> load -> compile -> register ->
# first_token), and the striped peer weight stream that makes the fetch
# rung seconds-scale. The planner reads the measured total as scale-up
# lead time — a decision made now yields capacity lead-time later.
COLDSTART_PHASE_SECONDS = Gauge(
    "dynamo_coldstart_phase_seconds",
    "Seconds this worker's most recent cold start spent in each arrival-"
    "ladder phase (boot / fetch / load / compile / register / "
    "first_token; boot is process start to the engine build's start)",
    ["worker", "phase"], registry=REGISTRY,
)
COLDSTART_TOTAL_SECONDS = Gauge(
    "dynamo_coldstart_total_seconds",
    "Wall seconds of this worker's most recent cold start, process "
    "start to first served token — should sit inside "
    "DYNT_COLDSTART_BUDGET_SECS",
    ["worker"], registry=REGISTRY,
)
COLDSTART_ARRIVALS = Counter(
    "dynamo_coldstart_arrivals_total",
    "Completed cold starts, by the weight source the arrival ladder "
    "resolved (peer_striped / peer / service / object_store / "
    "checkpoint / init / mock)",
    ["source"], registry=REGISTRY,
)
COLDSTART_LEAD_SECONDS = Gauge(
    "dynamo_coldstart_lead_seconds",
    "Cold-start lead time the planner used in its most recent scale-up "
    "decision (the measured arrival-ladder total it projects demand "
    "ahead by)",
    registry=REGISTRY,
)
WEIGHT_STREAM_CHUNKS = Counter(
    "dynamo_weight_stream_chunks_total",
    "Striped weight-stream chunks, by outcome: served (donor side), "
    "verified (puller digest ok), digest_mismatch (corrupt chunk "
    "rejected — re-fetched from another donor, never served), "
    "restriped (re-assigned after a donor died mid-stream)",
    ["outcome"], registry=REGISTRY,
)
WEIGHT_STREAM_DEFERRED = Counter(
    "dynamo_weight_stream_deferred_seconds_total",
    "Seconds weight-stream donors spent deferring param gathers to "
    "honor the DYNT_WEIGHT_STREAM_BW_FRAC bandwidth budget (the PR-8 "
    "offload pacing, applied to the arrival plane)",
    registry=REGISTRY,
)
# OTLP exporter health (runtime/otel.py): spans that reached the
# collector vs spans lost to a full buffer or a failed export.
OTEL_SPANS_EXPORTED = Counter(
    "dynamo_otel_spans_exported_total",
    "Spans successfully exported to the OTLP collector",
    registry=REGISTRY,
)
OTEL_SPANS_DROPPED = Counter(
    "dynamo_otel_spans_dropped_total",
    "Spans dropped before export (buffer_full | export_error)",
    ["reason"], registry=REGISTRY,
)
# Fleet observatory (dynamo_tpu/observatory/; docs/observability.md
# fleet section): per-process families scraped from every discovered
# /metrics endpoint and folded into one fleet-level view, plus the
# alerting and capture planes that act on it.
FLEET_GOODPUT_RATIO = Gauge(
    "dynamo_fleet_goodput_ratio",
    "Fleet-wide SLO goodput: sum(dynamo_slo_good_total) / "
    "sum(dynamo_slo_requests_total) across every scraped process "
    "(cumulative; the burn-rate rules use windowed rates instead)",
    registry=REGISTRY,
)
FLEET_TTFT_SECONDS = Gauge(
    "dynamo_fleet_ttft_seconds",
    "Fleet TTFT quantiles merged from every process's "
    "dynamo_time_to_first_token_seconds buckets (bucket-wise sum, then "
    "interpolated), by quantile (p50/p95/p99)",
    ["quantile"], registry=REGISTRY,
)
FLEET_ITL_SECONDS = Gauge(
    "dynamo_fleet_itl_seconds",
    "Fleet inter-token-latency quantiles merged from every process's "
    "dynamo_inter_token_latency_seconds buckets, by quantile",
    ["quantile"], registry=REGISTRY,
)
FLEET_POOL_MFU = Gauge(
    "dynamo_fleet_pool_mfu",
    "Mean dynamo_mfu across the scraped workers of a pool — the "
    "per-pool utilization pane the planner and humans share",
    ["pool"], registry=REGISTRY,
)
FLEET_POOL_TTFT_P95 = Gauge(
    "dynamo_fleet_pool_ttft_p95_seconds",
    "Per-pool TTFT p95 merged from that pool's workers' buckets — the "
    "attribution signal a firing perf alert names its pool from",
    ["pool"], registry=REGISTRY,
)
FLEET_TARGETS = Gauge(
    "dynamo_fleet_targets",
    "Scrape targets the fleet collector currently tracks, by health "
    "(ok / broken — broken means the target's scrape breaker is open)",
    ["health"], registry=REGISTRY,
)
FLEET_SCRAPES = Counter(
    "dynamo_fleet_scrapes_total",
    "Collector scrape attempts, by outcome: ok, error (fetch raised "
    "or timed out), skipped (circuit breaker open — target gets the "
    "cooldown, not a hammering)",
    ["outcome"], registry=REGISTRY,
)
ALERT_ACTIVE = Gauge(
    "dynamo_alert_active",
    "1 while the alert rule is firing, 0 otherwise — the pane planners "
    "and pagers watch, by rule and severity",
    ["rule", "severity"], registry=REGISTRY,
)
ALERTS_TOTAL = Counter(
    "dynamo_alerts_total",
    "Alert lifecycle transitions, by rule and transition "
    "(firing / resolved)",
    ["rule", "transition"], registry=REGISTRY,
)
OBSERVATORY_BUNDLES = Counter(
    "dynamo_observatory_bundles_total",
    "Anomaly-triggered capture bundles, by outcome: written, "
    "rate_limited (rule inside its capture cooldown), disabled "
    "(DYNT_OBSERVATORY_DIR unset), error (assembly failed — alert "
    "still fires, the artifact is best-effort)",
    ["outcome"], registry=REGISTRY,
)
OBSERVATORY_SPOOL_BYTES = Gauge(
    "dynamo_observatory_spool_bytes",
    "Bytes currently held by the capture-bundle spool under "
    "DYNT_OBSERVATORY_DIR (bounded by DYNT_OBSERVATORY_MAX_MB)",
    registry=REGISTRY,
)
METRIC_LABEL_OVERFLOW = Counter(
    "dynamo_metric_label_overflow_total",
    "Label values folded into the 'other' overflow bucket by the "
    "bounded label registry (runtime/metric_labels.py), by namespace. "
    "A namespace growing here means DYNT_METRIC_MAX_LABELS is below "
    "this fleet's real cardinality",
    ["namespace"], registry=REGISTRY,
)


def render() -> bytes:
    return generate_latest(REGISTRY)


def render_openmetrics() -> bytes:
    """OpenMetrics exposition of the same registry — the only format that
    carries exemplars, so the TTFT/ITL observations can link back to the
    trace_id that produced them (served on Accept negotiation)."""
    return _generate_openmetrics(REGISTRY)


class EndpointMetrics:
    """Per-endpoint recording helper bound to hierarchy labels."""

    def __init__(self, namespace: str, component: str, endpoint: str) -> None:
        self._labels = dict(namespace=namespace, component=component, endpoint=endpoint)

    def observe_request(self, start_monotonic: float, status: str) -> None:
        REQUESTS_TOTAL.labels(status=status, **self._labels).inc()
        REQUEST_DURATION.labels(**self._labels).observe(
            max(0.0, time.monotonic() - start_monotonic)
        )

    def inflight(self) -> Gauge:
        return INFLIGHT.labels(**self._labels)
