"""Env-first configuration registry.

The reference uses a canonical `DYN_*` env-var namespace registered in one
place (ref: lib/runtime/src/config/environment_names.rs) layered with TOML via
figment (ref: lib/runtime/src/config.rs). We keep the same design: every knob
has a canonical `DYNT_*` env name declared here, with typed accessors and an
optional YAML overlay, so components never read `os.environ` ad hoc.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable

_TRUTHY = {"1", "true", "yes", "on"}
_FALSY = {"0", "false", "no", "off", ""}


def is_truthy(val: str) -> bool:
    """Lenient bool parsing (ref: lib/config/src/lib.rs:20 `is_truthy`)."""
    return val.strip().lower() in _TRUTHY


@dataclasses.dataclass(frozen=True)
class EnvVar:
    name: str
    default: Any
    parse: Callable[[str], Any]
    doc: str


_REGISTRY: dict[str, EnvVar] = {}


def _register(name: str, default: Any, parse: Callable[[str], Any], doc: str) -> EnvVar:
    var = EnvVar(name, default, parse, doc)
    _REGISTRY[name] = var
    return var


def env(name: str) -> Any:
    """Read a registered env var with its declared parser/default."""
    var = _REGISTRY[name]
    raw = os.environ.get(name)
    if raw is None:
        return var.default
    return var.parse(raw)


def registry() -> dict[str, EnvVar]:
    return dict(_REGISTRY)


_str = str
_int = int
_float = float
_bool = is_truthy


# --- canonical knob registry (DYNT_* namespace) ------------------------------
# Discovery plane
_register("DYNT_DISCOVERY_BACKEND", "file", _str,
          "Discovery backend: mem | file | etcd (ref: DYN_DISCOVERY_BACKEND)")
_register("DYNT_DISCOVERY_PATH", "/tmp/dynamo_tpu_discovery", _str,
          "Root dir for the file discovery backend")
_register("DYNT_ETCD_ENDPOINTS", "http://127.0.0.1:2379", _str,
          "Comma-separated etcd endpoints")
_register("DYNT_LEASE_TTL_SECS", 10.0, _float,
          "Discovery lease TTL; dead workers deregister after this "
          "(ref: docs/design-docs/discovery-plane.md, 10s default)")

# Request plane
_register("DYNT_REQUEST_PLANE", "tcp", _str,
          "Request-plane transport: tcp (default) | http | mem "
          "(ref: DYN_REQUEST_PLANE tcp/http2/nats); addresses carry their "
          "scheme, so mixed-transport clusters interoperate")
_register("DYNT_TCP_HOST", "0.0.0.0", _str, "Request-plane TCP bind host")
_register("DYNT_TCP_ADVERTISE_HOST", "127.0.0.1", _str,
          "Host advertised to peers for request-plane connections")
_register("DYNT_TCP_PORT", 0, _int, "Request-plane TCP port (0 = ephemeral)")
_register("DYNT_REQUEST_TIMEOUT_SECS", 600.0, _float,
          "Per-request end-to-end timeout on the request plane")
_register("DYNT_CONNECT_TIMEOUT_SECS", 5.0, _float,
          "TCP connect timeout for request-plane clients")
_register("DYNT_STREAM_IDLE_TIMEOUT_SECS", 120.0, _float,
          "Max gap between response frames on a streaming request before "
          "the client declares the worker black-holed (network partition/"
          "SIGSTOP: the connection stays open but nothing flows). Also "
          "bounds the wait for the FIRST frame when no first-item "
          "timeout is set, so a fresh request to a black-holed worker "
          "fails over instead of hanging until lease expiry. Fires "
          "asyncio.TimeoutError -> the router fault-marks the instance "
          "and Migration replays the stream on a peer. Must exceed the "
          "longest legitimate inter-token stall AND the worst-case "
          "admission-queue + prefill latency to first token (a cold "
          "compile). 0 disables")

# Event plane
_register("DYNT_EVENT_PLANE", "zmq", _str,
          "Event-plane transport: zmq (default) | mem | journal (durable "
          "replayable log — the JetStream-mode analog, ref: "
          "kv_router/jetstream.rs)")
_register("DYNT_ZMQ_HOST", "127.0.0.1", _str, "Event-plane ZMQ bind/advertise host")
_register("DYNT_EVENT_JOURNAL_PATH", "/tmp/dynamo_tpu_events", _str,
          "Journal event-plane root directory (shared storage: local disk "
          "single-host, NFS/GCS-fuse across hosts)")
_register("DYNT_EVENT_JOURNAL_MAX_MB", 64, _int,
          "Per-publisher journal size that triggers a snapshot rotation")

# System status server
_register("DYNT_SYSTEM_PORT", 0, _int,
          "System status server port (/health,/live,/metrics); 0 = ephemeral")
_register("DYNT_SYSTEM_ENABLED", True, _bool, "Enable the system status server")

# Logging
_register("DYNT_LOG_LEVEL", "INFO", _str, "Log level")
_register("DYNT_DECODE_PIPELINE", 2, _int,
          "Pipelined decode-block dispatches in flight (>1 overlaps the "
          "host readback of block d with block d+1's compute — the tokens "
          "chain on-device; costs depth*block of page/token budget)")
_register("DYNT_DECODE_BLOCK", 8, _int,
          "Decode steps fused into one compiled call (lax.scan): "
          "amortizes host dispatch per token; fused blocks also run while "
          "prefill work is pending (prefill chunks interleave between "
          "blocks). Tokens stream in blocks of this size; 1 = per-token")
_register("DYNT_Q8_MATMUL", "auto", _str,
          "W8A16 matmul backend for int8 weights: auto (Pallas on TPU, "
          "XLA reference elsewhere) | pallas | xla")
_register("DYNT_Q4_MATMUL", "auto", _str,
          "W4A16 matmul backend for packed-int4 weights: auto (Pallas "
          "on TPU, XLA reference elsewhere) | pallas | xla")
_register("DYNT_SSM", "auto", _str,
          "Mamba-2 state kernels, the decode update (in place over the "
          "per-slot state) and the prefill chunked scan: auto (the "
          "Pallas kernels on TPU, the XLA reference elsewhere) | pallas "
          "| xla")
_register("DYNT_MOE_GMM", "auto", _str,
          "Dropless experts' grouped matmul: auto (the Pallas gmm that "
          "ships with JAX on TPU, lax.ragged_dot elsewhere) | pallas | xla")
_register("DYNT_Q4_GROUP", "256", _str,
          "int4 quantization group (contracted rows per scale/zero "
          "row): 256 (what the benchmark's cells run) | 128 (the "
          "GPTQ/AWQ convention; not measured on the chip)")
_register("DYNT_WEIGHT_SERVICE", "", _str,
          "Unix socket of the weight service (GMS analog): workers "
          "re-attach published weights on restart instead of initializing")
# Fast-start arrival plane (weights/striped.py, weights/objstore.py,
# engine/coldstart.py; docs/elasticity.md)
_register("DYNT_WEIGHT_STRIPE", True, _bool,
          "Striped peer weight pull: a joining worker (weights_from_peer) "
          "stripes the content-addressed chunk manifest across every "
          "live donor in parallel, with digest verification and "
          "resume-after-donor-death. Off falls back to the single-peer "
          "stream")
_register("DYNT_WEIGHT_STRIPE_DONORS", 4, _int,
          "Max donors a striped weight pull fans out across (more donors "
          "= more aggregate fetch bandwidth, but each pays its "
          "DYNT_WEIGHT_STREAM_BW_FRAC duty cycle)")
_register("DYNT_WEIGHT_STREAM_BW_FRAC", 0.5, _float,
          "Donor-side bandwidth budget for weight streaming: the "
          "fraction of wall time a serving donor may spend on param "
          "gathers for a cold peer. Same pacing formula as "
          "DYNT_OFFLOAD_BW_FRAC (defer g*(1/frac - 1) after a gather "
          "costing g), gathers ride the scheduler's dispatch/drain gap, "
          "so the donor's decode ITL does not regress. 1.0 disables "
          "pacing")
_register("DYNT_WEIGHT_STORE", "", _str,
          "Object-store root for the weight-tree fallback (filesystem/"
          "FUSE path or http(s) S3/GCS-shaped endpoint with DYNT_G4_* "
          "auth): a joining worker with no live peer fetches the "
          "content-addressed chunk tree from here; resolved workers "
          "publish to it best-effort off the startup critical path. "
          "Empty disables the leg")
_register("DYNT_COLDSTART_BUDGET_SECS", 60.0, _float,
          "Pinned cold-start-to-first-token budget for a joining worker "
          "(the arrival-side twin of DYNT_DRAIN_DEADLINE_SECS): the "
          "chaos-spot gate asserts measured arrivals stay inside it, "
          "and dynamo_coldstart_total_seconds above it is the "
          "page-worthy signal")
_register("DYNT_SNAPSHOT_MODE", "off", _str,
          "Worker snapshot protocol: off | dump (prepare engine, signal "
          "ready, block for restore before connecting — CRIU analog)")
_register("DYNT_SNAPSHOT_DIR", "/tmp/dynamo_tpu_snapshot", _str,
          "Directory for snapshot ready/restore marker files")
_register("DYNT_AUDIT_SINKS", "", _str,
          "Comma list of audit sinks for the frontend: 'log' and/or "
          "'jsonl:<path>' (ref: lib/llm/src/audit/ sink config)")
_register("DYNT_LOGGING_JSONL", False, _bool,
          "Emit JSONL logs (ref: DYN_LOGGING_JSONL)")

# Engine
_register("DYNT_KV_BLOCK_SIZE", 16, _int,
          "Tokens per KV block (block-hash granularity and paged-KV page size)")
_register("DYNT_COMPILE_CACHE_DIR", "/tmp/dynamo_tpu_jax_cache", _str,
          "Persistent XLA compilation cache dir")
_register("DYNT_COMPILE_CACHE_STORE", "", _str,
          "Object-store root (filesystem path or http(s) endpoint) the "
          "persistent compile cache syncs with: a joining worker pulls "
          "cache entries down before building its engine and pushes new "
          "entries up after warmup, so a warm-cache arrival compiles "
          "nothing before serving (docs/elasticity.md). Empty disables "
          "the sync")
_register("DYNT_COMPILE_CACHE_PREFIX", "compile-cache", _str,
          "Key prefix compile-cache entries live under in the "
          "DYNT_COMPILE_CACHE_STORE object store")
_register("DYNT_PREWARM", True, _bool,
          "Warmup scope for serving workers: on, warmup compiles the "
          "FULL jit-surface-registry-predicted key space (decode + "
          "every prefill bucket + each speculative k) so steady state "
          "compiles nothing; off keeps the minimal decode + smallest-"
          "bucket warmup")
_register("DYNT_ATTENTION", "auto", _str,
          "Attention kernel: auto | pallas | xla (auto = Pallas flash-decode "
          "on single-device TPU, XLA reference path elsewhere)")

# Speculative decoding (engine/spec.py + scheduler;
# docs/speculative-decoding.md)
_register("DYNT_SPEC_ENABLE", False, _bool,
          "Draftless speculative decoding (prompt-lookup n-gram proposals "
          "+ batched verification): up to DYNT_SPEC_MAX_K proposed tokens "
          "per slot are scored in ONE forward pass and the sampler-exact "
          "prefix commits. Output streams are bit-identical to "
          "non-speculative decode; off keeps the decode path untouched")
_register("DYNT_SPEC_MAX_K", 4, _int,
          "Max draft tokens proposed per slot per speculative step (the "
          "verification chunk is k+1 positions; jit compiles one variant "
          "per k, so this is fixed per serving process)")
_register("DYNT_SPEC_MIN_EMA", 0.1, _float,
          "Per-slot acceptance-rate EMA floor: a slot whose EMA falls "
          "below this stops proposing (it still probes occasionally — "
          "acceptance is a property of the text, which changes). 0 never "
          "disables a slot")
_register("DYNT_SPEC_BATCH_CUTOFF", 0, _int,
          "Auto-disable speculation when more than this many slots are "
          "decode-ready: speculation trades FLOPs for latency, and at "
          "high batch the MXU is busy so the verification FLOPs stop "
          "being free. 0 disables the cutoff (speculate at any batch)")

# KVBM offload overlap plane (block_manager/offload.py; docs/kvbm.md)
_register("DYNT_OFFLOAD_BW_FRAC", 0.25, _float,
          "Bandwidth budget for KVBM D2H offload: the fraction of wall "
          "time the offload path may hold the scheduler thread with "
          "device gathers. After a gather that took g seconds in-step, "
          "the next gather is deferred g*(1/frac - 1) seconds, so "
          "G2-active serving stays within budget of G2-idle. 0 disables "
          "throttling (gathers run back-to-back, the pre-overlap "
          "behavior)")
_register("DYNT_OFFLOAD_SUBBATCH", 2, _int,
          "Pages per in-step offload gather sub-batch: each offload "
          "batch is split into sub-batches this size so a single gather "
          "never holds the dispatch/drain gap for long; one sub-batch "
          "bundle sinks to G2 while the next gathers (double buffering)")
_register("DYNT_OFFLOAD_QUEUE_CAP", 4096, _int,
          "Bound on the KVBM offload queue (blocks awaiting D2H). A "
          "store burst past the cap drops the OLDEST queued blocks "
          "(counted by dynamo_kvbm_offload_dropped_total) — offload is "
          "best-effort cache population, never backpressure")

# Disaggregated prefill pipeline (engine/scheduler.py + worker.py +
# llm/prefill_router.py; docs/disaggregation.md)
_register("DYNT_DISAGG_PIPELINE", 1, _int,
          "Chunked streaming handoff for disaggregated prefill: any "
          "non-zero value makes the prefill worker stream "
          "kv_transfer_params after its FIRST chunk and park pages per "
          "chunk, so the decode worker pulls chunk i while chunk i+1 "
          "computes (the pull side drains chunks as fast as they land; "
          "values above 1 are reserved for a future in-flight-chunk "
          "bound). 0 disables streaming — the prefill leg returns "
          "transfer params only after the whole prompt, the serial "
          "pre-overlap behavior")
_register("DYNT_DISAGG_CHUNK", 0, _int,
          "Prefill tokens per streamed chunk for prefill-only sequences "
          "(the disagg handoff granularity). 0 uses the engine's max "
          "prefill chunk; smaller chunks start the KV handoff earlier "
          "and overlap it finer, at more dispatches per prompt")

# Router
_register("DYNT_ROUTER_OVERLAP_WEIGHT", 1.0, _float,
          "KV router cost weight for prefix-overlap blocks "
          "(ref: kv-router scheduling/selector.rs:155)")
_register("DYNT_ROUTER_TEMPERATURE", 0.0, _float,
          "KV router softmax sampling temperature (0 = argmin)")
_register("DYNT_BUSY_THRESHOLD", None, _float,
          "KV-load busy threshold for 503 load shedding; unset disables "
          "shedding (ref: http/service/busy_threshold.rs). The frontend "
          "--busy-threshold flag overrides")
_register("DYNT_ROUTER_QUEUE_POLICY", "fcfs", _str,
          "Router admission-queue ordering: fcfs | lcfs | wspt "
          "(ref: kv-router scheduling/policy.rs)")
_register("DYNT_ROUTER_QUEUE_THRESHOLD", -1.0, _float,
          "Park requests when every worker exceeds this fraction of its "
          "token budget; negative disables queueing "
          "(ref: kv-router scheduling/queue.rs threshold_frac)")
_register("DYNT_MAX_BATCHED_TOKENS", 0, _int,
          "Per-worker token budget for the router admission gate. 0 leaves "
          "the gate effectively unlimited (DEFAULT_MAX_BATCHED_TOKENS) — "
          "set a real budget for queueing to engage "
          "(ref: queue.rs DEFAULT_MAX_BATCHED_TOKENS)")

_register("DYNT_INDEXER_TTL_SECS", 0.0, _float,
          "Radix-index block TTL; 0 disables expiry "
          "(ref: indexer/pruning.rs PruneConfig ttl=120s when enabled)")
_register("DYNT_INDEXER_MAX_TREE_SIZE", 0, _int,
          "Radix-index node budget; above it the oldest blocks prune to "
          "80% of budget (0 = unlimited; ref PruneConfig max_tree_size)")

# Session tier — explicit prompt caching + cache-residency routing
# (dynamo_tpu/session/; docs/prompt-caching.md)
_register("DYNT_SESSION_ENABLE", True, _bool,
          "Session/prompt-cache tier: honor cache_control markers and "
          "session ids on /v1/chat/completions + /v1/messages (pin "
          "leases into KVBM, session-affinity routing). Off makes the "
          "new wire fields inert — requests behave exactly as before")
_register("DYNT_SESSION_TTL_SECS", 900.0, _float,
          "Idle TTL for a session-affinity entry in the SessionStore; "
          "an entry not touched for this long expires (its pin leases "
          "die with it). Bounds memory together with DYNT_SESSION_MAX")
_register("DYNT_SESSION_MAX", 1_000_000, _int,
          "Bound on live session entries per router process, across all "
          "shards. At the cap, admission is frequency-gated (TinyLFU "
          "doorkeeper) and the coldest session in the shard is evicted "
          "— millions of distinct one-shot sessions cannot grow the "
          "store without bound")
_register("DYNT_SESSION_SHARDS", 16, _int,
          "SessionStore shard count (cap is split evenly; sharding "
          "bounds per-eviction scan cost, not thread contention — the "
          "store lives on the event loop)")
_register("DYNT_SESSION_AFFINITY_WEIGHT", 4.0, _float,
          "KV-router logit bonus (in block units) for the worker a live "
          "session last landed on: cached-turn requests prefer the "
          "resident worker unless it is this many blocks more loaded "
          "than the best alternative. 0 disables affinity steering "
          "(pins and radix overlap still apply)")
_register("DYNT_SESSION_EVENTS", True, _bool,
          "Publish session pin/unpin events on the event plane "
          "(topic 'session_pins') so sharded router replicas converge "
          "on the same pin set (journal-event reconciliation)")
_register("DYNT_PIN_TTL_SECS", 300.0, _float,
          "Default lease TTL for a cache_control pinned prefix (a "
          "request-supplied ttl is clamped to at most this). A pinned "
          "prefix cannot be evicted from KVBM G2/G3 mid-lease but "
          "ALWAYS dies at TTL — re-pin (idempotent) to keep it warm")
_register("DYNT_PIN_MAX_BLOCKS", 65536, _int,
          "Bound on concurrently pinned blocks per PinLedger. Pins past "
          "the cap are refused (counted dynamo_pin_ops_total{op=refuse})"
          " — pinning is a cache hint, never a reservation guarantee")
_register("DYNT_INDEXER_ADMISSION", False, _bool,
          "TinyLFU admission/eviction for the router radix prefix index "
          "(block_manager tinylfu lifted into kv_router): insertions at "
          "the DYNT_INDEXER_MAX_TREE_SIZE node cap are frequency-gated "
          "(doorkeeper absorbs one-hit-wonders, a cold chain cannot "
          "flush a hot shared prefix). Forces the Python tree (the "
          "native core has no admission filter yet)")

# G4 object-store auth (block_manager/storage.py HttpObjectStoreClient;
# docs/prompt-caching.md §G4 auth modes)
_register("DYNT_G4_AUTH", "none", _str,
          "Auth mode for the HTTP(S) G4 object-store client: none | "
          "hmac (SigV4-style canonical-string request signing) | "
          "bearer (static token)")
_register("DYNT_G4_HMAC_KEY_ID", "", _str,
          "Access-key id sent in the Authorization Credential for "
          "hmac-signed G4 requests")
_register("DYNT_G4_HMAC_SECRET", "", _str,
          "HMAC-SHA256 signing secret for G4 request signing (prefer "
          "injecting via env from a secret manager; never logged)")
_register("DYNT_G4_BEARER_TOKEN", "", _str,
          "Static bearer token for G4 requests when DYNT_G4_AUTH=bearer")
_register("DYNT_G4_SIG_TTL_SECS", 300.0, _float,
          "Maximum age of a signed G4 request's x-dynt-date before the "
          "server rejects it (replay window; both the client clock-skew "
          "allowance and the stub server's enforcement bound)")

# Tracing + flight recorder (docs/observability.md)
_register("DYNT_OTLP_ENDPOINT", "", _str,
          "OTLP/HTTP collector base URL (e.g. http://localhost:4318); "
          "empty disables span export (ref: logging.rs OTLP init)")
_register("DYNT_OTEL_SERVICE_NAME", "dynamo_tpu", _str,
          "service.name resource attribute on exported spans")
_register("DYNT_CONFORMANCE", False, _bool,
          "Runtime protocol-conformance monitor (runtime/conformance.py): "
          "replay flight-recorder stamps, drain/breaker/coldstart/"
          "transfer/preemption lifecycle events against the dynastate "
          "protocol specs and count violations into "
          "dynamo_protocol_violations_total. Chaos scenarios enable it "
          "and assert zero violations")
_register("DYNT_FLIGHT_RECORDER_SIZE", 256, _int,
          "Completed request timelines the per-process flight recorder "
          "retains (ring buffer behind /debug/requests)")
_register("DYNT_SLOW_TRACE_MS", 0.0, _float,
          "Force-sample slow requests: a request whose end-to-end wall "
          "time meets this threshold has its flight-recorder timeline "
          "dumped to the log at WARNING (0 disables)")
_register("DYNT_DEBUG_ENDPOINTS", False, _bool,
          "Also serve /debug/requests on the tenant-facing OpenAI "
          "frontend port (it leaks cross-request timelines, so it is "
          "opt-in there; the internal status server always serves it)")
# Device-time attribution plane (perf/steptrace.py "dynaprof";
# docs/observability.md §Device-time attribution)
_register("DYNT_PROF_DIR", "/tmp/dynamo_tpu_profiles", _str,
          "Directory /debug/profile captures write jax.profiler traces "
          "into (one timestamped subdirectory per capture; open with "
          "TensorBoard/XProf)")
_register("DYNT_PROF_DEFAULT_MS", 1000, _int,
          "Capture duration for /debug/profile when the request sends "
          "no duration_ms query parameter")
_register("DYNT_PROF_MAX_MS", 30000, _int,
          "Ceiling on a single /debug/profile capture duration — "
          "profiling holds buffers in the serving process, so an "
          "operator typo must not pin it for minutes")
_register("DYNT_SLO_TTFT_MS", 0.0, _float,
          "TTFT target for the dynamo_slo_good_total goodput counter; "
          "0 means no TTFT requirement")
_register("DYNT_SLO_ITL_MS", 0.0, _float,
          "Worst-token ITL target for the dynamo_slo_good_total goodput "
          "counter; 0 means no ITL requirement")

# Deadline-aware admission — overload-control loop (runtime/admission.py;
# degradation ladder + chaos-overload how-to in docs/fault-tolerance.md)
_register("DYNT_ADMISSION_ENABLE", True, _bool,
          "Deadline-aware admission at the frontend, router admission "
          "queue and prefill router: refuse work whose x-dynt-deadline-ms "
          "budget cannot survive the estimated queue wait (503 + honest "
          "Retry-After) instead of FCFS-ing it into a late 504. Only "
          "acts on requests that carry a deadline AND pools with "
          "measured drain evidence — cold pools and empty queues always "
          "admit. Off restores pure FCFS admission")
_register("DYNT_ADMISSION_HALFLIFE_SECS", 5.0, _float,
          "Half-life of the per-pool drain-rate EWMA behind the queue-"
          "wait estimate; shorter reacts faster to stalls, longer "
          "smooths bursty drains")
_register("DYNT_ADMISSION_MARGIN", 1.2, _float,
          "Safety factor on the estimated queue wait when checked "
          "against the remaining deadline budget: refuse when "
          "est_wait * margin > remaining. >1 leaves headroom for the "
          "service time after the queue (a request admitted with "
          "exactly queue-wait budget still 504s mid-prefill)")
_register("DYNT_RETRY_AFTER_MIN_SECS", 1.0, _float,
          "Floor on the Retry-After seconds attached to 503 shed "
          "responses (derived from the estimated queue drain time)")
_register("DYNT_RETRY_AFTER_MAX_SECS", 30.0, _float,
          "Cap on the Retry-After seconds attached to 503 shed "
          "responses; also what a stalled pool (unbounded estimated "
          "wait) advertises")

# Multi-tenant QoS — priority classes, fair-share quotas, preemption
# (docs/multi-tenancy.md; runtime/admission.py TenantLedger +
# engine/scheduler.py preempt-to-KVBM)
_register("DYNT_TENANT_RATE_LIMIT", 0.0, _float,
          "Serving capacity (tokens/s: prompt + max_tokens of admitted "
          "requests) the weighted fair-share quota divides among "
          "tenants. Under contention a tenant over its share is shed "
          "503 reason=quota BEFORE untagged/under-share traffic "
          "degrades. 0 disables quota admission entirely")
_register("DYNT_TENANT_WINDOW_SECS", 10.0, _float,
          "Sliding window of the per-tenant token-rate ledger; shorter "
          "reacts faster to floods, longer tolerates bursts")
_register("DYNT_TENANT_WEIGHTS", "", _str,
          "Per-tenant fair-share weights as 'tenantA=4,tenantB=1'; a "
          "tenant's share is capacity * w / sum(w of active tenants). "
          "Unlisted tenants get DYNT_TENANT_DEFAULT_WEIGHT")
_register("DYNT_TENANT_DEFAULT_WEIGHT", 1.0, _float,
          "Fair-share weight of tenants not named in "
          "DYNT_TENANT_WEIGHTS")
_register("DYNT_PREEMPT_ENABLE", True, _bool,
          "Preempt batch-class decode slots under interactive pressure: "
          "park-to-KVBM (offload the sequence's pages, resume by onload "
          "when pressure clears — committed streams stay bit-identical) "
          "with cooperative preempt-and-migrate as the fallback when no "
          "park store is attached. Off = class-blind slot allocation "
          "(the pre-QoS behavior; priority still orders queues)")
_register("DYNT_PREEMPT_MAX_PARKED", 16, _int,
          "Bound on concurrently parked (preempted) sequences per "
          "engine. Past it, further preemptions take the cooperative "
          "migrate fallback instead of growing host memory unboundedly")
_register("DYNT_PREEMPT_MIGRATION_LIMIT", 3, _int,
          "Bound on COOPERATIVE migrations per request (worker-emitted "
          "finish_reason=migrate: QoS preemption, elastic reshard) — "
          "separate from DYNT_MIGRATION_LIMIT so planned hand-offs "
          "never consume the failure budget that protects against "
          "crash loops; cooperative replays also skip backoff jitter")

# Graceful drain plane — zero-drop worker departures
# (engine/drain.py; departure ladder in docs/fault-tolerance.md)
_register("DYNT_DRAIN_ENABLE", True, _bool,
          "Graceful drain on SIGTERM / POST /drain / faults 'evict': flip "
          "the worker to draining (routers stop selecting it), hand live "
          "decode sequences to peers via KV handoff, and deregister only "
          "when empty or the deadline expires. Off restores the old "
          "behavior — SIGTERM tears down and in-flight streams fall onto "
          "failure migration (a full re-prefill per stream)")
_register("DYNT_DRAIN_DEADLINE_SECS", 20.0, _float,
          "Budget for a graceful drain, end-to-end (sized to fit inside "
          "a ~30s spot/preemptible eviction notice). The degradation "
          "ladder runs inside it: KV-state handoff -> cooperative "
          "replay-migrate -> honest in-band error at expiry; parked "
          "handoff transfers not pulled by the deadline are expired and "
          "their pages released")
_register("DYNT_DRAIN_ANNOUNCE_SETTLE_SECS", 0.25, _float,
          "Pause between announcing `draining` (discovery card + "
          "LoadMetrics) and sweeping live sequences, giving routers one "
          "event tick to stop selecting this worker — a handoff migrate "
          "frame that lands before the flip would re-dispatch straight "
          "back at the vacating worker, bounce, and burn its replay on "
          "the cooperative rung. Comes out of the drain deadline budget")
_register("DYNT_DRAIN_HANDOFF", True, _bool,
          "Live KV-state handoff during drain: eligible decode sequences "
          "park their computed pages with the transfer table and emit a "
          "migrate frame carrying kv_transfer_params + resume state, so "
          "the destination pulls the KV and continues bit-identically "
          "instead of re-prefilling. Off forces every drained sequence "
          "onto the cooperative replay-migrate rung (ablation/debug)")
_register("DYNT_DRAIN_HTTP", True, _bool,
          "Serve POST /drain on the status server. The verb is "
          "unauthenticated and its effect is terminal (a drained worker "
          "never rejoins routing until restarted) — on deployments where "
          "the status port is reachable beyond the operators, disable it "
          "and drain via SIGTERM / the request-plane control verb / the "
          "faults service instead")

# Federation plane — one logical service over N cells
# (dynamo_tpu/federation/; cell model, residency routing, the
# reconciliation lag contract and the evacuation ladder in
# docs/federation.md)
_register("DYNT_FED_SPILL_PRESSURE", 0.85, _float,
          "Cell pressure (capacity-weighted KV usage + queue backlog, "
          "global_planner.PoolState semantics) past which the "
          "federation router stops defending residency and considers "
          "spilling a returning session to a neighbor cell. Below it, "
          "residency always wins — a cached multi-turn session is "
          "cheaper at its resident cell than anywhere else")
_register("DYNT_FED_SHED_SOFT_FRAC", 0.8, _float,
          "Graded-backpressure knee, as a fraction of "
          "DYNT_FED_SPILL_PRESSURE: new sessions are refused with a "
          "probability ramping linearly from 0 at soft (= threshold x "
          "this) to 1 at the hard threshold. Cell load reports are a "
          "heartbeat stale, so a hard open/shut admission gate "
          "oscillates — floods in the stale window, overshoots the "
          "queue, slams shut; the ramp lets admission settle just "
          "under the gate with the queue still empty. Set to >= 1.0 "
          "to disable the ramp and keep only the hard refusal")
_register("DYNT_FED_COLDSTART_DEFAULT_SECS", 30.0, _float,
          "Cold-start cost the spill model charges a neighbor cell "
          "that would have to scale up for the spilled session, used "
          "until the coldstart lead EWMA (engine/coldstart.py, "
          "dynamo_coldstart_lead_seconds) has a measured value — the "
          "honest 'moving you is not free' term that keeps marginal "
          "pressure from bouncing sessions between cells")
_register("DYNT_FED_MAX_LAG_SECS", 5.0, _float,
          "Cross-cell reconciliation lag contract: when a from->to "
          "session-event stream's measured lag (emit wall-clock to "
          "apply wall-clock) exceeds this, the reconciler abandons "
          "event-by-event replay and resyncs the destination from a "
          "full source snapshot (dynamo_federation_resyncs_total)")
_register("DYNT_FED_HEARTBEAT_TIMEOUT_SECS", 10.0, _float,
          "Cell heartbeat expiry: a cell silent this long is declared "
          "LOST by the federation directory — its breaker board is "
          "failed, residency pointing at it is cleared (pins expire "
          "at their own TTL), and its QoS budget is redistributed. "
          "Must exceed the cells' load-publish interval by a "
          "comfortable factor or a slow scrape reads as a dead region")
_register("DYNT_FED_EVAC_DEADLINE_SECS", 30.0, _float,
          "Budget for a graceful cell evacuation, end-to-end: the "
          "fleet-granularity drain ladder (KV handoff where meshes "
          "allow -> cooperative replay -> honest errors) must finish "
          "inside it; sessions still resident at expiry get in-band "
          "errors, never silence")
_register("DYNT_FED_DEDUPE_MAX", 4096, _int,
          "Per-origin cap on the session event consumer's dedupe "
          "window (entries also expire with each event's own absolute "
          "expiry): bounds reconciliation memory under origin churn — "
          "a federation of transient cells must not grow a dedupe set "
          "per origin id forever")
_register("DYNT_FED_HIT_RECOVERY_SECS", 60.0, _float,
          "Pinned budget for residency-hit-rate recovery after a cell "
          "loss: the federation chaos gate asserts the returning-"
          "session hit rate is back above its pre-loss floor within "
          "this many (scenario-clock) seconds of the loss")

# Fault tolerance — resilience plane (runtime/resilience.py; knob
# semantics and the degradation ladder in docs/fault-tolerance.md)
_register("DYNT_DEADLINE_SECS", 600.0, _float,
          "Default end-to-end request deadline the frontend stamps when "
          "the caller sends no x-dynt-deadline-ms header. Propagated as "
          "remaining-ms on every request-plane hop; migration replay, "
          "prefill legs and KV-transfer waits all consume the remainder "
          "instead of fresh flat timeouts. 0 disables deadlines")
_register("DYNT_RETRY_BUDGET_RATIO", 0.2, _float,
          "Retry-budget deposit per completed first attempt: total "
          "retry volume is capped at ~this fraction of live traffic "
          "(Finagle RetryBudget semantics — prevents retry storms)")
_register("DYNT_RETRY_BUDGET_MIN", 3.0, _float,
          "Retry-budget seed tokens so a cold client can still retry "
          "before any traffic has deposited")
_register("DYNT_RETRY_BACKOFF_BASE_MS", 50.0, _float,
          "Decorrelated-jitter backoff floor between retry attempts")
_register("DYNT_RETRY_BACKOFF_CAP_MS", 2000.0, _float,
          "Decorrelated-jitter backoff ceiling between retry attempts")
_register("DYNT_RETRY_MAX_ATTEMPTS", 3, _int,
          "Router retry attempt cap per request (raised to live "
          "instance count + 1 when more candidates exist)")
_register("DYNT_BREAKER_FAILURES", 1, _int,
          "Consecutive transport failures that open an instance's "
          "circuit breaker (1 mirrors the old first-failure down-mark)")
_register("DYNT_BREAKER_RESET_SECS", 5.0, _float,
          "Open->half-open delay: how long an open breaker waits before "
          "admitting its single recovery probe (replaces the old "
          "DOWN_COOLDOWN_SECS full re-admission)")
_register("DYNT_MIGRATION_LIMIT", 3, _int,
          "Max in-flight request migrations across workers (ref: migration.rs)")
_register("DYNT_CANARY_WAIT_SECS", 30.0, _float,
          "Idle time before canary health-check probes (ref: health_check.rs:22)")
_register("DYNT_MULTIHOST_PUBLISH_TIMEOUT_SECS", 600.0, _float,
          "How long the multihost driver waits on a follower's full ack "
          "window before declaring it hung and tearing down loudly. Must "
          "exceed the slowest follower-side cold XLA compile (a follower "
          "acks a step only after executing it)")
_register("DYNT_INTERLEAVE_SEED", 0, _int,
          "Default schedule seed for the deterministic interleaving "
          "harness (runtime/interleave.py): tests that drive "
          "cross-domain races through adversarial thread schedules "
          "derive their switch order from this seed, so a CI failure "
          "replays bit-identically with the same value. Explicit "
          "Interleaver(seed=...) arguments win over the knob")

# --- fleet observatory (dynamo_tpu/observatory/; docs/observability.md) ---
_register("DYNT_OBSERVATORY_DIR", "", _str,
          "On-disk spool for anomaly-triggered capture bundles "
          "(observatory/capture.py). Empty disables bundle writing — "
          "alerts still fire, only the postmortem artifact is skipped")
_register("DYNT_OBSERVATORY_MAX_BUNDLES", 8, _int,
          "Capture-bundle spool count bound: writing bundle N+1 deletes "
          "the oldest bundle first (the spool is an incident ring, not "
          "an archive)")
_register("DYNT_OBSERVATORY_MAX_MB", 64, _int,
          "Capture-bundle spool size bound in MiB across all bundles; "
          "oldest bundles are pruned until the new bundle fits")
_register("DYNT_OBSERVATORY_SCRAPE_INTERVAL_SECS", 5.0, _float,
          "Fleet collector scrape cadence: how often every discovered "
          "worker/frontend/cell /metrics endpoint is pulled and folded "
          "into the dynamo_fleet_* rollup")
_register("DYNT_OBSERVATORY_SCRAPE_TIMEOUT_MS", 2000.0, _float,
          "Per-target scrape deadline (runtime/resilience.py Deadline); "
          "a target that cannot answer inside it counts as a scrape "
          "failure against its circuit breaker")
_register("DYNT_OBSERVATORY_CAPTURE_COOLDOWN_SECS", 300.0, _float,
          "Per-rule capture-bundle rate limit: a rule that keeps firing "
          "assembles at most one bundle per cooldown window, so a "
          "flapping alert cannot churn the spool or hog the process-"
          "global /debug/profile capture lock")
_register("DYNT_OBSERVATORY_ALERT_LOG", 256, _int,
          "Bounded alert-transition log served on /debug/alerts "
          "(newest first; older transitions fall off the ring)")
_register("DYNT_METRIC_MAX_LABELS", 64, _int,
          "Per-namespace cap for the bounded metric-label registry "
          "(runtime/metric_labels.py): the first K distinct values of a "
          "request-derived label (tenant, cell, ...) keep their own "
          "series, everything later folds into the 'other' overflow "
          "bucket so label cardinality cannot grow with user count")
_register("DYNT_LOG_JSON", False, _bool,
          "Emit one-line JSON log records (same formatter as "
          "DYNT_LOGGING_JSONL; either knob enables it) with "
          "request_id/trace_id/cell correlation fields when a request "
          "context is active")


@dataclasses.dataclass
class RuntimeConfig:
    """Resolved runtime configuration (ref: DistributedConfig::from_settings,
    lib/runtime/src/distributed.rs:540)."""

    discovery_backend: str = "file"
    discovery_path: str = "/tmp/dynamo_tpu_discovery"
    etcd_endpoints: str = "http://127.0.0.1:2379"
    lease_ttl_secs: float = 10.0
    request_plane: str = "tcp"
    tcp_host: str = "0.0.0.0"
    tcp_advertise_host: str = "127.0.0.1"
    tcp_port: int = 0
    request_timeout_secs: float = 600.0
    connect_timeout_secs: float = 5.0
    event_plane: str = "zmq"
    zmq_host: str = "127.0.0.1"
    event_journal_path: str = "/tmp/dynamo_tpu_events"
    event_journal_max_mb: int = 64
    system_port: int = 0
    system_enabled: bool = True

    @classmethod
    def from_env(cls, **overrides: Any) -> "RuntimeConfig":
        cfg = cls(
            discovery_backend=env("DYNT_DISCOVERY_BACKEND"),
            discovery_path=env("DYNT_DISCOVERY_PATH"),
            etcd_endpoints=env("DYNT_ETCD_ENDPOINTS"),
            lease_ttl_secs=env("DYNT_LEASE_TTL_SECS"),
            request_plane=env("DYNT_REQUEST_PLANE"),
            tcp_host=env("DYNT_TCP_HOST"),
            tcp_advertise_host=env("DYNT_TCP_ADVERTISE_HOST"),
            tcp_port=env("DYNT_TCP_PORT"),
            request_timeout_secs=env("DYNT_REQUEST_TIMEOUT_SECS"),
            connect_timeout_secs=env("DYNT_CONNECT_TIMEOUT_SECS"),
            event_plane=env("DYNT_EVENT_PLANE"),
            zmq_host=env("DYNT_ZMQ_HOST"),
            event_journal_path=env("DYNT_EVENT_JOURNAL_PATH"),
            event_journal_max_mb=env("DYNT_EVENT_JOURNAL_MAX_MB"),
            system_port=env("DYNT_SYSTEM_PORT"),
            system_enabled=env("DYNT_SYSTEM_ENABLED"),
        )
        for key, val in overrides.items():
            if val is not None:
                setattr(cfg, key, val)
        return cfg
