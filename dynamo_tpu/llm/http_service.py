"""OpenAI-compatible HTTP frontend.

Routes (ref: lib/llm/src/http/service/openai.rs:1811-2191, service_v2.rs,
anthropic.rs:63):
  POST /v1/chat/completions   (SSE streaming + aggregated)
  POST /v1/completions
  POST /v1/embeddings
  POST /v1/messages           (Anthropic Messages API)
  POST /v1/responses          (OpenAI Responses API)
  GET  /v1/models
  GET  /health, /live, /metrics
503 load shedding above a KV-usage busy threshold (ref: busy_threshold.rs);
client-disconnect propagates cancellation into the pipeline (ref:
http/service/disconnect.rs).
"""

from __future__ import annotations

import asyncio
import base64
import json
import math
import time
import uuid
from typing import AsyncIterator, Optional

from aiohttp import web

from ..runtime import metrics as rt_metrics
from ..runtime.admission import AdmissionRefused, check_admission
from ..runtime.config import env
from ..runtime.flight_recorder import get_recorder
from ..runtime.metric_labels import bounded_label
from ..runtime.logging import (current_request_id, current_trace_id,
                               get_logger)
from ..runtime.otel import get_tracer, trace_id_of
from ..runtime.push_router import NoInstancesAvailable
from ..runtime.request_plane import RemoteError
from ..runtime.resilience import Deadline, DeadlineExceeded
from ..runtime.status import (
    debug_requests_response,
    metrics_response,
    profile_response,
)
from ..session.wire import (
    extract_cache_control,
    resolve_anchor_tokens,
    session_id_of,
    strip_cache_control,
)
from .manager import ModelEntry, ModelManager
from .preprocessor import DeltaGenerator, RequestError
from .protocols import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
    new_request_id,
    now_unix,
)

log = get_logger("llm.http")


def _error_body(status: int, message: str, err_type: str = "invalid_request_error") -> dict:
    return {"error": {"message": message, "type": err_type, "code": status}}


_SSE_DONE = "data: [DONE]\n\n"


def _sse(payload: dict, event: str = "") -> str:
    """One server-sent event: the payload's JSON behind its optional name."""
    head = f"event: {event}\n" if event else ""
    return f"{head}data: {json.dumps(payload)}\n\n"


async def _write_events(response: web.StreamResponse,
                        events: list[str]) -> None:
    """What one frame gave, in one write: the k events of a k-token frame
    leave as one send, and a client reads the bytes k writes gave it."""
    if not events:
        return
    rt_metrics.SSE_CHUNKS.inc(len(events))
    rt_metrics.SSE_WRITES.inc()
    await response.write("".join(events).encode())


def _trace_id_of(preprocessed: PreprocessedRequest) -> str:
    """Trace id carried on the request (empty when tracing is off) — the
    exemplar that links a latency observation back to its trace."""
    return trace_id_of(preprocessed.annotations.get("traceparent"))


class _SloObserver:
    """Per-request latency observer shared by the streaming and aggregate
    paths: TTFT/ITL histograms (with OpenMetrics trace_id exemplars), the
    flight-recorder first_token stamp, and the goodput verdict the
    planner consumes (dynamo_slo_good_total / dynamo_slo_requests_total;
    an unset target always passes)."""

    def __init__(self, preprocessed: PreprocessedRequest,
                 ttft_target_ms: float, itl_target_ms: float,
                 wait_estimator=None) -> None:
        self.model = preprocessed.model
        self.request_id = preprocessed.request_id
        # Per-class / per-tenant goodput attribution (the multi-tenant
        # QoS headline, docs/multi-tenancy.md).
        self.priority = preprocessed.priority or "standard"
        self.tenant = preprocessed.tenant or "untagged"
        trace_id = _trace_id_of(preprocessed)
        self.exemplar = {"trace_id": trace_id} if trace_id else None
        self.start = time.monotonic()
        self.first_at: Optional[float] = None
        self.last_at: Optional[float] = None
        self.itl_max = 0.0
        self.ttft_target_ms = ttft_target_ms
        self.itl_target_ms = itl_target_ms
        # Admission-loop drain signal (runtime/admission.py): a first
        # token means one request entered service — drained from the
        # pool's queue — which is the rate the queue-wait estimate
        # divides the published backlog by.
        self.wait_estimator = wait_estimator
        self._finalized = False

    def on_output(self, output: EngineOutput) -> None:
        if not output.token_ids:
            return
        now = time.monotonic()
        if self.first_at is None:
            self.first_at = now
            rt_metrics.TTFT_SECONDS.labels(model=self.model).observe(
                now - self.start, exemplar=self.exemplar)
            get_recorder().stamp(self.request_id, "first_token")
            if self.wait_estimator is not None:
                self.wait_estimator.observe_drained(1)
        elif self.last_at is not None:
            gap = now - self.last_at
            rt_metrics.ITL_SECONDS.labels(model=self.model).observe(
                gap / max(1, len(output.token_ids)), exemplar=self.exemplar)
            # Worst-token verdict uses the RAW gap: tokens inside one
            # chunk arrive together, so the chunk's first token waited
            # the whole gap — averaging would let a long stall hide
            # inside a large chunk and pass the DYNT_SLO_ITL_MS target.
            self.itl_max = max(self.itl_max, gap)
        self.last_at = now

    def finalize_from(self, delta_gen: DeltaGenerator) -> None:
        """Derive the goodput verdict from the terminal generator state:
        good means the stream reached a finish_reason and it wasn't
        "error". Defined once so the streaming and aggregate paths can
        never diverge on what counts as a good request."""
        self.finalize(ok=delta_gen.finish_reason is not None
                      and delta_gen.finish_reason != "error")

    def finalize(self, ok: bool) -> None:
        if self._finalized:
            return
        self._finalized = True
        rt_metrics.SLO_REQUESTS.labels(
            model=self.model, priority=self.priority,
            tenant=bounded_label("tenant", self.tenant)).inc()
        if not ok:
            return
        # An unset target always passes: a clean zero-token completion
        # (first_at None) only fails when a TTFT target is configured —
        # it never produced the first token that target is about.
        if self.ttft_target_ms and (
                self.first_at is None
                or (self.first_at - self.start) * 1e3 > self.ttft_target_ms):
            return
        if self.itl_target_ms and self.itl_max * 1e3 > self.itl_target_ms:
            return
        rt_metrics.SLO_GOOD.labels(
            model=self.model, priority=self.priority,
            tenant=bounded_label("tenant", self.tenant)).inc()


class HttpService:
    def __init__(
        self,
        manager: ModelManager,
        host: str = "0.0.0.0",
        port: int = 8000,
        busy_threshold: Optional[float] = None,
        audit=None,  # Optional[audit.AuditBus]
        recorder=None,  # Optional[audit.Recorder]
        runtime=None,  # Optional[DistributedRuntime]: admin fan-out routes
        slo_ttft_ms: Optional[float] = None,
        slo_itl_ms: Optional[float] = None,
    ) -> None:
        self.manager = manager
        self.host = host
        self.port = port
        self.busy_threshold = busy_threshold
        # Goodput targets for dynamo_slo_good_total (0 = no requirement);
        # the frontend CLI flags override the DYNT_SLO_* env defaults.
        self.slo_ttft_ms = (env("DYNT_SLO_TTFT_MS") if slo_ttft_ms is None
                            else slo_ttft_ms)
        self.slo_itl_ms = (env("DYNT_SLO_ITL_MS") if slo_itl_ms is None
                           else slo_itl_ms)
        # Per-model overrides set at runtime via POST /busy_threshold
        # (ref: busy_threshold.rs); the constructor value is the default.
        self.busy_thresholds: dict[str, float] = {}
        self.audit = audit
        self.recorder = recorder
        self.runtime = runtime
        self._runner: Optional[web.AppRunner] = None

    # -- helpers -----------------------------------------------------------

    def _lookup(self, model: str) -> tuple[ModelEntry, Optional[str]]:
        """Resolve a model OR adapter name to (entry, lora_name). Resolved
        exactly once per request — re-resolving later could silently fall
        back to the base model if the adapter is unloaded concurrently."""
        entry, lora = self.manager.resolve(model)
        if entry is None:
            raise web.HTTPNotFound(
                text=json.dumps(_error_body(
                    404, f"model '{model}' not found", "model_not_found")),
                content_type="application/json",
            )
        return entry, lora

    def _retry_after(self, entry: Optional[ModelEntry]) -> str:
        """Retry-After seconds for 503 shed responses: the estimated
        drain time of the model pool's queue (runtime/admission.py),
        floored/capped by the DYNT_RETRY_AFTER_MIN/MAX_SECS knobs — an
        honest hint instead of the old fixed constant. Integer per
        RFC 9110 (ceil so the client never retries a hair early)."""
        if entry is None:
            return str(max(1, int(env("DYNT_RETRY_AFTER_MIN_SECS"))))
        est = entry.wait_estimator
        secs = est.retry_after_s(est.estimate_wait_ms(extra=1))
        return str(max(1, math.ceil(secs)))

    def _check_busy(self, entry: ModelEntry) -> None:
        """Shed load when every live worker is past the KV busy threshold
        (ref: busy_threshold.rs + KvWorkerMonitor). Uses published
        LoadMetrics usage, which flows in every router mode."""
        threshold = self.busy_thresholds.get(entry.card.name,
                                             self.busy_threshold)
        if threshold is None:
            return
        usages = [
            entry.worker_usage[iid]
            for iid in entry.router.client.instance_ids()
            if iid in entry.worker_usage
        ]
        if usages and min(usages) >= threshold:
            rt_metrics.REQUESTS_SHED.labels(reason="busy").inc()
            raise web.HTTPServiceUnavailable(
                text=json.dumps(_error_body(503, "service busy", "overloaded")),
                content_type="application/json",
                headers={"Retry-After": self._retry_after(entry)},
            )

    def _admit_deadline(self, request: web.Request,
                        entry: Optional[ModelEntry] = None,
                        ) -> Optional[Deadline]:
        """Derive the request's end-to-end Deadline: an upstream-propagated
        x-dynt-deadline-ms header wins; otherwise DYNT_DEADLINE_SECS (0
        disables). A budget already spent on arrival is shed immediately
        with 503 + Retry-After — dispatching it would occupy a worker for
        a client that has already timed out ('The Tail at Scale'
        admission control)."""
        # HTTP headers are case-insensitive; Deadline.from_wire keys are
        # canonical lowercase.
        deadline = Deadline.from_wire(
            {k.lower(): v for k, v in request.headers.items()})
        if deadline is None:
            budget = env("DYNT_DEADLINE_SECS")
            if budget and budget > 0:
                deadline = Deadline(budget)
        if deadline is not None and deadline.expired():
            rt_metrics.REQUESTS_SHED.labels(reason="deadline").inc()
            raise web.HTTPServiceUnavailable(
                text=json.dumps(_error_body(
                    503, "request deadline already spent", "overloaded")),
                content_type="application/json",
                headers={"Retry-After": self._retry_after(entry)},
            )
        return deadline

    @staticmethod
    def _refused_503(exc: AdmissionRefused) -> web.HTTPServiceUnavailable:
        """The ONE AdmissionRefused -> 503 translation (body shape +
        integer Retry-After) every pre-dispatch admission edge raises."""
        return web.HTTPServiceUnavailable(
            text=json.dumps(_error_body(503, str(exc), "overloaded")),
            content_type="application/json",
            headers={"Retry-After": str(max(1, math.ceil(
                exc.retry_after_s)))},
        )

    def _check_queue_admission(self, entry: ModelEntry,
                               deadline: Optional[Deadline],
                               tenant: str = "") -> None:
        """Deadline-aware admission (the shed-early rung of the
        degradation ladder, docs/fault-tolerance.md): refuse a request
        whose budget cannot survive the estimated queue wait of the
        model's pool — BEFORE preprocessing or dispatch burns any work
        on a reply the client will never wait for. The wait is the
        backlog AHEAD of this arrival (extra=0): an empty pool admits
        regardless of how slow the measured drain is."""
        try:
            check_admission(entry.wait_estimator, deadline, tenant=tenant)
        except AdmissionRefused as exc:
            raise self._refused_503(exc)

    @staticmethod
    def _tenant_of(request: web.Request, body: dict) -> str:
        """Tenant identity for shed attribution BEFORE preprocessing —
        same precedence as _fold_qos_headers (body wins over the
        header) and the same bound the preprocessor applies, so queue
        sheds and quota/goodput series always name the same tenant."""
        raw = body.get("tenant") or request.headers.get(
            "x-dynt-tenant-id") or ""
        return str(raw).strip()[:64]

    @staticmethod
    def _fold_qos_headers(request: web.Request, body: dict) -> dict:
        """Multi-tenant QoS wire surface (docs/multi-tenancy.md): the
        x-dynt-priority / x-dynt-tenant-id headers fold into the body
        fields the preprocessor normalizes. Body fields win on conflict
        (the more specific declaration). Shared by every completion-
        shaped endpoint."""
        pr = request.headers.get("x-dynt-priority")
        if pr and not body.get("priority"):
            body["priority"] = pr
        ten = request.headers.get("x-dynt-tenant-id")
        if ten and not body.get("tenant"):
            body["tenant"] = ten
        return body

    def _check_tenant_quota(self, entry: ModelEntry,
                            preprocessed: PreprocessedRequest) -> None:
        """Weighted fair-share admission (runtime/admission.py
        TenantLedger): refuse an over-share tenant under contention
        with 503 + Retry-After BEFORE dispatch. The entry edge — it
        deposits admitted token costs into the shared ledger the
        downstream (router queue / prefill) edges read. Contention =
        the pool's queue-wait estimate is non-zero (work is waiting)."""
        from ..runtime.admission import (
            check_tenant_admission,
            get_tenant_ledger,
        )

        tokens = (len(preprocessed.token_ids)
                  + preprocessed.sampling.max_tokens)
        contended = entry.wait_estimator.estimate_wait_ms() > 0
        try:
            check_tenant_admission(get_tenant_ledger(),
                                   preprocessed.tenant, tokens,
                                   contended=contended, observe=True)
        except AdmissionRefused as exc:
            raise self._refused_503(exc)

    def _session_prepare(self, request: web.Request,
                         body: dict) -> tuple[dict, Optional[str], list]:
        """Session-tier wire surface, shared by chat and messages:
        extract cache_control anchors + the session id, and strip the
        markers so the preprocessor sees a byte-identical unmarked
        request (the unpinned-fallback contract). Returns
        (clean_body, session_id, raw_anchors)."""
        if not env("DYNT_SESSION_ENABLE"):
            return body, None, []
        anchors = extract_cache_control(body)
        sid = session_id_of(body, request.headers)
        if anchors or sid or "cache_control" in body \
                or "session_id" in body:
            body = strip_cache_control(body)
        return body, sid, anchors

    def _session_register(self, entry: ModelEntry, preprocessed,
                          chat_messages, sid: Optional[str],
                          anchors_raw: list) -> None:
        """Resolve anchors to token prefixes, pin them into the ledger,
        and stamp the request — after preprocessing, before dispatch.
        Failures degrade to an unpinned request, never a 5xx: the
        session tier is an accelerator, not a dependency."""
        if entry.session is None or not (anchors_raw or sid):
            return
        try:
            preprocessed.session_id = sid
            anchors = []
            if anchors_raw and not preprocessed.media_hashes:
                # Multimodal prompts skip anchors: image-placeholder
                # splicing breaks the rendered-prefix <-> token-prefix
                # correspondence the resolution relies on.
                anchors = resolve_anchor_tokens(
                    entry.preprocessor, chat_messages, anchors_raw,
                    preprocessed.token_ids)
            preprocessed.cache_anchors = [n for n, _ in anchors]
            if anchors and anchors[-1][1]:
                # Carry the longest anchor's requested TTL to the worker
                # so its KVBM pin honors the client's lease, not the
                # system ceiling.
                preprocessed.cache_ttl = float(anchors[-1][1])
            pinned = entry.session.register_request(preprocessed, anchors)
            if anchors or sid:
                get_recorder().event(
                    preprocessed.request_id, "session",
                    pinned_blocks=len(pinned), anchors=len(anchors),
                    session=bool(sid))
        except Exception:  # noqa: BLE001 — degrade to unpinned
            log.exception("session registration failed for %s",
                          preprocessed.request_id)

    # -- handlers ----------------------------------------------------------

    async def _models(self, _request: web.Request) -> web.Response:
        data = [
            {"id": card.name, "object": "model", "created": 0,
             "owned_by": "dynamo_tpu"}
            for card in self.manager.list_models()
        ]
        data += [
            {"id": name, "object": "model", "created": 0,
             "owned_by": "dynamo_tpu", "parent": base}
            for name, base in self.manager.list_adapters()
        ]
        data += [
            {"id": name, "object": "model", "created": 0,
             "owned_by": "dynamo_tpu"}
            for name in sorted(self.manager.image_pools)
        ]
        return web.json_response({"object": "list", "data": data})

    async def _health(self, _request: web.Request) -> web.Response:
        models = [c.name for c in self.manager.list_models()]
        return web.json_response(
            {"status": "healthy" if models else "no_models", "models": models}
        )

    async def _metrics(self, request: web.Request) -> web.Response:
        return metrics_response(request)

    async def _debug_requests(self, request: web.Request) -> web.Response:
        return debug_requests_response(request)

    async def _debug_profile(self, request: web.Request) -> web.Response:
        return await profile_response(request)

    async def _chat(self, request: web.Request) -> web.StreamResponse:
        return await self._completion_common(request, kind="chat")

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        return await self._completion_common(request, kind="completions")

    async def _completion_common(self, request: web.Request, kind: str) -> web.StreamResponse:
        arrival = time.time()
        try:
            body = await request.json()
        except (ValueError, UnicodeDecodeError):
            return web.json_response(_error_body(400, "invalid JSON body"), status=400)
        model = body.get("model", "")
        entry, lora = self._lookup(model)
        self._check_busy(entry)
        deadline = self._admit_deadline(request, entry)
        self._check_queue_admission(entry, deadline,
                                    tenant=self._tenant_of(request, body))
        sid, anchors_raw = None, []
        if kind == "chat":
            body, sid, anchors_raw = self._session_prepare(request, body)
        body = self._fold_qos_headers(request, body)
        pre_start = time.monotonic()
        try:
            if kind == "chat":
                preprocessed = entry.preprocessor.preprocess_chat(body)
            else:
                preprocessed = entry.preprocessor.preprocess_completions(body)
        except RequestError as exc:
            return web.json_response(_error_body(400, str(exc)), status=400)
        rt_metrics.STAGE_DURATION.labels(stage="preprocess",
                                         model=model).observe(
            time.monotonic() - pre_start)
        # Fair-share quota edge: after preprocessing (the token cost is
        # known), before any dispatch work.
        self._check_tenant_quota(entry, preprocessed)
        preprocessed.lora_name = lora
        preprocessed.deadline = deadline
        # W3C trace-context propagation + span export: the frontend opens a
        # SERVER span (child of any incoming traceparent) and re-injects
        # ITS OWN context into the request annotations, so worker spans
        # parent under it across the request plane (ref: logging.rs OTLP
        # init + Injector/Extractor propagation).
        span = get_tracer().start_span(
            "http.chat" if kind == "chat" else "http.completions",
            parent=request.headers.get("traceparent"),
            kind=2, **{"request.id": preprocessed.request_id,
                       "model": model,
                       "input.tokens": len(preprocessed.token_ids)})
        self._open_http_trace(request, preprocessed, span, received=arrival)
        if kind == "chat":
            # After the timeline opens so the `session` event lands in
            # the flight record; markers resolve against the flattened
            # message list preprocess_chat produced in place.
            self._session_register(entry, preprocessed,
                                   body.get("messages") or [], sid,
                                   anchors_raw)
        # Gateway EPP header contract: an external endpoint picker (e.g.
        # the gateway/ EPP service behind a standard K8s gateway) pins
        # routing via headers — x-worker-instance-id direct-routes the
        # decode/aggregated leg; x-prefill-instance-id the prefill leg
        # (ref: deploy/inference-gateway/epp +
        # lib/llm/src/kv_router/prefill_router/mod.rs:117-120).
        target = request.headers.get("x-worker-instance-id")
        if target:
            preprocessed.annotations["target_instance"] = target
        prefill_target = request.headers.get("x-prefill-instance-id")
        if prefill_target:
            preprocessed.annotations["prefill_instance"] = prefill_target
        current_request_id.set(preprocessed.request_id)
        # Everything from here runs under the span: setup failures export
        # it with ok=False via __exit__ — failing requests are exactly the
        # ones operators need spans for. An exception escaping before the
        # response paths run their own accounting must also close the
        # flight-recorder timeline (no-op when already finished), or the
        # entry sits phantom-inflight until stale eviction.
        return await self._finish_guard(
            preprocessed.request_id,
            self._completion_traced(
                request, entry, preprocessed, span, body, kind, model),
            span=span)

    async def _finish_guard(self, request_id: str, coro, span):
        """Escape guard shared by every completion-shaped endpoint: an
        exception before the stream helpers' own handlers are armed
        (e.g. a disconnect during response.prepare) must still close the
        flight-recorder timeline (no-op when the response path already
        closed it) — a client going away is normal teardown, not an
        error, so the recorder's cancelled status skips the WARNING
        dump. The endpoint's server span is entered here so an escaping
        exception exports it ok=False via __exit__; the response helpers
        end it with the real outcome first (first end() wins)."""
        try:
            with span:
                return await coro
        except (ConnectionResetError, asyncio.CancelledError):
            get_recorder().finish(request_id, "cancelled")
            raise
        except BaseException:
            get_recorder().finish(request_id, "error")
            raise

    def _open_http_trace(self, request: web.Request,
                         preprocessed: PreprocessedRequest, span,
                         received: Optional[float] = None) -> None:
        """Inject the server span's context into the request annotations
        (falling back to the client's header when export is disabled) and
        open the flight-recorder timeline. Shared by every
        completion-shaped endpoint; the span itself is created at the
        call site so the span-name registry sees a literal name.
        `received` backdates the timeline to handler entry so the
        tokenization cost (which precedes the request id) stays visible
        against the deadline budget."""
        tp = span.traceparent or request.headers.get("traceparent")
        if tp:
            preprocessed.annotations["traceparent"] = tp
        if received is not None:
            # The worker closes the `ingress` stage from it (frontend,
            # router, request plane): docs/observability.md.
            preprocessed.annotations["received_at"] = received
        current_trace_id.set(_trace_id_of(preprocessed) or None)
        get_recorder().start(preprocessed.request_id,
                             model=preprocessed.model,
                             trace_id=_trace_id_of(preprocessed),
                             tenant=preprocessed.tenant,
                             received=received)

    async def _completion_traced(
        self, request: web.Request, entry: ModelEntry,
        preprocessed: PreprocessedRequest, span, body: dict, kind: str,
        model: str,
    ) -> web.StreamResponse:
        # Span ownership matches _messages/_responses: _finish_guard holds
        # `with span:` (close-on-escape); the response helpers end it with
        # the real outcome (first end() wins).
        if self.recorder is not None:
            self.recorder.record_request(preprocessed.request_id, kind,
                                         body)
        # Tool parsing activates only when the request declares tools
        # (the reference gates on request.tools the same way);
        # reasoning parsing follows the model card.
        card = entry.preprocessor.card
        delta_gen = DeltaGenerator(
            entry.preprocessor, preprocessed, kind=kind,
            tool_parser=(card.tool_parser if body.get("tools")
                         else None),
            reasoning_parser=card.reasoning_parser,
        )
        stream = bool(body.get("stream", False))
        rt_metrics.INPUT_TOKENS.labels(model=model).observe(
            len(preprocessed.token_ids))
        if stream:
            return await self._stream_response(request, entry,
                                               preprocessed, delta_gen,
                                               body, span)
        return await self._aggregate_response(entry, preprocessed,
                                              delta_gen, span)

    def _count_request(self, model: str, status: str,
                       start: Optional[float] = None, *,
                       preprocessed: Optional[PreprocessedRequest] = None,
                       delta_gen: Optional[DeltaGenerator] = None,
                       kind: str = "", request_id: Optional[str] = None,
                       prompt_tokens: Optional[int] = None) -> None:
        """Frontend request counter + duration — the planner's num_req and
        concurrency signals (ref: http/service/metrics.rs request counts
        feeding the Planner). Also emits the audit record (off hot path:
        emit is a queue put)."""
        labels = dict(namespace="http", component="frontend", endpoint=model)
        rt_metrics.REQUESTS_TOTAL.labels(status=status, **labels).inc()
        if delta_gen is not None:
            delta_gen.count_detok()
        if start is not None:
            rt_metrics.REQUEST_DURATION.labels(**labels).observe(
                max(0.0, time.monotonic() - start))
        rid = (request_id if request_id is not None
               else preprocessed.request_id if preprocessed else None)
        if rid:
            # Close the flight-recorder timeline on EVERY outcome (no-op
            # when a more specific status — deadline_exceeded — already
            # finished it, or when this endpoint never opened one).
            get_recorder().finish(rid, status)
        if self.audit is not None:
            from .audit import AuditRecord

            self.audit.emit(AuditRecord(
                request_id=(request_id if request_id is not None
                            else preprocessed.request_id if preprocessed
                            else ""),
                model=model, kind=kind, status=status,
                lora=(preprocessed.lora_name if preprocessed else None),
                prompt_tokens=(prompt_tokens if prompt_tokens is not None
                               else len(preprocessed.token_ids)
                               if preprocessed else 0),
                completion_tokens=(delta_gen.completion_tokens
                                   if delta_gen else 0),
                finish_reason=(delta_gen.finish_reason if delta_gen else None),
                latency_ms=((time.monotonic() - start) * 1e3 if start else 0.0),
            ))

    async def _consume(
        self, entry: ModelEntry, preprocessed: PreprocessedRequest,
        delta_gen: DeltaGenerator, observe_latency: bool = False,
    ) -> Optional[web.Response]:
        """Drive the engine stream to completion through `delta_gen`.
        Returns an error Response, or None on success. Shared by every
        non-streaming handler so error mapping stays in one place."""
        obs = (_SloObserver(preprocessed, self.slo_ttft_ms, self.slo_itl_ms,
                            wait_estimator=entry.wait_estimator)
               if observe_latency else None)
        cancelled = False
        try:
            async for output in self._generate(entry, preprocessed):
                if obs is not None:
                    obs.on_output(output)
                delta_gen.on_output(output)
                if output.error:
                    return web.json_response(
                        _error_body(502, output.error, "engine_error"),
                        status=502)
        except asyncio.CancelledError:
            # Client abort: don't let it count against the goodput ratio
            # or dump the timeline as an error.
            cancelled = True
            get_recorder().finish(preprocessed.request_id, "cancelled")
            raise
        except NoInstancesAvailable:
            return web.json_response(
                _error_body(503, "no workers available", "overloaded"),
                status=503, headers={"Retry-After": "1"})
        except AdmissionRefused as exc:
            # Deadline-aware refusal from a downstream admission edge
            # (router queue / prefill router): same 503 + honest
            # Retry-After contract as the frontend's own check — the
            # shed was already counted where it was decided.
            get_recorder().finish(preprocessed.request_id, "shed")
            return web.json_response(
                _error_body(503, str(exc), "overloaded"), status=503,
                headers={"Retry-After": str(max(1, math.ceil(
                    exc.retry_after_s)))})
        except DeadlineExceeded as exc:
            rt_metrics.DEADLINE_EXCEEDED.labels(component="frontend").inc()
            get_recorder().finish(preprocessed.request_id,
                                  "deadline_exceeded")
            return web.json_response(
                _error_body(504, str(exc), "deadline_exceeded"), status=504)
        except RemoteError as exc:
            return web.json_response(
                _error_body(502, str(exc), "engine_error"), status=502)
        finally:
            if obs is not None and not cancelled:
                obs.finalize_from(delta_gen)
        return None

    async def _generate(
        self, entry: ModelEntry, preprocessed: PreprocessedRequest
    ) -> AsyncIterator[EngineOutput]:
        rec = self.recorder
        async for output in entry.engine.generate(preprocessed):
            if rec is not None:
                rec.record_output(preprocessed.request_id, output.to_wire())
                if output.finish_reason is not None:
                    rec.record_end(preprocessed.request_id,
                                   output.finish_reason)
            yield output

    async def _aggregate_response(
        self, entry: ModelEntry, preprocessed: PreprocessedRequest,
        delta_gen: DeltaGenerator, span,
    ) -> web.Response:
        model = preprocessed.model
        start = time.monotonic()
        status = "error"
        try:
            err = await self._consume(entry, preprocessed, delta_gen,
                                      observe_latency=True)
            if err is not None:
                return err
            rt_metrics.OUTPUT_TOKENS.labels(model=model).observe(
                delta_gen.completion_tokens)
            status = "ok"
            return web.json_response(delta_gen.final_response())
        finally:
            # Counts + audit on EVERY outcome (error returns included) so
            # the audit trail never undercounts failures; the server span
            # must export ERROR for error Responses too, not just raises
            # (first end() wins over the enclosing `with span:`).
            span.end(ok=status == "ok")
            self._count_request(model, status, start,
                                preprocessed=preprocessed,
                                delta_gen=delta_gen, kind=delta_gen.kind)

    async def _stream_response(
        self, request: web.Request, entry: ModelEntry,
        preprocessed: PreprocessedRequest, delta_gen: DeltaGenerator,
        body: dict, span,
    ) -> web.StreamResponse:
        model = preprocessed.model
        response = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "X-Request-Id": preprocessed.request_id,
            },
        )
        await response.prepare(request)
        start = time.monotonic()
        obs = _SloObserver(preprocessed, self.slo_ttft_ms, self.slo_itl_ms,
                           wait_estimator=entry.wait_estimator)
        disconnected = False
        include_usage = bool(
            (body.get("stream_options") or {}).get("include_usage", False)
        )
        try:
            async for output in self._generate(entry, preprocessed):
                obs.on_output(output)
                await _write_events(response, [
                    _sse(chunk) for chunk in delta_gen.on_output(output)])
                if delta_gen.finish_reason is not None:
                    break
            tail = []
            if include_usage:
                tail.append(_sse({
                    "id": delta_gen.chunk_id,
                    "object": ("chat.completion.chunk"
                               if delta_gen.kind == "chat"
                               else "text_completion"),
                    "created": delta_gen.created, "model": model,
                    "choices": [], "usage": delta_gen.usage()}))
            await _write_events(response, tail + [_SSE_DONE])
        except NoInstancesAvailable:
            await _write_events(response, [
                _sse(_error_body(503, 'no workers available')), _SSE_DONE])
        except AdmissionRefused as exc:
            # Mid-pipeline refusal after the stream headers went out:
            # surface in-band like every other post-prepare failure.
            get_recorder().finish(preprocessed.request_id, "shed")
            await _write_events(response, [
                _sse(_error_body(503, str(exc), 'overloaded')), _SSE_DONE])
        except DeadlineExceeded as exc:
            rt_metrics.DEADLINE_EXCEEDED.labels(component="frontend").inc()
            get_recorder().finish(preprocessed.request_id,
                                  "deadline_exceeded")
            await _write_events(response, [
                _sse(_error_body(504, str(exc), 'deadline_exceeded')),
                _SSE_DONE])
        except RemoteError as exc:
            # Emit an OpenAI-shaped error event then terminate the stream
            # cleanly so SDK clients see a parseable failure, not a dropped
            # chunked read.
            await _write_events(response, [
                _sse(_error_body(502, str(exc), 'engine_error')), _SSE_DONE])
        except (ConnectionResetError, asyncio.CancelledError):
            # Client went away: stop generating (cancellation propagates to
            # the worker through the request plane). Normal teardown — the
            # timeline closes as cancelled (no WARNING dump) and the
            # request is excluded from the goodput ratio.
            get_recorder().finish(preprocessed.request_id, "cancelled")
            disconnected = True
            log.info("client disconnected: %s", preprocessed.request_id)
            raise
        finally:
            rt_metrics.OUTPUT_TOKENS.labels(model=model).observe(
                delta_gen.completion_tokens)
            # finish_reason "error" is an in-band engine failure (the
            # worker streamed an error output), not a completion.
            status = ("ok" if delta_gen.finish_reason
                      not in (None, "error") else "error")
            # In-band SSE error terminations (deadline, engine error) must
            # export the server span as ERROR even though no exception
            # escapes the `with span:` (mirrors _anthropic_stream).
            span.end(ok=status == "ok" and not disconnected)
            if not disconnected:
                obs.finalize_from(delta_gen)
            self._count_request(model, status, start,
                                preprocessed=preprocessed,
                                delta_gen=delta_gen, kind=delta_gen.kind)
        await response.write_eof()
        return response

    # -- image / video generation (diffusion pools) ------------------------

    async def _diffusion_generate(self, model: str, body: dict,
                                  n_frames: int):
        """Call the model's diffusion pool; returns list of [frames, S, S,
        3] float arrays (one per image) or an error Response."""
        import numpy as np

        pool = self.manager.image_pools.get(model)
        if pool is None or not pool.instances:
            return web.json_response(_error_body(
                404, f"image model '{model}' not found", "model_not_found"),
                status=404)
        try:
            request = {
                "prompt": body.get("prompt", ""),
                "n": int(body.get("n", 1)),
                "steps": int(body.get("steps", 20)),
                "seed": int(body.get("seed", 0)),
                "frames": n_frames,
                # classifier-free guidance (production diffusion
                # sampling): scale > 1 steers away from negative_prompt
                # (or empty conditioning)
                "guidance_scale": float(body.get("guidance_scale", 1.0)),
                "negative_prompt": body.get("negative_prompt"),
            }
        except (TypeError, ValueError):
            return web.json_response(_error_body(
                400, "n/steps/seed/guidance_scale must be numbers"),
                status=400)
        if not request["prompt"]:
            return web.json_response(
                _error_body(400, "'prompt' is required"), status=400)
        images = []
        try:
            async for frame in pool.router.generate(request):
                if frame.get("error"):
                    return web.json_response(
                        _error_body(502, frame["error"], "engine_error"),
                        status=502)
                images.append(np.frombuffer(
                    frame["data"], np.float32).reshape(
                        tuple(frame["shape"])))
        except NoInstancesAvailable:
            return web.json_response(
                _error_body(503, "no diffusion workers", "overloaded"),
                status=503)
        return images

    async def _images(self, request: web.Request) -> web.Response:
        """OpenAI Images API (ref: openai.rs /v1/images/generations)."""
        try:
            body = await request.json()
        except (ValueError, UnicodeDecodeError):
            return web.json_response(_error_body(400, "invalid JSON body"),
                                     status=400)
        model = body.get("model", "")
        start = time.monotonic()
        status = "error"
        try:
            result = await self._diffusion_generate(model, body, n_frames=1)
            if isinstance(result, web.Response):
                return result
            from ..diffusion import to_png_b64 as _to_png_b64

            data = [{"b64_json": _to_png_b64(img[0])} for img in result]
            status = "ok"
            return web.json_response({"created": now_unix(), "data": data})
        finally:
            # count + audit every outcome (same invariant as the chat
            # routes: failures must not vanish from the trail)
            self._count_request(model, status, start, kind="images")

    async def _videos(self, request: web.Request) -> web.Response:
        """Video generation: N temporally-threaded frames returned as an
        animated GIF (ref: openai.rs /v1/videos route; the reference
        delegates to SGLang video diffusion)."""
        try:
            body = await request.json()
        except (ValueError, UnicodeDecodeError):
            return web.json_response(_error_body(400, "invalid JSON body"),
                                     status=400)
        model = body.get("model", "")
        try:
            fps = max(1, min(int(body.get("fps", 4)), 30))
            seconds = float(body.get("seconds", 1.0))
            n_frames = max(1, min(int(seconds * fps), 16))
        except (TypeError, ValueError, OverflowError):
            return web.json_response(_error_body(
                400, "fps/seconds must be finite numbers"), status=400)
        start = time.monotonic()
        status = "error"
        try:
            result = await self._diffusion_generate(model, body,
                                                    n_frames=n_frames)
            if isinstance(result, web.Response):
                return result
            from ..diffusion import to_gif_b64 as _to_gif_b64

            data = [{"b64_json": _to_gif_b64(img, fps=fps), "format": "gif",
                     "frames": int(img.shape[0])} for img in result]
            status = "ok"
            return web.json_response({"created": now_unix(), "data": data})
        finally:
            self._count_request(model, status, start, kind="videos")

    # -- embeddings --------------------------------------------------------

    def _embedding_inputs(self, raw, entry: ModelEntry) -> list[list[int]]:
        """Normalize OpenAI `input` (str | [str] | [int] | [[int]]) into
        token-id lists."""
        if isinstance(raw, str):
            return [entry.preprocessor.tokenizer.encode(raw)]
        if isinstance(raw, list) and raw:
            if all(isinstance(x, str) for x in raw):
                return [entry.preprocessor.tokenizer.encode(x) for x in raw]
            if all(isinstance(x, int) for x in raw):
                return [[int(x) for x in raw]]
            if all(isinstance(x, list) for x in raw):
                return [[int(t) for t in x] for x in raw]
        raise RequestError("'input' must be a string, list of strings, or "
                           "token array(s)")

    async def _embed_one(self, entry: ModelEntry, model: str,
                         token_ids: list[int]) -> list[float]:
        pre = PreprocessedRequest(
            request_id=new_request_id(),
            token_ids=token_ids,
            sampling=SamplingOptions(max_tokens=1, temperature=0.0),
            stop=StopConditions(),
            model=model,
            annotations={"embed": True},
        )
        async for out in entry.engine.generate(pre):
            if out.error:
                raise RemoteError(out.error)
            if out.embedding is not None:
                return out.embedding
            if out.finish_reason is not None:
                break
        raise RemoteError("worker returned no embedding")

    async def _embeddings(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except (ValueError, UnicodeDecodeError):
            return web.json_response(_error_body(400, "invalid JSON body"),
                                     status=400)
        model = body.get("model", "")
        entry, lora = self._lookup(model)
        if lora is not None:
            return web.json_response(_error_body(
                400, f"model '{model}' is a LoRA adapter; adapters are not "
                     "supported for embeddings"), status=400)
        self._check_busy(entry)
        # One id correlates the recorder entry with the audit record (the
        # join-by-request_id model every other endpoint follows).
        request_id = new_request_id()
        current_request_id.set(request_id)
        if self.recorder is not None:
            self.recorder.record_request(request_id, "embeddings", body)
        try:
            inputs = self._embedding_inputs(body.get("input"), entry)
            for toks in inputs:
                if len(toks) >= entry.card.context_length:
                    raise RequestError(
                        f"input of {len(toks)} tokens exceeds the model "
                        f"context length ({entry.card.context_length})")
        except RequestError as exc:
            return web.json_response(_error_body(400, str(exc)), status=400)
        encoding = body.get("encoding_format", "float")
        if encoding not in ("float", "base64"):
            return web.json_response(
                _error_body(400, "encoding_format must be float or base64"),
                status=400)
        total = sum(len(t) for t in inputs)
        start = time.monotonic()
        status = "error"
        try:
            try:
                vectors = await asyncio.gather(*[
                    self._embed_one(entry, model, toks) for toks in inputs
                ])
            except NoInstancesAvailable:
                return web.json_response(
                    _error_body(503, "no workers available", "overloaded"),
                    status=503)
            except RemoteError as exc:
                return web.json_response(
                    _error_body(502, str(exc), "engine_error"), status=502)
            data = []
            for i, vec in enumerate(vectors):
                if encoding == "base64":
                    import numpy as np

                    payload = base64.b64encode(
                        np.asarray(vec, np.float32).tobytes()).decode()
                else:
                    payload = vec
                data.append({"object": "embedding", "index": i,
                             "embedding": payload})
            status = "ok"
            return web.json_response({
                "object": "list",
                "data": data,
                "model": model,
                "usage": {"prompt_tokens": total, "total_tokens": total},
            })
        finally:
            self._count_request(model, status, start, kind="embeddings",
                                request_id=request_id, prompt_tokens=total)

    # -- Anthropic Messages API (ref: http/service/anthropic.rs) -----------

    @staticmethod
    def _messages_to_chat(body: dict) -> dict:
        """Lower an Anthropic Messages request onto the chat pipeline."""
        if not body.get("messages"):
            raise RequestError("'messages' is required")
        if not body.get("max_tokens"):
            raise RequestError("'max_tokens' is required")
        messages = []
        system = body.get("system")
        if system:
            if isinstance(system, list):  # content-block form
                system = "".join(b.get("text", "") for b in system
                                 if isinstance(b, dict))
            messages.append({"role": "system", "content": system})
        for msg in body["messages"]:
            content = msg.get("content")
            if isinstance(content, list):
                content = "".join(b.get("text", "") for b in content
                                  if isinstance(b, dict)
                                  and b.get("type") == "text")
            messages.append({"role": msg.get("role", "user"),
                             "content": content or ""})
        chat = {
            "model": body.get("model", ""),
            "messages": messages,
            "max_tokens": body["max_tokens"],
            "temperature": body.get("temperature", 1.0),
            "top_p": body.get("top_p", 1.0),
            "top_k": body.get("top_k", 0),
            "stop": body.get("stop_sequences"),
        }
        # QoS fields ride every completion-shaped endpoint
        # (docs/multi-tenancy.md); the preprocessor validates the class.
        if body.get("priority"):
            chat["priority"] = body["priority"]
        if body.get("tenant"):
            chat["tenant"] = body["tenant"]
        return chat

    @staticmethod
    def _anthropic_stop(delta_gen: DeltaGenerator) -> tuple[str, Optional[str]]:
        """(stop_reason, stop_sequence) in Anthropic terms."""
        if delta_gen.stop_sequence_hit is not None:
            return "stop_sequence", delta_gen.stop_sequence_hit
        reason = {"length": "max_tokens"}.get(
            delta_gen.finish_reason or "stop", "end_turn")
        return reason, None

    async def _anthropic_messages(self, request: web.Request) -> web.StreamResponse:
        arrival = time.time()
        try:
            body = await request.json()
        except (ValueError, UnicodeDecodeError):
            return web.json_response(_error_body(400, "invalid JSON body"),
                                     status=400)
        model = body.get("model", "")
        entry, lora = self._lookup(model)
        self._check_busy(entry)
        deadline = self._admit_deadline(request, entry)
        self._check_queue_admission(entry, deadline,
                                    tenant=self._tenant_of(request, body))
        clean_body, sid, anchors_raw = self._session_prepare(request, body)
        clean_body = self._fold_qos_headers(request, clean_body)
        try:
            chat_body = self._messages_to_chat(clean_body)
            preprocessed = entry.preprocessor.preprocess_chat(chat_body)
        except RequestError as exc:
            return web.json_response(_error_body(400, str(exc)), status=400)
        self._check_tenant_quota(entry, preprocessed)
        preprocessed.lora_name = lora
        preprocessed.deadline = deadline
        if self.recorder is not None:
            self.recorder.record_request(
                preprocessed.request_id, "messages", body)
        current_request_id.set(preprocessed.request_id)
        span = get_tracer().start_span(
            "http.messages", parent=request.headers.get("traceparent"),
            kind=2, **{"request.id": preprocessed.request_id,
                       "model": model,
                       "input.tokens": len(preprocessed.token_ids)})
        self._open_http_trace(request, preprocessed, span, received=arrival)
        # Anthropic anchor indices are against body["messages"]; the
        # lowered chat list may prepend a system message — remap (-1 =
        # marked system block -> chat index 0).
        chat_msgs = chat_body.get("messages") or []
        offset = 1 if (chat_msgs and chat_msgs[0].get("role") == "system") \
            else 0
        self._session_register(
            entry, preprocessed, chat_msgs, sid,
            [(i if i < 0 else i + offset, ttl) for i, ttl in anchors_raw])
        return await self._finish_guard(
            preprocessed.request_id,
            self._messages_traced(
                request, entry, preprocessed, span, body, model),
            span=span)

    async def _messages_traced(
        self, request: web.Request, entry: ModelEntry,
        preprocessed: PreprocessedRequest, span, body: dict, model: str,
    ) -> web.StreamResponse:
        delta_gen = DeltaGenerator(entry.preprocessor, preprocessed,
                                   kind="chat")
        msg_id = f"msg_{uuid.uuid4().hex[:24]}"
        if bool(body.get("stream", False)):
            return await self._anthropic_stream(request, entry, preprocessed,
                                                delta_gen, msg_id, span)
        start = time.monotonic()
        status = "error"
        try:
            err = await self._consume(entry, preprocessed, delta_gen,
                                      observe_latency=True)
            if err is not None:
                return err
            status = "ok"
        finally:
            span.end(ok=status == "ok")
            self._count_request(model, status, start,
                                preprocessed=preprocessed,
                                delta_gen=delta_gen, kind="messages")
        stop_reason, stop_sequence = self._anthropic_stop(delta_gen)
        return web.json_response({
            "id": msg_id,
            "type": "message",
            "role": "assistant",
            "model": model,
            "content": [{"type": "text", "text": delta_gen.full_text}],
            "stop_reason": stop_reason,
            "stop_sequence": stop_sequence,
            "usage": {
                "input_tokens": len(preprocessed.token_ids),
                "output_tokens": delta_gen.completion_tokens,
            },
        })

    async def _anthropic_stream(
        self, request: web.Request, entry: ModelEntry,
        preprocessed: PreprocessedRequest, delta_gen: DeltaGenerator,
        msg_id: str, span,
    ) -> web.StreamResponse:
        response = web.StreamResponse(
            status=200,
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache",
                     "X-Request-Id": preprocessed.request_id},
        )
        await response.prepare(request)

        async def emit(*events: tuple[str, dict]) -> None:
            await _write_events(
                response, [_sse(payload, name) for name, payload in events])

        await emit(("message_start", {
            "type": "message_start",
            "message": {"id": msg_id, "type": "message", "role": "assistant",
                        "model": preprocessed.model, "content": [],
                        "stop_reason": None, "stop_sequence": None,
                        "usage": {"input_tokens": len(preprocessed.token_ids),
                                  "output_tokens": 0}},
        }), ("content_block_start", {
            "type": "content_block_start", "index": 0,
            "content_block": {"type": "text", "text": ""},
        }))
        start = time.monotonic()
        obs = _SloObserver(preprocessed, self.slo_ttft_ms, self.slo_itl_ms,
                           wait_estimator=entry.wait_estimator)
        errored = False
        disconnected = False
        try:
            async for output in self._generate(entry, preprocessed):
                obs.on_output(output)
                if output.error:
                    errored = True
                    await emit(("error", {"type": "error",
                                          "error": {"type": "api_error",
                                                    "message": output.error}}))
                    break
                texts = [chunk["choices"][0]["delta"].get("content")
                         for chunk in delta_gen.on_output(output)]
                await emit(*(("content_block_delta", {
                    "type": "content_block_delta", "index": 0,
                    "delta": {"type": "text_delta", "text": text},
                }) for text in texts if text))
                if delta_gen.finish_reason is not None:
                    break
            if not errored:
                stop_reason, stop_sequence = self._anthropic_stop(delta_gen)
                await emit(
                    ("content_block_stop",
                     {"type": "content_block_stop", "index": 0}),
                    ("message_delta", {
                        "type": "message_delta",
                        "delta": {"stop_reason": stop_reason,
                                  "stop_sequence": stop_sequence},
                        "usage": {"output_tokens":
                                  delta_gen.completion_tokens},
                    }),
                    ("message_stop", {"type": "message_stop"}))
        except (NoInstancesAvailable, AdmissionRefused, RemoteError) as exc:
            errored = True
            if isinstance(exc, AdmissionRefused):
                # Deliberate early shed, not a failure: keep its
                # timeline out of the error auto-dump storm.
                get_recorder().finish(preprocessed.request_id, "shed")
            await emit(("error", {"type": "error",
                                  "error": {"type": "api_error",
                                            "message": str(exc)}}))
        except DeadlineExceeded as exc:
            # Same classification as the chat stream: counted, recorded
            # as deadline_exceeded (not a bare error), surfaced as a
            # parseable error event instead of a dropped chunked read.
            errored = True
            rt_metrics.DEADLINE_EXCEEDED.labels(component="frontend").inc()
            get_recorder().finish(preprocessed.request_id,
                                  "deadline_exceeded")
            await emit(("error", {"type": "error",
                                  "error": {"type": "timeout_error",
                                            "message": str(exc)}}))
        except (ConnectionResetError, asyncio.CancelledError):
            # Client went away: normal teardown, excluded from goodput.
            get_recorder().finish(preprocessed.request_id, "cancelled")
            disconnected = True
            raise
        finally:
            ok = delta_gen.finish_reason is not None and not errored
            span.end(ok=ok and not disconnected)
            if not disconnected:
                obs.finalize_from(delta_gen)
            self._count_request(preprocessed.model,
                                "ok" if ok else "error", start,
                                preprocessed=preprocessed,
                                delta_gen=delta_gen, kind="messages")
        await response.write_eof()
        return response

    # -- OpenAI Responses API ----------------------------------------------

    @staticmethod
    def _responses_to_chat(body: dict) -> dict:
        """Lower a Responses API request onto the chat pipeline."""
        raw = body.get("input")
        if raw is None:
            raise RequestError("'input' is required")
        messages = []
        instructions = body.get("instructions")
        if instructions:
            messages.append({"role": "system", "content": instructions})
        if isinstance(raw, str):
            messages.append({"role": "user", "content": raw})
        elif isinstance(raw, list):
            for item in raw:
                if not isinstance(item, dict):
                    raise RequestError("input items must be objects")
                content = item.get("content")
                if isinstance(content, list):
                    content = "".join(
                        b.get("text", "") for b in content
                        if isinstance(b, dict)
                        and b.get("type") in ("input_text", "output_text",
                                              "text"))
                messages.append({"role": item.get("role", "user"),
                                 "content": content or ""})
        else:
            raise RequestError("'input' must be a string or message list")
        chat = {
            "model": body.get("model", ""),
            "messages": messages,
            "max_tokens": body.get("max_output_tokens"),
            "temperature": body.get("temperature", 1.0),
            "top_p": body.get("top_p", 1.0),
        }
        # QoS fields ride every completion-shaped endpoint.
        if body.get("priority"):
            chat["priority"] = body["priority"]
        if body.get("tenant"):
            chat["tenant"] = body["tenant"]
        return chat

    def _responses_body(self, resp_id: str, model: str,
                        delta_gen: DeltaGenerator, status: str) -> dict:
        return {
            "id": resp_id,
            "object": "response",
            "created_at": now_unix(),
            "status": status,
            "model": model,
            "output": [{
                "type": "message",
                "id": f"msg_{uuid.uuid4().hex[:24]}",
                "status": status,
                "role": "assistant",
                "content": [{"type": "output_text",
                             "text": delta_gen.full_text,
                             "annotations": []}],
            }],
            "usage": {
                "input_tokens": len(delta_gen.request.token_ids),
                "output_tokens": delta_gen.completion_tokens,
                "total_tokens": (len(delta_gen.request.token_ids)
                                 + delta_gen.completion_tokens),
            },
        }

    async def _responses(self, request: web.Request) -> web.StreamResponse:
        arrival = time.time()
        try:
            body = await request.json()
        except (ValueError, UnicodeDecodeError):
            return web.json_response(_error_body(400, "invalid JSON body"),
                                     status=400)
        model = body.get("model", "")
        entry, lora = self._lookup(model)
        self._check_busy(entry)
        deadline = self._admit_deadline(request, entry)
        self._check_queue_admission(entry, deadline,
                                    tenant=self._tenant_of(request, body))
        body = self._fold_qos_headers(request, body)
        try:
            chat_body = self._responses_to_chat(body)
            preprocessed = entry.preprocessor.preprocess_chat(chat_body)
        except RequestError as exc:
            return web.json_response(_error_body(400, str(exc)), status=400)
        self._check_tenant_quota(entry, preprocessed)
        preprocessed.lora_name = lora
        preprocessed.deadline = deadline
        if self.recorder is not None:
            self.recorder.record_request(
                preprocessed.request_id, "responses", body)
        current_request_id.set(preprocessed.request_id)
        span = get_tracer().start_span(
            "http.responses", parent=request.headers.get("traceparent"),
            kind=2, **{"request.id": preprocessed.request_id,
                       "model": model,
                       "input.tokens": len(preprocessed.token_ids)})
        self._open_http_trace(request, preprocessed, span, received=arrival)
        return await self._finish_guard(
            preprocessed.request_id,
            self._responses_traced(
                request, entry, preprocessed, span, body, model),
            span=span)

    async def _responses_traced(
        self, request: web.Request, entry: ModelEntry,
        preprocessed: PreprocessedRequest, span, body: dict, model: str,
    ) -> web.StreamResponse:
        delta_gen = DeltaGenerator(entry.preprocessor, preprocessed,
                                   kind="chat")
        resp_id = f"resp_{uuid.uuid4().hex[:24]}"
        if bool(body.get("stream", False)):
            return await self._responses_stream(request, entry, preprocessed,
                                                delta_gen, resp_id, span)
        start = time.monotonic()
        status = "error"
        try:
            err = await self._consume(entry, preprocessed, delta_gen,
                                      observe_latency=True)
            if err is not None:
                return err
            status = "ok"
        finally:
            span.end(ok=status == "ok")
            self._count_request(model, status, start,
                                preprocessed=preprocessed,
                                delta_gen=delta_gen, kind="responses")
        return web.json_response(
            self._responses_body(resp_id, model, delta_gen, "completed"))

    async def _responses_stream(
        self, request: web.Request, entry: ModelEntry,
        preprocessed: PreprocessedRequest, delta_gen: DeltaGenerator,
        resp_id: str, span,
    ) -> web.StreamResponse:
        response = web.StreamResponse(
            status=200,
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache",
                     "X-Request-Id": preprocessed.request_id},
        )
        await response.prepare(request)

        async def emit(*events: tuple[str, dict]) -> None:
            await _write_events(
                response, [_sse(payload, name) for name, payload in events])

        await emit(("response.created", {
            "type": "response.created",
            "response": self._responses_body(resp_id, preprocessed.model,
                                             delta_gen, "in_progress"),
        }))
        start = time.monotonic()
        obs = _SloObserver(preprocessed, self.slo_ttft_ms, self.slo_itl_ms,
                           wait_estimator=entry.wait_estimator)
        errored = False
        disconnected = False
        try:
            async for output in self._generate(entry, preprocessed):
                obs.on_output(output)
                if output.error:
                    errored = True
                    await emit(("error", {"type": "error",
                                          "message": output.error}))
                    break
                texts = [chunk["choices"][0]["delta"].get("content")
                         for chunk in delta_gen.on_output(output)]
                await emit(*(("response.output_text.delta", {
                    "type": "response.output_text.delta",
                    "delta": text,
                }) for text in texts if text))
                if delta_gen.finish_reason is not None:
                    break
            if not errored:
                await emit(("response.output_text.done", {
                    "type": "response.output_text.done",
                    "text": delta_gen.full_text,
                }), ("response.completed", {
                    "type": "response.completed",
                    "response": self._responses_body(
                        resp_id, preprocessed.model, delta_gen, "completed"),
                }))
        except (NoInstancesAvailable, AdmissionRefused, RemoteError) as exc:
            errored = True
            if isinstance(exc, AdmissionRefused):
                # Deliberate early shed, not a failure: keep its
                # timeline out of the error auto-dump storm.
                get_recorder().finish(preprocessed.request_id, "shed")
            await emit(("error", {"type": "error", "message": str(exc)}))
        except DeadlineExceeded as exc:
            # Same classification as the chat stream (see _stream_response).
            errored = True
            rt_metrics.DEADLINE_EXCEEDED.labels(component="frontend").inc()
            get_recorder().finish(preprocessed.request_id,
                                  "deadline_exceeded")
            await emit(("error", {"type": "error",
                                  "message": str(exc),
                                  "code": "deadline_exceeded"}))
        except (ConnectionResetError, asyncio.CancelledError):
            # Client went away: normal teardown, excluded from goodput.
            get_recorder().finish(preprocessed.request_id, "cancelled")
            disconnected = True
            raise
        finally:
            ok = delta_gen.finish_reason is not None and not errored
            span.end(ok=ok and not disconnected)
            if not disconnected:
                obs.finalize_from(delta_gen)
            self._count_request(preprocessed.model,
                                "ok" if ok else "error", start,
                                preprocessed=preprocessed,
                                delta_gen=delta_gen, kind="responses")
        await response.write_eof()
        return response

    # -- lifecycle ---------------------------------------------------------

    # -- admin + docs routes (ref: busy_threshold.rs, clear_kv_blocks.rs,
    # service_v2.rs /openapi.json + /docs) --------------------------------

    async def _busy_threshold_list(self, _request: web.Request) -> web.Response:
        return web.json_response({"thresholds": [
            {"model": m, "active_decode_blocks_threshold": v}
            for m, v in sorted(self.busy_thresholds.items())
        ]})

    async def _busy_threshold_post(self, request: web.Request) -> web.Response:
        """Get or set a model's busy threshold: body with a threshold
        sets it; body with only the model name reads it back (the
        reference's get-or-set POST contract, busy_threshold.rs)."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response(
                _error_body(400, "invalid JSON body"), status=400)
        model = body.get("model")
        if not isinstance(model, str) or not model:
            return web.json_response(
                _error_body(400, "'model' is required"), status=400)
        entry, _ = self.manager.resolve(model)
        if entry is None:
            return web.json_response(
                _error_body(404, f"model '{model}' not found",
                            "model_not_found"), status=404)
        name = entry.card.name
        value = body.get("active_decode_blocks_threshold",
                         body.get("busy_threshold"))
        if value is not None:
            try:
                value = float(value)
            except (TypeError, ValueError):
                return web.json_response(_error_body(
                    400, "active_decode_blocks_threshold must be a "
                    "number in [0, 1]"), status=400)
            if not 0.0 <= value <= 1.0:
                return web.json_response(_error_body(
                    400, "active_decode_blocks_threshold must be in "
                    "[0, 1]"), status=400)
            self.busy_thresholds[name] = value
        current = self.busy_thresholds.get(name, self.busy_threshold)
        return web.json_response(
            {"model": name, "active_decode_blocks_threshold": current})

    async def _clear_kv_blocks(self, _request: web.Request) -> web.Response:
        """Fan out to every worker group's clear_kv_blocks endpoint and
        report per-worker outcomes (ref: clear_kv_blocks.rs)."""
        entries = self.manager.entries()
        if not entries:
            return web.json_response(
                {"message": "No active worker groups found"})
        if self.runtime is None:
            return web.json_response(
                {"message": "Failed to create distributed runtime"})
        cleared, failed = [], []
        seen: set[tuple[str, str]] = set()
        for entry in entries:
            card = entry.card
            key = (card.namespace, card.component)
            if key in seen:  # chat+completions share a worker group
                continue
            seen.add(key)
            endpoint = f"{card.namespace}/{card.component}/clear_kv_blocks"
            client = None
            try:
                client = (
                    self.runtime.namespace(card.namespace)
                    .component(card.component)
                    .endpoint("clear_kv_blocks")
                    .client()
                )
                await client.start()
                instance_ids = list(client.instance_ids()) or [None]
                for iid in instance_ids:
                    rec = {"name": card.name, "endpoint": endpoint,
                           "instance": iid}
                    try:
                        if iid is None:
                            raise RuntimeError("no live instances")
                        async for resp in client.direct({}, iid):
                            rec["response"] = resp
                            break
                        rec["status"] = "cleared"
                        cleared.append(rec)
                    except Exception as exc:  # noqa: BLE001 — report
                        rec["status"] = "failed"
                        rec["error"] = str(exc)
                        failed.append(rec)
            except Exception as exc:  # noqa: BLE001 — report per group
                failed.append({"name": card.name, "endpoint": endpoint,
                               "status": "failed", "error": str(exc)})
            finally:
                # per-request client: close its discovery watcher/task
                # or every POST leaks one for the frontend's lifetime
                if client is not None:
                    try:
                        await client.close()
                    except Exception:  # noqa: BLE001 — best-effort
                        log.exception("clear_kv client close failed")
        return web.json_response(
            {"cleared_workers": cleared, "failed_workers": failed})

    # (method, path, summary) — drives both the aiohttp route table and
    # the generated OpenAPI document (ref: RouteDoc in service_v2.rs).
    _ROUTE_DOCS = (
        ("post", "/v1/chat/completions",
         "OpenAI chat completions (SSE streaming + aggregate)"),
        ("post", "/v1/completions", "OpenAI text completions"),
        ("post", "/v1/embeddings", "OpenAI embeddings"),
        ("post", "/v1/messages", "Anthropic messages"),
        ("post", "/v1/responses", "OpenAI responses"),
        ("post", "/v1/images/generations", "Image generation (diffusion)"),
        ("post", "/v1/videos", "Video generation (diffusion)"),
        ("get", "/v1/models", "List served models, adapters, and pools"),
        ("get", "/health", "Service health + served model list"),
        ("get", "/live", "Liveness probe"),
        ("get", "/metrics",
         "Prometheus metrics (OpenMetrics + exemplars via Accept)"),
        ("get", "/debug/requests",
         "Flight recorder: inflight + recent request timelines"),
        ("get", "/debug/profile",
         "On-demand jax.profiler capture (?duration_ms=); returns the "
         "trace artifact path"),
        ("get", "/busy_threshold", "List per-model busy thresholds"),
        ("post", "/busy_threshold",
         "Get or set a model's busy threshold (load shedding)"),
        ("post", "/clear_kv_blocks",
         "Clear every worker's KV prefix cache"),
        ("get", "/openapi.json", "This OpenAPI document"),
        ("get", "/docs", "Human-readable API index"),
    )

    def _route_docs(self):
        """_ROUTE_DOCS minus routes not actually registered (the opt-in
        /debug/* endpoints), so /openapi.json and /docs never advertise
        an endpoint that 404s."""
        if env("DYNT_DEBUG_ENDPOINTS"):
            return self._ROUTE_DOCS
        return tuple(r for r in self._ROUTE_DOCS
                     if not r[1].startswith("/debug/"))

    async def _openapi(self, _request: web.Request) -> web.Response:
        paths: dict[str, dict] = {}
        for method, path, summary in self._route_docs():
            paths.setdefault(path, {})[method] = {
                "summary": summary,
                "responses": {"200": {"description": "OK"}},
            }
        return web.json_response({
            "openapi": "3.0.3",
            "info": {"title": "dynamo_tpu frontend",
                     "version": "1.0.0"},
            "paths": paths,
        })

    async def _docs(self, _request: web.Request) -> web.Response:
        # Self-contained (zero-CDN) index rendered from _ROUTE_DOCS; the
        # machine-readable spec lives at /openapi.json.
        rows = "".join(
            f"<tr><td><code>{m.upper()}</code></td>"
            f"<td><code>{p}</code></td><td>{s}</td></tr>"
            for m, p, s in self._route_docs())
        html = (
            "<!doctype html><html><head><title>dynamo_tpu API</title>"
            "<style>body{font-family:sans-serif;margin:2em}"
            "table{border-collapse:collapse}td,th{border:1px solid #ccc;"
            "padding:4px 10px;text-align:left}</style></head><body>"
            "<h1>dynamo_tpu frontend API</h1>"
            "<p>Machine-readable spec: <a href='/openapi.json'>"
            "/openapi.json</a></p>"
            f"<table><tr><th>Method</th><th>Path</th><th>Summary</th></tr>"
            f"{rows}</table></body></html>")
        return web.Response(text=html, content_type="text/html")

    def build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_post("/v1/chat/completions", self._chat)
        app.router.add_post("/v1/completions", self._completions)
        app.router.add_post("/v1/embeddings", self._embeddings)
        app.router.add_post("/v1/messages", self._anthropic_messages)
        app.router.add_post("/v1/responses", self._responses)
        app.router.add_post("/v1/images/generations", self._images)
        app.router.add_post("/v1/videos", self._videos)
        app.router.add_get("/v1/models", self._models)
        app.router.add_get("/health", self._health)
        app.router.add_get("/live", self._health)
        app.router.add_get("/metrics", self._metrics)
        if env("DYNT_DEBUG_ENDPOINTS"):
            # Tenant-facing port: the flight recorder exposes every
            # client's request timelines and a profile capture burns
            # serving-process time, so both are opt-in here (the
            # internal status server always serves them).
            app.router.add_get("/debug/requests", self._debug_requests)
            app.router.add_get("/debug/profile", self._debug_profile)
        app.router.add_get("/busy_threshold", self._busy_threshold_list)
        app.router.add_post("/busy_threshold", self._busy_threshold_post)
        app.router.add_post("/clear_kv_blocks", self._clear_kv_blocks)
        app.router.add_get("/openapi.json", self._openapi)
        app.router.add_get("/docs", self._docs)
        return app

    async def start(self) -> None:
        self._runner = web.AppRunner(self.build_app(), access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]  # type: ignore[union-attr]
        log.info("OpenAI frontend listening on %s:%d", self.host, self.port)

    async def close(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
