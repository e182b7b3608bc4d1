"""KServe v2 gRPC inference service over the serving pipeline.

Tensor convention (matches the reference's LLM mapping, kserve.rs):
  inputs:  "text_input" BYTES [1]   — the prompt
           "streaming"  BOOL [1]    — stream tokens (ModelStreamInfer only)
  request parameters: "max_tokens" int64, "temperature" double,
           "top_p" double, "chat" bool (route through the chat template)
  outputs: "text_output" BYTES [1]  — generated text (delta when streaming)

ModelInfer aggregates; ModelStreamInfer streams one response per text delta.
"""

from __future__ import annotations

import asyncio
import time
from typing import AsyncIterator, Optional

import grpc

from ...runtime.flight_recorder import get_recorder
from ...runtime.logging import (current_request_id, current_trace_id,
                                get_logger)
from ...runtime.otel import get_tracer, trace_id_of
from ..manager import ModelManager
from ..preprocessor import DeltaGenerator, RequestError
from . import inference_pb2 as pb

log = get_logger("llm.kserve")


def _grpc_traceparent(context) -> Optional[str]:
    """W3C trace context from the gRPC invocation metadata (the header
    contract is identical to HTTP: lowercase `traceparent` key)."""
    try:
        for key, value in context.invocation_metadata() or ():
            if key == "traceparent":
                return value
    except Exception:  # noqa: BLE001 — metadata is best-effort
        pass
    return None

_SERVICE = "inference.GRPCInferenceService"


def _param(params, name: str, kind: str, default=None):
    p = params.get(name)
    if p is None:
        return default
    return getattr(p, kind)


def _text_response(model: str, request_id: str, text: str) -> pb.ModelInferResponse:
    return pb.ModelInferResponse(
        model_name=model,
        id=request_id,
        outputs=[pb.ModelInferResponse.InferOutputTensor(
            name="text_output", datatype="BYTES", shape=[1],
            contents=pb.InferTensorContents(
                bytes_contents=[text.encode()]),
        )],
    )


class KServeGrpcService:
    def __init__(self, manager: ModelManager, host: str = "0.0.0.0",
                 port: int = 0) -> None:
        self.manager = manager
        self.host = host
        self.port = port
        self._server: Optional[grpc.aio.Server] = None

    # -- request lowering --------------------------------------------------

    async def _entry(self, model_name: str, context):
        # resolve() also matches LoRA adapter names, keeping the gRPC and
        # HTTP surfaces consistent.
        entry, lora = self.manager.resolve(model_name)
        if entry is None:
            # context.abort raises; the await satisfies grpc.aio's contract.
            await context.abort(grpc.StatusCode.NOT_FOUND,
                                f"model '{model_name}' not found")
        return entry, lora

    async def _preprocess(self, request: pb.ModelInferRequest, context):
        text = None
        for i, tensor in enumerate(request.inputs):
            if tensor.name == "text_input":
                if tensor.contents.bytes_contents:
                    text = tensor.contents.bytes_contents[0].decode()
                elif len(request.raw_input_contents) > i:
                    raw = request.raw_input_contents[i]
                    # raw BYTES tensor: 4-byte LE length prefix + payload
                    text = raw[4:4 + int.from_bytes(raw[:4], "little")].decode()
        if text is None:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                "missing 'text_input' BYTES tensor")
        entry, lora = await self._entry(request.model_name, context)
        params = request.parameters
        body = {
            "model": request.model_name,
            "max_tokens": _param(params, "max_tokens", "int64_param"),
            "temperature": _param(params, "temperature", "double_param", 1.0),
            "top_p": _param(params, "top_p", "double_param", 1.0),
        }
        try:
            if _param(params, "chat", "bool_param", False):
                body["messages"] = [{"role": "user", "content": text}]
                preprocessed = entry.preprocessor.preprocess_chat(body)
            else:
                body["prompt"] = text
                preprocessed = entry.preprocessor.preprocess_completions(body)
        except RequestError as exc:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(exc))
        preprocessed.lora_name = lora
        return entry, preprocessed

    # -- handlers ----------------------------------------------------------

    async def _server_live(self, request, context) -> pb.ServerLiveResponse:
        return pb.ServerLiveResponse(live=True)

    async def _server_ready(self, request, context) -> pb.ServerReadyResponse:
        return pb.ServerReadyResponse(ready=True)

    async def _model_ready(self, request, context) -> pb.ModelReadyResponse:
        entry, _ = self.manager.resolve(request.name)
        return pb.ModelReadyResponse(ready=entry is not None)

    async def _server_metadata(self, request, context) -> pb.ServerMetadataResponse:
        return pb.ServerMetadataResponse(
            name="dynamo_tpu", version="1.0",
            extensions=["model_repository"])

    async def _model_metadata(self, request, context) -> pb.ModelMetadataResponse:
        entry, _ = await self._entry(request.name, context)
        return pb.ModelMetadataResponse(
            name=entry.card.name,
            versions=["1"],
            platform="dynamo_tpu",
            inputs=[pb.ModelMetadataResponse.TensorMetadata(
                name="text_input", datatype="BYTES", shape=[1])],
            outputs=[pb.ModelMetadataResponse.TensorMetadata(
                name="text_output", datatype="BYTES", shape=[1])],
        )

    @staticmethod
    def _start_trace(preprocessed, context, span_name_is_stream: bool,
                     received: Optional[float] = None):
        """SERVER span + flight-recorder timeline for one gRPC inference —
        the same observability contract as the HTTP path (previously the
        kserve surface only logged the traceparent)."""
        tp = _grpc_traceparent(context)
        span = get_tracer().start_span(
            "grpc.stream_infer" if span_name_is_stream else "grpc.infer",
            parent=tp, kind=2,
            **{"request.id": preprocessed.request_id,
               "model": preprocessed.model,
               "input.tokens": len(preprocessed.token_ids)})
        wire_tp = span.traceparent or tp
        if wire_tp:
            preprocessed.annotations["traceparent"] = wire_tp
        current_request_id.set(preprocessed.request_id)
        current_trace_id.set(trace_id_of(wire_tp) or None)
        # Record the trace id of the traceparent actually forwarded on
        # the wire — same semantics as the HTTP path, which keeps the
        # client's trace id even when local export is disabled.
        get_recorder().start(preprocessed.request_id,
                             model=preprocessed.model,
                             trace_id=trace_id_of(wire_tp),
                             tenant=preprocessed.tenant,
                             received=received)
        return span

    async def _model_infer(self, request, context) -> pb.ModelInferResponse:
        arrival = time.time()
        entry, preprocessed = await self._preprocess(request, context)
        delta_gen = DeltaGenerator(entry.preprocessor, preprocessed,
                                   kind="completions")
        span = self._start_trace(preprocessed, context,
                                 span_name_is_stream=False,
                                 received=arrival)
        status = "error"
        try:
            async for output in entry.engine.generate(preprocessed):
                delta_gen.on_output(output)
                if output.error:
                    # abort raises; the span closes ok=False below.
                    await context.abort(grpc.StatusCode.INTERNAL,
                                        output.error)
            status = "ok"
            span.end(ok=True)
            return _text_response(request.model_name, request.id,
                                  delta_gen.full_text)
        except asyncio.CancelledError:
            # Client cancelled the RPC: routine teardown, not an error —
            # same classification as the HTTP path (keeps the flight
            # recorder from WARNING-dumping every normal cancel).
            status = "cancelled"
            raise
        finally:
            # Aborts, client cancellation, and engine exceptions all pass
            # here: the span must never leak open (first end() wins).
            span.end(ok=False)
            delta_gen.count_detok()
            get_recorder().finish(preprocessed.request_id, status)

    async def _model_stream_infer(
        self, request_iterator, context
    ) -> AsyncIterator[pb.ModelStreamInferResponse]:
        async for request in request_iterator:
            arrival = time.time()
            entry, preprocessed = await self._preprocess(request, context)
            delta_gen = DeltaGenerator(entry.preprocessor, preprocessed,
                                       kind="completions")
            span = self._start_trace(preprocessed, context,
                                     span_name_is_stream=True,
                                     received=arrival)
            status = "error"
            try:
                async for output in entry.engine.generate(preprocessed):
                    for chunk in delta_gen.on_output(output):
                        text = chunk["choices"][0].get("text", "")
                        if text:
                            yield pb.ModelStreamInferResponse(
                                infer_response=_text_response(
                                    request.model_name, request.id, text))
                    if delta_gen.finish_reason is not None:
                        break
                # Terminal empty response carrying the finish marker.
                final = _text_response(request.model_name, request.id, "")
                final.parameters["triton_final_response"].bool_param = True
                yield pb.ModelStreamInferResponse(infer_response=final)
                status = "ok"
                span.end(ok=True)
            except asyncio.CancelledError:
                # Client cancelled the stream: routine teardown, not an
                # error (suppresses the recorder's WARNING auto-dump).
                status = "cancelled"
                raise
            except GeneratorExit:
                # grpc.aio aclose()d the handler generator (stream torn
                # down without task cancellation): same routine teardown.
                status = "cancelled"
                raise
            except Exception as exc:  # noqa: BLE001 — deliver as stream error
                yield pb.ModelStreamInferResponse(error_message=str(exc))
            finally:
                # Stream torn down mid-request (client cancel) included.
                span.end(ok=False)
                delta_gen.count_detok()
                get_recorder().finish(preprocessed.request_id, status)

    # -- lifecycle ---------------------------------------------------------

    def _handlers(self) -> grpc.GenericRpcHandler:
        def unary(fn, req_cls, resp_cls):
            return grpc.unary_unary_rpc_method_handler(
                fn, request_deserializer=req_cls.FromString,
                response_serializer=resp_cls.SerializeToString)

        method_handlers = {
            "ServerLive": unary(self._server_live, pb.ServerLiveRequest,
                                pb.ServerLiveResponse),
            "ServerReady": unary(self._server_ready, pb.ServerReadyRequest,
                                 pb.ServerReadyResponse),
            "ModelReady": unary(self._model_ready, pb.ModelReadyRequest,
                                pb.ModelReadyResponse),
            "ServerMetadata": unary(self._server_metadata,
                                    pb.ServerMetadataRequest,
                                    pb.ServerMetadataResponse),
            "ModelMetadata": unary(self._model_metadata,
                                   pb.ModelMetadataRequest,
                                   pb.ModelMetadataResponse),
            "ModelInfer": unary(self._model_infer, pb.ModelInferRequest,
                                pb.ModelInferResponse),
            "ModelStreamInfer": grpc.stream_stream_rpc_method_handler(
                self._model_stream_infer,
                request_deserializer=pb.ModelInferRequest.FromString,
                response_serializer=pb.ModelStreamInferResponse.SerializeToString),
        }
        return grpc.method_handlers_generic_handler(_SERVICE, method_handlers)

    async def start(self) -> None:
        self._server = grpc.aio.server()
        self._server.add_generic_rpc_handlers((self._handlers(),))
        self.port = self._server.add_insecure_port(f"{self.host}:{self.port}")
        await self._server.start()
        log.info("KServe gRPC frontend listening on %s:%d", self.host,
                 self.port)

    async def close(self) -> None:
        if self._server is not None:
            await self._server.stop(grace=2.0)
            self._server = None


