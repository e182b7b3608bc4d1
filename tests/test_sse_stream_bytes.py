"""A streamed body is byte for byte what it was before PR 45 joined a
frame's events into one write: `DeltaGenerator` and the frontend's
stream handlers run over scripted engine streams, and the bytes handed to
the response are compared with the bodies the tree before PR 45 wrote
for the same streams (`tests/sse_stream_bodies.json`, recorded from
commit 83dcef2 by `python tests/test_sse_stream_bytes.py <file>`)."""

import asyncio
import json
import os
import sys
import types

import pytest

from dynamo_tpu.llm import (
    ByteTokenizer,
    DeltaGenerator,
    EngineOutput,
    ModelDeploymentCard,
    OpenAIPreprocessor,
)
from dynamo_tpu.llm import http_service
from dynamo_tpu.runtime import metrics as rt_metrics

BODIES = os.path.join(os.path.dirname(__file__), "sse_stream_bodies.json")

# text, frame sizes (the last repeats), stop strings, finish, eos appended
STREAMS = {
    # frames of 1, 8 and 14 tokens; the finish rides the last frame's
    # last token; "wörld" and "你好" split their bytes over frames
    "frames_1_8_14": ("hello wörld, 你好 again", [1, 8, 14, 5], None,
                      "length", False),
    # "END" begins in the frame of 8 and ends in the frame of 14, which
    # holds 11 tokens more that no client may see
    "stop_string_mid_frame": ("abcdefgENDxyzwvutsrqponm", [1, 8, 14], ["END"],
                              "length", False),
    "trimmed_eos": ("bye now, all", [1, 8, 14], None, "stop", True),
}
APIS = ("chat", "completions", "messages", "responses")


class _Sink:
    """Stands where `web.StreamResponse` stands; keeps every write."""

    def __init__(self, **_kwargs) -> None:
        self.writes: list[bytes] = []

    async def prepare(self, _request) -> None:
        pass

    async def write(self, data) -> None:
        self.writes.append(bytes(data))

    async def write_eof(self) -> None:
        pass


class _Span:
    def end(self, ok: bool = True) -> None:
        pass


def _frames(ids, sizes, finish, prompt_tokens):
    frames, at, sizes = [], 0, list(sizes)
    while at < len(ids):
        n = sizes.pop(0) if len(sizes) > 1 else sizes[0]
        part = ids[at:at + n]
        frames.append(EngineOutput(
            token_ids=part, prompt_tokens=prompt_tokens if at == 0 else None,
            logprobs=[-0.25 * (at + j + 1) for j in range(len(part))],
            top_logprobs=[[[t, -0.25 * (at + j + 1)],
                           [65 + (at + j) % 20, -3.0]]
                          for j, t in enumerate(part)]))
        at += n
    frames[-1].finish_reason = finish
    return frames


def stream_body(api: str, stream: str) -> tuple[bytes, int, int]:
    """(body, writes, frames) of one scripted stream through one API's
    stream handler, with every id and clock in it fixed."""
    text, sizes, stop, finish, eos = STREAMS[stream]
    pre = OpenAIPreprocessor(ModelDeploymentCard(name="test-model",
                                                 context_length=1024))
    kind = "completions" if api == "completions" else "chat"
    body = {"max_tokens": 64, "stop": stop, "stream": True,
            "stream_options": {"include_usage": True}}
    if kind == "chat":
        body.update({"logprobs": True, "top_logprobs": 2})
        req = pre.preprocess_chat(
            {"messages": [{"role": "user", "content": "hi"}], **body})
    else:
        body.update({"logprobs": 2})
        req = pre.preprocess_completions({"prompt": "hi", **body})
    req.eos_token_ids = [ByteTokenizer.EOS]
    req.request_id = "req-fixed"
    gen = DeltaGenerator(pre, req, kind=kind)
    gen.chunk_id, gen.created = "cmpl-fixed", 1
    ids = pre.tokenizer.encode(text) + ([ByteTokenizer.EOS] if eos else [])
    frames = _frames(ids, sizes, finish, len(req.token_ids))

    async def generate(_preprocessed):
        for frame in frames:
            yield frame

    entry = types.SimpleNamespace(
        engine=types.SimpleNamespace(generate=generate), wait_estimator=None)
    service = http_service.HttpService(manager=None)
    sinks: list[_Sink] = []

    def sink(**kwargs):
        sinks.append(_Sink(**kwargs))
        return sinks[-1]

    fixed_uuid = types.SimpleNamespace(
        uuid4=lambda: types.SimpleNamespace(hex="0" * 32))
    saved = (http_service.web.StreamResponse, http_service.uuid,
             http_service.now_unix)
    http_service.web.StreamResponse = sink
    http_service.uuid, http_service.now_unix = fixed_uuid, lambda: 1
    try:
        if api == "messages":
            coro = service._anthropic_stream(None, entry, req, gen,
                                             "msg_fixed", _Span())
        elif api == "responses":
            coro = service._responses_stream(None, entry, req, gen,
                                             "resp_fixed", _Span())
        else:
            coro = service._stream_response(None, entry, req, gen, body,
                                            _Span())
        asyncio.run(coro)
    finally:
        (http_service.web.StreamResponse, http_service.uuid,
         http_service.now_unix) = saved
    writes = sinks[0].writes
    return b"".join(writes), len(writes), len(frames)


def read(name: str) -> float:
    return rt_metrics.REGISTRY.get_sample_value(
        f"dynamo_frontend_{name}_total")


@pytest.mark.parametrize("stream", sorted(STREAMS))
@pytest.mark.parametrize("api", APIS)
def test_a_streamed_body_is_the_parents_in_a_write_a_frame(api, stream):
    with open(BODIES) as f:
        recorded = json.load(f)[f"{api}/{stream}"]
    before = {name: read(name) for name in (
        "sse_chunks", "sse_writes", "detok_tokens", "detok_decoded_tokens")}
    body, writes, frames = stream_body(api, stream)
    grown = {name: read(name) - was for name, was in before.items()}
    assert body.decode() == recorded
    assert body.count(b"\n\n") >= 6  # several events: the join has work
    # a write a frame, the opening events and the closing ones
    assert writes <= frames + 2
    assert grown["sse_writes"] == writes
    assert grown["sse_chunks"] == body.count(b"\n\n")
    assert grown["sse_chunks"] > grown["sse_writes"]
    # the byte tokenizer is prefix-stable: a push decodes the pushed id
    # and the bytes of a character still held back, three at most
    assert 0 < grown["detok_tokens"] <= grown["detok_decoded_tokens"]
    assert grown["detok_decoded_tokens"] <= 4 * grown["detok_tokens"]


if __name__ == "__main__":
    # record: run with PYTHONPATH at a checkout of the tree to record from
    with open(sys.argv[1], "w") as out:
        json.dump({f"{api}/{stream}": stream_body(api, stream)[0].decode()
                   for api in APIS for stream in sorted(STREAMS)},
                  out, indent=0, sort_keys=True, ensure_ascii=False)
        out.write("\n")
