"""The load generator: token-id prompts over streamed `/v1/completions`,
timed on this process's monotonic clock. One event loop, one thread.

Stream timing follows `dynamo_tpu/bench/__init__.py` (first token, last
token, usage from the closing chunk); arrivals follow
`dynamo_tpu/mocker/loadgen.py` (open loop: send when due, whatever is in
flight; closed loop: each caller sends its next when its last completes).
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Iterable, Optional

import aiohttp

from .stats import Timeline
from .traffic import Request

REQUEST_TIMEOUT_SECS = 1100.0  # above a cold compile of several programs
DRAIN_SECS = 90.0  # for what is in flight at a window's end to finish


class Client:
    def __init__(self, base_url: str, model: str) -> None:
        self.url = f"{base_url}/v1/completions"
        self.model = model
        self.timelines: list[Timeline] = []
        self._session: Optional[aiohttp.ClientSession] = None

    async def __aenter__(self) -> "Client":
        self._session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=REQUEST_TIMEOUT_SECS))
        return self

    async def __aexit__(self, *exc) -> None:
        await self._session.close()

    async def send(self, req: Request, due: float, tag: str) -> Timeline:
        """One request. `due` is on time.monotonic()'s clock. The tag goes
        out as the OpenAI `user` field, which the frontend's recording
        keeps, so the served token ids can be found again."""
        t = Timeline(index=req.index, due=due, sent=time.monotonic(),
                     n_prompt=len(req.prompt), want_tokens=req.max_tokens,
                     tag=tag)
        self.timelines.append(t)
        body = {"model": self.model, "prompt": list(req.prompt),
                "max_tokens": req.max_tokens, "temperature": 0,
                "ignore_eos": True, "stream": True,
                "stream_options": {"include_usage": True}, "user": tag}
        try:
            async with self._session.post(self.url, json=body) as resp:
                if resp.status != 200:
                    text = (await resp.text())[:200]
                    t.error = f"http {resp.status}: {text}"
                    return t
                done = False
                async for raw in resp.content:
                    line = raw.strip()
                    if not line.startswith(b"data: "):
                        continue
                    now = time.monotonic()
                    if line == b"data: [DONE]":
                        done = True
                        break
                    chunk = json.loads(line[6:])
                    if "error" in chunk:
                        t.error = f"stream error: {str(chunk['error'])[:200]}"
                        break
                    if chunk.get("usage"):
                        t.got_tokens = chunk["usage"]["completion_tokens"]
                    if any(c.get("text") for c in chunk.get("choices", [])):
                        if t.first is None:
                            t.first = now
                        t.last = now
                        t.chunk_times.append(now)
                if t.error is None and not done:
                    t.error = "stream ended without [DONE]"
        except asyncio.CancelledError:
            return t  # cut at the window's end: no outcome, end stays None
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
                KeyError) as exc:
            t.error = f"{type(exc).__name__}: {str(exc)[:200]}"
        finally:
            if t.error is not None or t.got_tokens is not None:
                t.end = time.monotonic()
        if t.error is None and t.got_tokens is None:
            t.error = "no usage in the stream"
            t.end = time.monotonic()
        return t

    # -- arrival patterns ---------------------------------------------------

    async def closed_loop(self, requests: Iterable[Request], callers: int,
                          stop_at: float, tag: str,
                          stagger_s: float = 0.0) -> None:
        """`callers` callers, each sending its next request when its last
        completes, until `stop_at`; whatever is in flight then runs to
        its end (and counts in no window). Caller i starts i * stagger_s late, so that they do not prefill,
        decode and finish in lockstep."""
        it = iter(requests)

        async def caller(i: int) -> None:
            await asyncio.sleep(i * stagger_s)
            for req in it:
                if time.monotonic() >= stop_at:
                    return
                await self.send(req, time.monotonic(), f"{tag}-{req.index}")

        await self._run_until([asyncio.ensure_future(caller(i))
                               for i in range(callers)], stop_at)

    async def open_loop(self, requests: Iterable[Request], start_at: float,
                        stop_at: float, tag: str) -> None:
        """Each request leaves when it is due (start_at + its offset),
        whatever is in flight; its clock starts at the due time. Nothing
        leaves after `stop_at`; what is in flight then runs to its end."""
        tasks: list[asyncio.Future] = []

        async def dispatcher() -> None:
            for req in requests:
                due = start_at + req.due_s
                if due >= stop_at:
                    return
                delay = due - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                tasks.append(asyncio.ensure_future(
                    self.send(req, due, f"{tag}-{req.index}")))

        head = asyncio.ensure_future(dispatcher())
        # until stop_at, or sooner where the requests run out before it
        # (--trace 2 ends its tail's traffic with the capture)
        await asyncio.wait([head],
                           timeout=max(0.0, stop_at - time.monotonic()))
        head.cancel()
        await self._run_until(tasks + [head], stop_at)

    @staticmethod
    async def _run_until(tasks: list, stop_at: float) -> None:
        """Wait for the tasks; none is cancelled short of DRAIN_SECS past
        `stop_at` (a cancelled stream is a code path of the server's own,
        and no part of what a window measures)."""
        delay = max(0.0, stop_at - time.monotonic()) + DRAIN_SECS
        _done, pending = await asyncio.wait(tasks, timeout=delay) \
            if tasks else (set(), set())
        for task in pending:
            task.cancel()
        if pending:
            await asyncio.wait(pending, timeout=10.0)

    async def together(self, requests: list[Request], tag: str,
                       stagger_s: float = 0.0) -> list[Timeline]:
        """Send a crafted group: the first at once, the rest `stagger_s`
        later, all awaited."""
        now = time.monotonic()
        first = asyncio.ensure_future(
            self.send(requests[0], now, f"{tag}-0"))
        if stagger_s and len(requests) > 1:
            await asyncio.sleep(stagger_s)
        rest = [asyncio.ensure_future(
            self.send(r, time.monotonic(), f"{tag}-{i + 1}"))
            for i, r in enumerate(requests[1:])]
        return list(await asyncio.gather(first, *rest))
