"""Client-side arithmetic: from request timelines to the end-to-end metrics.

Copied in spirit from `dynamo_tpu/bench/__init__.py` (stream timing and
percentiles); kept here so that no PR which claims a gain can change it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass
class Timeline:
    """One request as the client saw it, on the harness's clock (seconds)."""

    index: int
    due: float  # when it was due to be sent
    sent: float  # when it really left (>= due; the gap is generator lag)
    n_prompt: int
    want_tokens: int
    first: Optional[float] = None  # first streamed token
    last: Optional[float] = None  # last streamed token
    end: Optional[float] = None  # stream closed (ok or not)
    got_tokens: Optional[int] = None  # usage.completion_tokens
    error: Optional[str] = None
    tag: str = ""
    # arrival time of every streamed chunk that carried text (one engine
    # output, so one token, each)
    chunk_times: list = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return (self.error is None and self.end is not None
                and self.got_tokens == self.want_tokens)


# What window_summary()["metrics"] can hold: a per-layer metric of one of
# these names is read from there and needs no reader file of its own.
CLIENT_METRICS = ("out_tok_s", "ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms",
                  "tpot_p95_ms")


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tpot_ms(t: Timeline) -> Optional[float]:
    """Time per output token after the first: (last - first) / (n - 1).
    Not the raw gap between chunks: tokens arrive in blocks of the
    scheduler's decode block, so the raw gap is a sawtooth."""
    if t.first is None or t.last is None or not t.got_tokens \
            or t.got_tokens < 2:
        return None
    return (t.last - t.first) / (t.got_tokens - 1) * 1e3


def streamed_tokens(t: Timeline, t0: float, t1: float) -> float:
    """Output tokens of one request that reached the client inside
    [t0, t1): its streamed chunks in that span, one token each, scaled to
    the token count the server reported once the request has finished (a
    chunk can carry two tokens when a byte sequence completes)."""
    inside = sum(1 for c in t.chunk_times if t0 <= c < t1)
    if t.got_tokens and t.chunk_times:
        return inside * t.got_tokens / len(t.chunk_times)
    return float(inside)


def window_summary(timelines: list[Timeline], t0: float,
                   seconds: float) -> dict:
    """Everything the metrics need. `out_tok_s` is over all the work of
    the window: every output token that reached a client inside
    [t0, t0 + seconds), whenever its request began or ended. Latencies
    are over the requests whose stream closed inside the window; one
    still streaming at the window's end is neither attempted nor failed.
    A request that failed (error, refusal, wrong token count) misses
    every latency and adds no tokens."""
    t1 = t0 + seconds
    done = [t for t in timelines if t.end is not None and t0 <= t.end < t1]
    good = [t for t in done if t.ok]
    ttft = [(t.first - t.due) * 1e3 for t in good if t.first is not None]
    tpot = [v for v in (tpot_ms(t) for t in good) if v is not None]
    lag = [(t.sent - t.due) * 1e3 for t in timelines
           if t0 <= t.sent < t1]
    streamed = sum(streamed_tokens(t, t0, t1) for t in timelines
                   if t.error is None and (t.end is None or t.ok))
    out = {
        "attempted": len(done),
        "failed": len(done) - len(good),
        "completed": len(good),
        "out_tokens_completed": sum(t.got_tokens for t in good),
        "out_tokens_streamed": streamed,
        "generator_lag_ms_max": max(lag) if lag else 0.0,
        "generator_lag_ms_p50": percentile(lag, 50) if lag else 0.0,
        "metrics": {},
    }
    if seconds > 0:
        out["metrics"]["out_tok_s"] = streamed / seconds
    if ttft:
        out["metrics"]["ttft_p50_ms"] = percentile(ttft, 50)
        out["metrics"]["ttft_p95_ms"] = percentile(ttft, 95)
    if tpot:
        out["metrics"]["tpot_p50_ms"] = percentile(tpot, 50)
        out["metrics"]["tpot_p95_ms"] = percentile(tpot, 95)
    return out


def live_decode_tokens(timelines: list[Timeline], at: float) -> tuple[int, int]:
    """(sequences decoding, context tokens they hold) at instant `at`, as
    the client can tell: a request between its first token and the close
    of its stream holds its prompt plus the tokens streamed so far. The
    least the decode step must read."""
    rows, tokens = 0, 0
    for t in timelines:
        if t.first is None or t.first > at:
            continue
        if t.end is not None and t.end < at:
            continue
        rows += 1
        tokens += t.n_prompt + sum(1 for c in t.chunk_times if c <= at)
    return rows, tokens


def mean_live_decode_tokens(timelines: list[Timeline], a: float,
                            b: float) -> float:
    """Mean over [a, b), sampled every 50 ms, of the context tokens held
    by decoding sequences."""
    n = max(1, int((b - a) / 0.05))
    return sum(live_decode_tokens(timelines, a + (i + 0.5) * (b - a) / n)[1]
               for i in range(n)) / n
