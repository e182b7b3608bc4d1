"""The one traffic generator: a mix file of parameters -> the requests of a run.

A pure function of (mix, vocabulary size, seed). Every seed gets the SAME
multiset of prompt lengths, the same multiset of output lengths and, in an
open loop, the same multiset of inter-arrival gaps; which output goes with
which prompt, their order, and the prompt token ids follow the seed. So two
runs with different seeds offer the server the same work, paired and ordered
anew, and their spread is the system's, not the dice's (the builder's
contract asks for this where the seed would change the work).

Lengths are the quantile points of a clipped lognormal (no sampling noise).
"""

from __future__ import annotations

import dataclasses
import math
import random
from statistics import NormalDist


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float  # offset from the start of the traffic; 0.0 in a closed loop
    prompt: tuple[int, ...]
    max_tokens: int

    @property
    def total_tokens(self) -> int:
        return len(self.prompt) + self.max_tokens


def _lognormal_quantiles(spec: dict, n: int) -> list[int]:
    """n quantile points (midpoints of n equal-probability bins) of a
    lognormal with the given median and sigma, clipped to [min, max]."""
    norm = NormalDist()
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        z = norm.inv_cdf((i + 0.5) / n)
        value = int(round(math.exp(mu + spec["sigma"] * z)))
        out.append(max(spec["min"], min(spec["max"], value)))
    return out


def population(mix: dict, seed: int) -> list[tuple[int, int]]:
    """The run's (prompt tokens, output tokens) pairs: the mix's fixed
    prompt lengths and fixed output lengths, paired from the seed. (An
    output is cut where prompt + output would pass `max_total_tokens`.)"""
    n = int(mix["population"])
    prompts = _lognormal_quantiles(mix["prompt_tokens"], n)
    outputs = _lognormal_quantiles(mix["output_tokens"], n)
    random.Random(f"pairs/{seed}").shuffle(outputs)
    cap = int(mix["max_total_tokens"])
    return [(p, min(o, cap - p)) for p, o in zip(prompts, outputs)]


def arrival_gaps(mix: dict, n: int) -> list[float]:
    """n inter-arrival gaps: the quantile points of the exponential with
    the mix's rate (a Poisson process's gaps), or zeros in a closed loop."""
    if mix["loop"] == "closed":
        return [0.0] * n
    rate = float(mix["rate_rps"])
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def requests(mix: dict, vocab_size: int, seed: int, count: int) -> list[Request]:
    """The first `count` requests of the run with this seed. The population
    is walked in a seeded order and reshuffled each time it is exhausted."""
    pairs = population(mix, seed)
    gaps = arrival_gaps(mix, len(pairs))
    order_rng = random.Random(f"order/{seed}")
    gap_rng = random.Random(f"gaps/{seed}")
    out: list[Request] = []
    due = 0.0
    while len(out) < count:
        cycle = pairs[:]
        order_rng.shuffle(cycle)
        cycle_gaps = gaps[:]
        gap_rng.shuffle(cycle_gaps)
        for (n_prompt, n_out), gap in zip(cycle, cycle_gaps):
            index = len(out)
            due += gap
            token_rng = random.Random(f"tokens/{seed}/{index}")
            prompt = tuple(token_rng.randrange(vocab_size)
                           for _ in range(n_prompt))
            out.append(Request(index, due, prompt, n_out))
            if len(out) == count:
                break
    return out


def crafted(vocab_size: int, n_prompt: int, max_tokens: int,
            tag: str) -> Request:
    """A warm-up request of a chosen length (ids from the tag, so two
    crafted prompts never share a prefix)."""
    rng = random.Random(f"crafted/{tag}")
    prompt = tuple(rng.randrange(vocab_size) for _ in range(n_prompt))
    return Request(-1, 0.0, prompt, max_tokens)
