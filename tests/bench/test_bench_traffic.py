"""The mix generator is a pure function of the seed, and every seed gets
the same work in another order."""

import json
import os
from collections import Counter

import pytest

from bench_paths import BENCH
from dtbench import traffic


def mix(name):
    with open(os.path.join(BENCH, "mixes", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chunk-sat", "rehearsal-open", "rehearsal"])
def test_same_seed_same_requests(name):
    m = dict(mix(name), rate_rps=2.0)
    a = traffic.requests(m, 32768, 2**31 + 12345, 300)
    b = traffic.requests(m, 32768, 2**31 + 12345, 300)
    assert a == b
    c = traffic.requests(m, 32768, 7, 300)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", ["chunk-sat", "rehearsal-open"])
def test_every_seed_gets_the_same_work_in_another_order(name):
    m = dict(mix(name), rate_rps=2.0)
    n = m["population"]
    a = traffic.requests(m, 1000, 1, n)
    b = traffic.requests(m, 1000, 2, n)
    for sizes in (lambda rs: Counter(len(r.prompt) for r in rs),
                  lambda rs: Counter(r.max_tokens for r in rs)):
        assert sizes(a) == sizes(b)  # the same prompts, the same outputs
    pairs = lambda rs: Counter((len(r.prompt), r.max_tokens) for r in rs)
    assert pairs(a) == Counter(traffic.population(m, 1))
    assert pairs(a) != pairs(b)  # paired from the seed
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_lengths_keep_to_the_mix():
    m = mix("chunk-sat")
    pairs = traffic.population(m, 2**31 + 5)
    assert len(pairs) == m["population"]
    for n_prompt, n_out in pairs:
        assert m["prompt_tokens"]["min"] <= n_prompt <= m["prompt_tokens"]["max"]
        assert m["output_tokens"]["min"] <= n_out <= m["output_tokens"]["max"]
        # fused decode writes up to 16 positions past the stop: the
        # table has 64 pages of 16
        assert n_prompt + n_out + 16 <= 1024
    prompts = sorted(p for p, _ in pairs)
    assert 720 <= prompts[len(prompts) // 2] <= 750  # the stated median


def test_open_loop_arrivals_are_a_poisson_process_gaps():
    m = dict(mix("rehearsal-open"), rate_rps=4.0)
    reqs = traffic.requests(m, 100, 3, m["population"])
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues)
    mean_gap = dues[-1] / len(dues)
    assert mean_gap == pytest.approx(1 / 4.0, rel=0.05)
    closed = traffic.requests(mix("chunk-sat"), 100, 3, 10)
    assert all(r.due_s == 0.0 for r in closed)


def test_prompt_ids_cover_the_vocabulary_and_crafted_prompts_differ():
    reqs = traffic.requests(mix("chunk-sat"), 32768, 9, 20)
    ids = [t for r in reqs for t in r.prompt]
    assert 0 <= min(ids) and max(ids) < 32768 and max(ids) > 32000
    a = traffic.crafted(32768, 100, 1, "x")
    b = traffic.crafted(32768, 100, 1, "y")
    assert a.prompt != b.prompt and len(a.prompt) == 100
