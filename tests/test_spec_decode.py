"""Speculative decoding plane: draftless n-gram proposals + batched
verification (engine/spec.py, ModelRunner.decode_spec, scheduler spec
path; docs/speculative-decoding.md).

The load-bearing invariant is EXACTNESS: for a fixed request seed the
speculative engine must emit the bit-identical token stream the
per-token path emits — greedy, temperature, and with logits processors
active — because verification commits only the prefix that matches the
target sampler's own draws. Speedup is a measurement concern (bench.py);
correctness is pinned here on the CPU mesh.
"""

import asyncio
import uuid

import numpy as np
import pytest

from dynamo_tpu.engine import InferenceScheduler, ModelRunner, RunnerConfig
from dynamo_tpu.engine.spec import (
    BlockLookahead,
    NGramProposer,
    SlotSpec,
    propose_for,
)
from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import get_config
from dynamo_tpu.parallel import MeshConfig, make_mesh
from dynamo_tpu.tokens import TokenBlockSequence, compute_block_hashes


def _runner():
    return ModelRunner(
        get_config("tiny-test"),
        RunnerConfig(page_size=4, num_pages=256, max_batch=4,
                     max_pages_per_seq=32, prefill_buckets=(8, 16, 32)),
        make_mesh(MeshConfig()),
        seed=0,
    )


def _request(tokens, max_tokens=32, temperature=0.0, seed=0, top_k=0,
             top_p=1.0, eos=None, processors=None, logit_bias=None,
             repetition_penalty=1.0, min_p=0.0, min_tokens=0):
    return PreprocessedRequest(
        request_id=uuid.uuid4().hex,
        token_ids=list(tokens),
        sampling=SamplingOptions(
            max_tokens=max_tokens, temperature=temperature, seed=seed,
            top_k=top_k, top_p=top_p, logit_bias=logit_bias,
            repetition_penalty=repetition_penalty, min_p=min_p),
        stop=StopConditions(ignore_eos=eos is None, min_tokens=min_tokens),
        eos_token_ids=list(eos or []),
        logits_processors=processors or [],
    )


async def _run_one(sched, request):
    loop = asyncio.get_running_loop()
    queue = asyncio.Queue()
    sched.submit(
        request, lambda o: loop.call_soon_threadsafe(queue.put_nowait, o))
    toks, err, finish = [], None, None
    while True:
        out = await asyncio.wait_for(queue.get(), 60)
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            err = out.error
            finish = out.finish_reason
            return toks, finish, err


_SHARED_RUNNER = None


def _shared_runner():
    """One runner for every scheduler-level test: schedulers run
    strictly sequentially, each with a fresh PagePool (no prefix-cache
    carryover), and stale KV in reallocated pages is rewritten by
    prefill before anything attends it — so sharing is safe and saves a
    model build + jit compile per test."""
    global _SHARED_RUNNER
    if _SHARED_RUNNER is None:
        _SHARED_RUNNER = _runner()
    return _SHARED_RUNNER


def _serve(request, spec: bool, monkeypatch, runner=None):
    """Run one request through a fresh scheduler with speculation on/off
    and return (tokens, finish_reason, error, stats)."""
    monkeypatch.setenv("DYNT_SPEC_ENABLE", "1" if spec else "0")
    monkeypatch.setenv("DYNT_SPEC_MAX_K", "3")
    sched = InferenceScheduler(runner or _shared_runner())
    sched.start()
    try:
        toks, finish, err = asyncio.run(_run_one(sched, request))
    finally:
        sched.stop()
    return toks, finish, err, sched.stats


REPETITIVE = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3]


class TestNGramProposer:
    def test_deterministic_and_chained(self):
        p1 = NGramProposer(REPETITIVE)
        p2 = NGramProposer(REPETITIVE)
        assert p1.propose(4) == p2.propose(4)
        # Suffix (1,2,3) recurred; the continuation chains through the
        # repeating pattern to fill the full draft.
        assert p1.propose(4) == [4, 1, 2, 3]

    def test_no_match_is_empty(self):
        assert NGramProposer([1, 2, 3, 4, 5]).propose(4) == []
        assert NGramProposer([]).propose(4) == []
        assert NGramProposer([7]).propose(0) == []

    def test_extend_indexes_new_continuations(self):
        p = NGramProposer([5, 6, 7])
        assert p.propose(2) == []
        p.extend([5, 6, 7])  # now the suffix (5,6,7) recurred
        assert p.propose(3) == [5, 6, 7]

    def test_pure_repetition_fills_k(self):
        p = NGramProposer([9, 9, 9, 9])
        assert p.propose(6) == [9] * 6

    def test_proposals_never_invent_tokens(self):
        history = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 1, 4]
        p = NGramProposer(history)
        for k in (1, 3, 8):
            for tok in p.propose(k):
                assert tok in history


class TestProposeFor:
    def _slot(self, tokens, stop_ids=()):
        return SlotSpec(proposer=NGramProposer(tokens),
                        stop_ids=frozenset(stop_ids),
                        hasher=TokenBlockSequence(4))

    def test_truncates_at_stop_token(self):
        # Continuation would be [4, 1, 2, ...]; 4 is a stop token, so
        # nothing may be proposed past it (it ends the stream).
        slot = self._slot(REPETITIVE, stop_ids=(4,))
        assert propose_for(slot, None, 4, remaining=100) == [4]

    def test_caps_at_remaining_budget(self):
        slot = self._slot(REPETITIVE)
        # remaining=3: the verify step always emits one extra target, so
        # at most 2 drafts are useful.
        assert len(propose_for(slot, None, 4, remaining=3)) == 2
        assert propose_for(slot, None, 4, remaining=1) == []

    def test_block_lookahead_fallback(self):
        ps = 4
        # A finished sequence's tokens + chained hashes...
        done = list(range(20, 36))
        hashes = compute_block_hashes(done, ps)
        store = BlockLookahead(ps)
        store.record(hashes, done)
        # ...predict a live sequence sharing the first two full blocks
        # (same chained hash) but with NO internal n-gram repetition.
        live = done[: 2 * ps + 2]  # 2 full blocks + 2 tokens into block 3
        slot = self._slot([99])  # proposer with useless history
        slot.proposer = NGramProposer(live)
        slot.hasher = TokenBlockSequence(ps)
        slot.hasher.extend(live)
        got = propose_for(slot, store, 4, remaining=100)
        assert got == done[2 * ps + 2: 2 * ps + 6]

    def test_block_lookahead_bounded(self):
        store = BlockLookahead(4, capacity=2)
        for i in range(5):
            toks = list(range(i * 10, i * 10 + 8))
            store.record(compute_block_hashes(toks, 4), toks)
        assert len(store) <= 2


class TestSpecVerifySampler:
    def test_greedy_accept_prefix(self):
        from dynamo_tpu.engine.sampler import spec_verify

        import jax.numpy as jnp

        b, t, v = 2, 4, 16
        logits = np.full((b, t, v), -10.0, np.float32)
        # Slot 0's target stream: 5, 6, 7, 8; slot 1's: 3, 3, 3, 3.
        for i, tok in enumerate([5, 6, 7, 8]):
            logits[0, i, tok] = 10.0
        logits[1, :, 3] = 10.0
        drafts = np.array([[5, 6, 9], [2, 3, 3]], np.int32)
        zeros = np.zeros(b, np.float32)
        targets, n_acc = spec_verify(
            jnp.asarray(logits), jnp.asarray(drafts), jnp.asarray(zeros),
            jnp.ones(b, jnp.float32), jnp.zeros(b, jnp.int32),
            jnp.zeros(b, jnp.uint32), jnp.zeros(b, jnp.int32))
        assert list(np.asarray(targets)[0]) == [5, 6, 7, 8]
        assert list(np.asarray(targets)[1]) == [3, 3, 3, 3]
        # slot 0: drafts 5,6 match, 9 mismatches -> 2 accepted;
        # slot 1: first draft 2 mismatches -> 0 accepted.
        assert list(np.asarray(n_acc)) == [2, 0]


class TestSpecParity:
    """Speculative output == per-token output, bit-identical, while
    speculation demonstrably engages (nonzero accepted drafts)."""

    def test_greedy_parity_and_engagement(self, monkeypatch):
        req = lambda: _request(REPETITIVE, max_tokens=48)
        base, f0, e0, _ = _serve(req(), False, monkeypatch)
        spec, f1, e1, stats = _serve(req(), True, monkeypatch)
        assert e0 is None and e1 is None
        assert (base, f0) == (spec, f1)
        assert stats.spec_steps > 0
        assert stats.spec_accepted > 0
        assert stats.spec_proposed >= stats.spec_accepted

    def test_temperature_parity(self, monkeypatch):
        req = lambda: _request(REPETITIVE, max_tokens=32, temperature=0.8,
                               seed=1234)
        base, f0, e0, _ = _serve(req(), False, monkeypatch)
        spec, f1, e1, _ = _serve(req(), True, monkeypatch)
        assert e0 is None and e1 is None
        assert (base, f0) == (spec, f1)

    def test_truncation_parity(self, monkeypatch):
        """top-k/top-p truncation goes through the same masked sampler
        on both paths."""
        req = lambda: _request(REPETITIVE, max_tokens=24, temperature=0.7,
                               seed=42, top_k=8, top_p=0.9)
        base, f0, e0, _ = _serve(req(), False, monkeypatch)
        spec, f1, e1, _ = _serve(req(), True, monkeypatch)
        assert e0 is None and e1 is None
        assert (base, f0) == (spec, f1)

    def test_eos_stops_stream_identically(self, monkeypatch):
        """An EOS token generated mid-stream finishes the request at the
        same position with and without speculation (no token leaks past
        the stop from a committed chunk)."""
        base, f0, e0, _ = _serve(
            _request(REPETITIVE, max_tokens=48, eos=[276]),
            False, monkeypatch)
        spec, f1, e1, _ = _serve(
            _request(REPETITIVE, max_tokens=48, eos=[276]),
            True, monkeypatch)
        assert e0 is None and e1 is None
        assert (base, f0) == (spec, f1)
        if f0 == "stop":  # tiny-test greedy does emit 276 here
            assert spec.count(276) == 1 and spec[-1] == 276

    def test_multi_slot_batch_parity(self, monkeypatch):
        """A batch mixing repetitive (speculating) and non-repetitive
        slots stays per-slot identical to the sequential engine."""
        reqs = [
            _request(REPETITIVE, max_tokens=24, seed=3),
            _request(list(range(30, 41)), max_tokens=24, temperature=0.9,
                     seed=9),
            _request([7] * 9, max_tokens=24, seed=5),
        ]

        async def run_all(sched, requests):
            return await asyncio.gather(
                *[_run_one(sched, r) for r in requests])

        def serve_batch(spec):
            import dataclasses
            batch = [dataclasses.replace(r, request_id=uuid.uuid4().hex)
                     for r in reqs]
            import os
            os.environ["DYNT_SPEC_ENABLE"] = "1" if spec else "0"
            os.environ["DYNT_SPEC_MAX_K"] = "3"
            sched = InferenceScheduler(_shared_runner())
            sched.start()
            try:
                return asyncio.run(run_all(sched, batch))
            finally:
                sched.stop()
                os.environ.pop("DYNT_SPEC_ENABLE", None)
                os.environ.pop("DYNT_SPEC_MAX_K", None)

        assert serve_batch(False) == serve_batch(True)


class TestSpecProcessors:
    """Satellite: logits processors must be applied identically on the
    verification path as on the single-token path (the host-verified
    spec leg applies them per position with the same input_ids prefix
    and (seed, step) sampling key)."""

    def test_repetition_penalty_parity(self, monkeypatch):
        req = lambda: _request(REPETITIVE, max_tokens=24, temperature=0.8,
                               seed=11, repetition_penalty=1.3)
        base, f0, e0, _ = _serve(req(), False, monkeypatch)
        spec, f1, e1, _ = _serve(req(), True, monkeypatch)
        assert e0 is None and e1 is None
        assert (base, f0) == (spec, f1)

    def test_min_p_and_bias_parity(self, monkeypatch):
        req = lambda: _request(
            REPETITIVE, max_tokens=20, temperature=0.9, seed=21,
            min_p=0.05, logit_bias={"276": 2.0})
        base, f0, e0, _ = _serve(req(), False, monkeypatch)
        spec, f1, e1, _ = _serve(req(), True, monkeypatch)
        assert e0 is None and e1 is None
        assert (base, f0) == (spec, f1)

    def test_guided_style_mask_respected(self, monkeypatch):
        """A hard-masking processor (forced_response — the guided-DFA
        shape: all but one token at -inf per step) must win over any
        proposal: the output is exactly the forced sequence."""
        forced = [44, 45, 44, 45, 44]
        req = lambda: _request(
            REPETITIVE, max_tokens=16, eos=[500],
            processors=[{"name": "forced_response",
                         "args": {"token_ids": list(forced),
                                  "eos_id": 500}}])
        base, f0, e0, _ = _serve(req(), False, monkeypatch)
        spec, f1, e1, _ = _serve(req(), True, monkeypatch)
        assert e0 is None and e1 is None
        assert base == forced + [500] and f0 == "stop"
        assert (base, f0) == (spec, f1)

    def test_min_tokens_retirement_parity(self, monkeypatch):
        """min_tokens retires its processor mid-stream; the spec path
        must hand back to the device sampler at the same point the
        sequential path does."""
        req = lambda: _request(REPETITIVE, max_tokens=24, eos=[276],
                               min_tokens=6)
        base, f0, e0, _ = _serve(req(), False, monkeypatch)
        spec, f1, e1, _ = _serve(req(), True, monkeypatch)
        assert e0 is None and e1 is None
        assert (base, f0) == (spec, f1)


class TestSpecPolicy:
    def test_batch_cutoff_gates_dispatch(self, monkeypatch):
        """Above the batch-pressure cutoff the spec dispatcher stands
        down (speculation trades FLOPs for latency; at high batch the
        MXU is busy) — white-box: the cutoff check precedes any device
        work, so dummy ready entries suffice."""
        import types

        monkeypatch.setenv("DYNT_SPEC_ENABLE", "1")
        monkeypatch.setenv("DYNT_SPEC_BATCH_CUTOFF", "1")
        sched = InferenceScheduler(_shared_runner())  # never started
        assert sched.spec_cutoff == 1
        ready = [types.SimpleNamespace(first_deferred=False)
                 for _ in range(2)]
        assert sched._maybe_dispatch_spec(ready, False, False) is None
        assert sched.stats.spec_last_k == 0

    def test_min_ema_gates_proposing_with_probes(self, monkeypatch):
        """A slot whose acceptance EMA fell below the floor stops
        proposing but probes on the PROBE_EVERY cadence."""
        from dynamo_tpu.engine.spec import PROBE_EVERY

        monkeypatch.setenv("DYNT_SPEC_ENABLE", "1")
        slot = SlotSpec(proposer=NGramProposer(REPETITIVE),
                        stop_ids=frozenset(),
                        hasher=TokenBlockSequence(4))
        slot.ema = 0.01  # below any sane floor
        probes = sum(1 for _ in range(PROBE_EVERY * 3)
                     if slot.wants_probe())
        assert probes == 3

    def test_spec_off_keeps_path_untouched(self, monkeypatch):
        toks, _, _, stats = _serve(
            _request(REPETITIVE, max_tokens=32), False, monkeypatch)
        assert stats.spec_steps == 0
        assert stats.spec_proposed == 0

    def test_flight_recorder_spec_event(self, monkeypatch):
        from dynamo_tpu.runtime.flight_recorder import get_recorder

        monkeypatch.setenv("DYNT_SPEC_ENABLE", "1")
        monkeypatch.setenv("DYNT_SPEC_MAX_K", "3")
        rid = uuid.uuid4().hex
        rec = get_recorder()
        rec.start(rid, model="tiny-test")
        sched = InferenceScheduler(_shared_runner())
        sched.start()
        try:
            req = _request(REPETITIVE, max_tokens=32)
            loop_toks = []

            async def go():
                loop = asyncio.get_running_loop()
                queue = asyncio.Queue()
                sched.submit(
                    req,
                    lambda o: loop.call_soon_threadsafe(
                        queue.put_nowait, o),
                    record_id=rid)
                while True:
                    out = await asyncio.wait_for(queue.get(), 60)
                    loop_toks.extend(out.token_ids)
                    if out.finish_reason is not None:
                        return

            asyncio.run(go())
            # Reap happens on the scheduler thread right after the
            # finish emit; give it a beat.
            import time
            deadline = time.time() + 10
            events = []
            while time.time() < deadline:
                timeline = rec.get(rid)
                events = [e for e in getattr(timeline, "events", [])
                          if e.get("event") == "spec"]
                if events:
                    break
                time.sleep(0.05)
        finally:
            sched.stop()
            rec.finish(rid, "ok")
        assert events, "no spec event on the request timeline"
        assert events[-1]["proposed"] >= events[-1]["accepted"] > 0


class TestSpecKernelInterpret:
    """Interpret-mode Pallas verification-kernel tests on CPU against
    the XLA reference attention path."""

    @pytest.mark.parametrize("t", [1, 3, 5])
    def test_spec_kernel_matches_xla_oracle(self, t):
        import jax.numpy as jnp

        from dynamo_tpu.models.transformer import paged_attention_spec_xla
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_spec,
            paged_attention_spec_pool,
        )

        rng = np.random.default_rng(0)
        layers, pages, ps, kh, hd = 2, 16, 8, 2, 32
        b, qh = 3, 4
        kv = jnp.asarray(
            rng.standard_normal((layers, 2, pages, ps, kh, hd)),
            jnp.float32)
        q = jnp.asarray(rng.standard_normal((b, t, qh, hd)), jnp.float32)
        kc = jnp.asarray(rng.standard_normal((b, t, kh, hd)), jnp.float32)
        vc = jnp.asarray(rng.standard_normal((b, t, kh, hd)), jnp.float32)
        tables = jnp.asarray(
            rng.permutation(np.arange(1, 13)).reshape(3, 4), jnp.int32)
        # kv_lens include the empty-history edge (len 1 = chunk only).
        kv_lens = jnp.asarray([1, 9, 25], jnp.int32)
        ref = paged_attention_spec_xla(q, kv, 1, tables, kv_lens, kc, vc)
        out = paged_attention_spec(q, kv, 1, tables, kv_lens, kc, vc,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)
        pool = paged_attention_spec_pool(
            q, kv, jnp.int32(1), tables, kv_lens, kc, vc, interpret=True)
        np.testing.assert_allclose(np.asarray(pool), np.asarray(ref),
                                   atol=1e-4)

    def test_spec_pool_kernel_q8_matches_xla_oracle(self):
        """The int8 (values, scales) pool — the flagship's KV format —
        through the q8 spec variant vs the XLA dequant oracle."""
        import jax.numpy as jnp

        from dynamo_tpu.models.transformer import (
            paged_attention_spec_xla,
            quantize_kv,
        )
        from dynamo_tpu.ops.paged_attention import paged_attention_spec_pool

        rng = np.random.default_rng(2)
        layers, pages, ps, kh, hd = 2, 16, 8, 2, 32
        b, t, qh = 2, 3, 4
        raw = jnp.asarray(
            rng.standard_normal((layers, 2, pages, ps, kh, hd)),
            jnp.float32)
        kv = quantize_kv(raw)  # (int8 values, lane-broadcast bf16 scales)
        q = jnp.asarray(rng.standard_normal((b, t, qh, hd)), jnp.float32)
        kc = jnp.asarray(rng.standard_normal((b, t, kh, hd)), jnp.float32)
        vc = jnp.asarray(rng.standard_normal((b, t, kh, hd)), jnp.float32)
        tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        kv_lens = jnp.asarray([7, 21], jnp.int32)
        ref = paged_attention_spec_xla(q, kv, 1, tables, kv_lens, kc, vc)
        out = paged_attention_spec_pool(
            q, kv, jnp.int32(1), tables, kv_lens, kc, vc, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)

class TestCombineChunk:
    def test_combine_chunk_causality(self):
        """The chunk combine must be causal: query i's output is
        independent of chunk tokens j > i (checked without the kernel —
        pure XLA partials, runs on any jax)."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.paged_attention import _combine_chunk

        rng = np.random.default_rng(1)
        b, t, kh, g, hd = 2, 4, 2, 2, 8
        qh = kh * g
        q = jnp.asarray(rng.standard_normal((b, t, qh, hd)), jnp.float32)
        acc = jnp.zeros((b, t, kh, g, hd), jnp.float32)
        m = jnp.full((b, t, kh, g), -jnp.inf)
        l = jnp.zeros((b, t, kh, g), jnp.float32)
        kc = rng.standard_normal((b, t, kh, hd)).astype(np.float32)
        vc = rng.standard_normal((b, t, kh, hd)).astype(np.float32)
        base = np.asarray(_combine_chunk(q, acc, m, l, jnp.asarray(kc),
                                         jnp.asarray(vc)))
        kc2, vc2 = kc.copy(), vc.copy()
        kc2[:, -1] += 100.0  # perturb ONLY the last chunk token
        vc2[:, -1] += 100.0
        pert = np.asarray(_combine_chunk(q, acc, m, l, jnp.asarray(kc2),
                                         jnp.asarray(vc2)))
        np.testing.assert_allclose(pert[:, :-1], base[:, :-1], atol=1e-5)
        assert not np.allclose(pert[:, -1], base[:, -1])


class TestMockerSpecProfile:
    def test_spec_profile_multi_token_steps(self):
        import dataclasses

        from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine

        async def go():
            engine = MockerEngine(MockerConfig(
                speedup_ratio=1000.0, spec_k=4, spec_acceptance=1.0))
            req = PreprocessedRequest(
                request_id=uuid.uuid4().hex, token_ids=list(range(16)),
                sampling=SamplingOptions(max_tokens=20),
                stop=StopConditions(ignore_eos=True))
            frames = []
            async for item in engine.generate(req.to_wire()):
                frames.append(item)
            await engine.close()
            return engine, frames

        engine, frames = asyncio.run(go())
        toks = [t for f in frames for t in (f.get("t") or [])]
        assert len(toks) == 20  # exact budget despite multi-token steps
        # acceptance=1.0 -> every step commits 1 + k tokens
        assert any(len(f.get("t") or []) > 1 for f in frames)
        assert engine.spec_proposed > 0
        assert engine.spec_accepted == engine.spec_proposed

    def test_timing_preset_and_report_stats(self):
        from dynamo_tpu.mocker.engine import (
            TIMING_PRESETS,
            MockerConfig,
        )
        from dynamo_tpu.mocker.loadgen import (
            OfflineReplay,
            synthesize_trace,
        )

        assert "tpu-v5e-qwen3-0.6b-spec" in TIMING_PRESETS
        cfg = MockerConfig.from_timing_preset(
            "tpu-v5e-qwen3-0.6b-spec", speedup_ratio=500.0)
        assert cfg.spec_k > 0 and 0 < cfg.spec_acceptance < 1

        records = synthesize_trace(8, rate_rps=200.0, isl_mean=48,
                                   osl_mean=24, seed=3)
        report = asyncio.run(OfflineReplay(config=cfg).run(records))
        summary = report.summary()
        assert summary["errors"] == 0
        assert summary["spec"]["proposed"] > 0
        assert 0 < summary["spec"]["acceptance_rate"] <= 1

    def test_spec_profile_faster_than_plain(self):
        """The speculative profile's modeled step physics must deliver
        more tokens per modeled second than the plain profile (the
        planner sees speculation as real throughput)."""
        from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine

        plain = MockerConfig.from_timing_preset("tpu-v5e-qwen3-0.6b")
        spec = MockerConfig.from_timing_preset("tpu-v5e-qwen3-0.6b-spec")
        # tokens per modeled step second at bs=1, ~256-token context:
        eng_p = MockerEngine(plain)
        eng_s = MockerEngine(spec)
        step_p = eng_p._step_time(0, 1, 16)
        step_s = eng_s._step_time(0, 1, 16)
        # expected tokens per spec step at per-position acceptance p:
        p, k = spec.spec_acceptance, spec.spec_k
        exp_tokens = 1 + p * (1 - p ** k) / (1 - p)
        assert exp_tokens / step_s > 1.0 / step_p
