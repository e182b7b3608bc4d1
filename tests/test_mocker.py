"""Mocker engine tests: continuous batching, prefix cache, KV events
(ref contract: lib/mocker scheduler + kv_manager behavior)."""

import asyncio

from dynamo_tpu.kv_router.protocols import KV_EVENT_TOPIC, RouterEvent
from dynamo_tpu.llm.protocols import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.mocker import MockerConfig, MockerEngine


def _request(tokens, max_tokens=8, rid="r1"):
    return PreprocessedRequest(
        request_id=rid,
        token_ids=list(tokens),
        sampling=SamplingOptions(max_tokens=max_tokens),
        stop=StopConditions(),
    ).to_wire()


class _CapturePublisher:
    def __init__(self):
        self.events = []

    async def publish(self, topic, payload):
        self.events.append((topic, payload))


def _fast_config(**kwargs):
    defaults = dict(speedup_ratio=1000.0, block_size=16, num_blocks=64,
                    max_batch=8)
    defaults.update(kwargs)
    return MockerConfig(**defaults)


class TestMockerEngine:
    def test_generates_exactly_max_tokens(self, run):
        async def body():
            engine = MockerEngine(_fast_config())
            outs = [EngineOutput.from_wire(o)
                    async for o in engine.generate(_request(range(40), 5))]
            tokens = [t for o in outs for t in o.token_ids]
            assert len(tokens) == 5
            assert outs[-1].finish_reason == "length"
            assert outs[0].prompt_tokens == 40
            await engine.close()

        run(body())

    def test_concurrent_requests_batched(self, run):
        async def body():
            engine = MockerEngine(_fast_config())

            async def one(rid):
                outs = [o async for o in engine.generate(
                    _request(range(32), 6, rid=rid))]
                return sum(len(o["t"]) for o in outs)

            counts = await asyncio.gather(*[one(f"r{i}") for i in range(6)])
            assert counts == [6] * 6
            # Batched: far fewer steps than 6 sequential requests would take.
            assert engine.steps < 6 * 10
            await engine.close()

        run(body())

    def test_kv_events_published_and_prefix_reused(self, run):
        async def body():
            pub = _CapturePublisher()
            engine = MockerEngine(_fast_config(), worker_id=42,
                                  event_publisher=pub)
            prompt = list(range(48))  # 3 full blocks
            async for _ in engine.generate(_request(prompt, 4, "a")):
                pass
            stored = [RouterEvent.from_wire(p) for t, p in pub.events
                      if t == KV_EVENT_TOPIC]
            assert stored and stored[0].stored is not None
            assert len(stored[0].stored.block_hashes) == 3
            assert stored[0].worker_id == 42

            # Second request with same prefix: cache hit -> fewer new blocks.
            usage_before = engine.kv.usage()
            async for _ in engine.generate(_request(prompt, 4, "b")):
                pass
            # No duplicate stored events for the same blocks.
            stored2 = [RouterEvent.from_wire(p) for t, p in pub.events
                       if t == KV_EVENT_TOPIC]
            all_hashes = [h for e in stored2 if e.stored
                          for h in e.stored.block_hashes]
            assert len(all_hashes) == len(set(all_hashes))
            await engine.close()

        run(body())

    def test_eviction_emits_removed_events(self, run):
        async def body():
            pub = _CapturePublisher()
            # Tiny pool: 8 blocks; requests of 3 blocks + decode room force
            # eviction of previous cached prefixes.
            engine = MockerEngine(_fast_config(num_blocks=8), worker_id=1,
                                  event_publisher=pub)
            for i in range(4):
                prompt = list(range(i * 100, i * 100 + 48))
                async for _ in engine.generate(_request(prompt, 4, f"r{i}")):
                    pass
            removed = [RouterEvent.from_wire(p) for t, p in pub.events
                       if t == KV_EVENT_TOPIC]
            assert any(e.removed for e in removed)
            await engine.close()

        run(body())

    def test_load_metrics(self, run):
        async def body():
            engine = MockerEngine(_fast_config())
            metrics = engine.load_metrics()
            assert metrics.total_blocks == 64
            assert metrics.active_requests == 0
            await engine.close()

        run(body())

    def test_cancellation_frees_slot(self, run):
        async def body():
            engine = MockerEngine(_fast_config(speedup_ratio=1.0))
            gen = engine.generate(_request(range(16), 1000, "slow"))
            got = await gen.__anext__()
            await gen.aclose()
            # Next step should drop the cancelled sequence.
            for _ in range(100):
                if not engine._running:
                    break
                await asyncio.sleep(0.02)
            assert not engine._running
            await engine.close()

        run(body())


class TestTimingFidelity:
    """The v5e timing preset must reproduce the step times its one
    chip probe measured (the table below, from the r3/r4 sections of
    `git show 6b5a9d4:BASELINE.md`) within 20%
    — the bar for planner/SLA validation against the mocker (ref:
    lib/mocker vllm core.rs timing model fidelity)."""

    PROBE_TABLE = [
        # (batch, ctx_tokens, measured us/step on v5e)
        (8, 0, 2580.0),
        (16, 0, 3298.0),
        (32, 0, 5241.0),
        (8, 256, 3203.0),
    ]

    def test_preset_matches_probe_within_20pct(self):
        from dynamo_tpu.mocker.engine import MockerConfig

        cfg = MockerConfig.from_timing_preset("tpu-v5e-qwen3-0.6b")
        eng = MockerEngine(cfg, worker_id=0)
        try:
            for bs, ctx, measured in self.PROBE_TABLE:
                blocks = bs * (-(-ctx // cfg.block_size))
                model = eng._step_time(0, bs, blocks) * 1e6
                err = abs(model - measured) / measured
                assert err < 0.20, (bs, ctx, model, measured, err)
        finally:
            eng._closed = True

    def test_derived_profile_consistent(self):
        from dynamo_tpu.mocker.engine import derive_decode_profile

        prof = derive_decode_profile("tpu-v5e-qwen3-0.6b")
        # throughput rises with batch at fixed context...
        t = {(k, c): v for k, c, v in zip(prof["x_kv_usage"],
                                          prof["y_context_length"],
                                          prof["z_thpt_per_chip"])}
        itl = {(k, c): v for k, c, v in zip(prof["x_kv_usage"],
                                            prof["y_context_length"],
                                            prof["z_itl"])}
        by_ctx = {}
        for (k, c), v in t.items():
            by_ctx.setdefault(c, []).append((k, v))
        for c, rows in by_ctx.items():
            rows.sort()
            thpts = [v for _k, v in rows]
            assert thpts == sorted(thpts)  # more batch -> more tok/s
        # ...and ITL grows with context at fixed batch share
        assert max(itl.values()) > min(itl.values())


class TestMockerPreemption:
    """Chip-free QoS plane (docs/multi-tenancy.md): interactive
    arrivals preempt batch decode slots; parked sequences resume and
    still deliver their full token budget."""

    def _request(self, tokens, max_tokens, rid, priority="standard"):
        return PreprocessedRequest(
            request_id=rid,
            token_ids=list(tokens),
            sampling=SamplingOptions(max_tokens=max_tokens),
            stop=StopConditions(),
            priority=priority,
        ).to_wire()

    def _one_preemption(self, run):
        """One slot, a batch stream decoding in it, then an interactive
        arrival that MUST preempt to run."""
        async def body():
            engine = MockerEngine(_fast_config(max_batch=1,
                                               speedup_ratio=50.0))

            async def one(req):
                outs = [EngineOutput.from_wire(o)
                        async for o in engine.generate(req)]
                return [t for o in outs for t in o.token_ids], outs[-1]

            batch_task = asyncio.create_task(one(self._request(
                range(32), 24, "batch-1", priority="batch")))
            # Let the batch request start decoding.
            for _ in range(200):
                await asyncio.sleep(0.005)
                if engine._running and engine._running[0].generated >= 1:
                    break
            inter_tokens, _ = await one(self._request(
                range(64, 96), 4, "inter-1", priority="interactive"))
            batch_tokens, batch_last = await batch_task
            await engine.close()
            return engine, inter_tokens, batch_tokens, batch_last

        return run(body())

    def test_interactive_preempts_batch_slot(self, run):
        engine, inter_tokens, batch_tokens, batch_last = \
            self._one_preemption(run)
        assert engine.preempt_parked >= 1
        assert engine.preempt_resumed == engine.preempt_parked
        assert len(inter_tokens) == 4
        # The preempted batch stream still delivers every token.
        assert len(batch_tokens) == 24
        assert batch_last.finish_reason == "length"
        assert not engine._parked

    def test_a_park_and_its_resume_reach_dynamo_preempt_total(self, run):
        """The counter `mocker/overload.py`'s `preemptions_observed`
        reads: the mocker swallows a metrics error, so a label set that
        no longer matches the family's would lose the count silently."""
        from dynamo_tpu.runtime.metrics import PREEMPT_TOTAL

        def count(kind, group):
            return PREEMPT_TOTAL.labels(kind=kind, group=group)._value.get()

        before = count("park", "slot"), count("resume", "full")
        engine, *_ = self._one_preemption(run)
        assert count("park", "slot") - before[0] == engine.preempt_parked >= 1
        assert count("resume", "full") - before[1] == engine.preempt_resumed

    def test_waiting_order_is_class_strict(self, run, monkeypatch):
        # No preemption: this test pins pure ADMISSION order, so the
        # standard-class warm request must keep its slot.
        monkeypatch.setenv("DYNT_PREEMPT_ENABLE", "0")

        async def body():
            # Real-time step pacing (speedup 1): the warm request holds
            # the single slot long enough for both later arrivals to
            # queue behind it.
            engine = MockerEngine(_fast_config(max_batch=1,
                                               speedup_ratio=1.0))
            order = []

            async def one(req, tag):
                outs = [o async for o in engine.generate(req)]
                order.append(tag)
                return outs

            warm = asyncio.create_task(one(self._request(
                range(32), 30, "warm"), "warm"))
            await asyncio.sleep(0.05)
            # Batch arrives first, interactive second — interactive
            # must still admit (and finish) first.
            t_batch = asyncio.create_task(one(self._request(
                range(32, 64), 2, "b", priority="batch"), "b"))
            await asyncio.sleep(0.02)
            t_inter = asyncio.create_task(one(self._request(
                range(96, 128), 2, "i", priority="interactive"), "i"))
            await asyncio.gather(warm, t_batch, t_inter)
            await engine.close()
            assert order.index("i") < order.index("b")

        run(body())

    def test_preempt_disabled_keeps_fcfs(self, run, monkeypatch):
        monkeypatch.setenv("DYNT_PREEMPT_ENABLE", "0")

        async def body():
            engine = MockerEngine(_fast_config(max_batch=1,
                                               speedup_ratio=50.0))

            async def one(req):
                return [o async for o in engine.generate(req)]

            batch_task = asyncio.create_task(one(self._request(
                range(32), 16, "batch-2", priority="batch")))
            await asyncio.sleep(0.05)
            await one(self._request(range(64, 96), 2, "inter-2",
                                    priority="interactive"))
            await batch_task
            await engine.close()
            assert engine.preempt_parked == 0

        run(body())


class TestMockerDoubleDrain:
    def test_rolling_restart_handoff_chain_stays_bit_identical(self, run):
        """Rolling restart: a stream handed off A->B must survive a
        SECOND drain B->C with its FULL committed history — B inherits
        the handed-off tokens as delivered, so B's own handoff frame
        ships inherited + locally-delivered tokens, and C's
        continuation matches an undrained run byte-for-byte."""

        async def body():
            prompt = list(range(40))
            # Undrained oracle: one engine, straight through.
            oracle_engine = MockerEngine(_fast_config(speedup_ratio=50.0))
            oracle = [t for o in [EngineOutput.from_wire(w) async for w in
                                  oracle_engine.generate(
                                      _request(prompt, 24, "oracle"))]
                      for t in o.token_ids]
            await oracle_engine.close()
            assert len(oracle) == 24

            async def drain_mid_stream(engine, req, min_delivered):
                outs = []

                async def consume():
                    async for w in engine.generate(req):
                        outs.append(EngineOutput.from_wire(w))

                task = asyncio.create_task(consume())
                for _ in range(400):
                    await asyncio.sleep(0.005)
                    seqs = list(engine._running)
                    if seqs and seqs[0].delivered >= min_delivered:
                        break
                engine.drain_sweep()
                await task
                assert outs[-1].finish_reason == "migrate"
                params = outs[-1].kv_transfer_params
                assert params and params.get("handoff") is not None
                tokens = [t for o in outs for t in o.token_ids]
                return tokens, params

            # Hop 1: engine A drains mid-decode.
            a = MockerEngine(_fast_config(speedup_ratio=2.0))
            got_a, params_a = await drain_mid_stream(
                a, _request(prompt, 24, "roll"), min_delivered=4)
            await a.close()
            assert got_a == params_a["handoff"]["generated"]

            # Hop 2: engine B resumes from A's frame, then drains too.
            # The Migration handoff re-dispatches the SAME request (the
            # total budget; the destination counts generated from the
            # inherited history), only swapping in the pull params.
            req_b = PreprocessedRequest(
                request_id="roll", token_ids=list(prompt),
                sampling=SamplingOptions(max_tokens=24),
                stop=StopConditions(),
                disaggregated_params=params_a).to_wire()
            b = MockerEngine(_fast_config(speedup_ratio=2.0))
            got_b, params_b = await drain_mid_stream(
                b, req_b, min_delivered=len(got_a) + 4)
            await b.close()
            # B's handoff frame must carry inherited + local history.
            assert params_b["handoff"]["generated"] == got_a + got_b

            # Hop 3: engine C finishes the stream.
            req_c = PreprocessedRequest(
                request_id="roll", token_ids=list(prompt),
                sampling=SamplingOptions(max_tokens=24),
                stop=StopConditions(),
                disaggregated_params=params_b).to_wire()
            c = MockerEngine(_fast_config(speedup_ratio=50.0))
            got_c = [t for o in [EngineOutput.from_wire(w) async for w in
                                 c.generate(req_c)]
                     for t in o.token_ids]
            await c.close()
            assert got_a + got_b + got_c == oracle

        run(body())
