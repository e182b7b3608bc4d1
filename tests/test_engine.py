"""JAX engine tests on the virtual CPU mesh: model correctness, page pool,
scheduler, end-to-end worker (tiny model; ref contract: engine-side behavior
the reference gets from vLLM — continuous batching, prefix cache, streaming)."""

import asyncio
import uuid

import numpy as np
import pytest

import jax

from dynamo_tpu.engine import (
    InferenceScheduler,
    ModelRunner,
    PagePool,
    RunnerConfig,
    TpuWorker,
)
from dynamo_tpu.llm.protocols import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import get_config
from dynamo_tpu.parallel import MeshConfig, make_mesh


def _runner(max_batch=4, num_pages=64, page_size=4, max_pages=16):
    return ModelRunner(
        get_config("tiny-test"),
        RunnerConfig(page_size=page_size, num_pages=num_pages,
                     max_batch=max_batch, max_pages_per_seq=max_pages,
                     prefill_buckets=(8, 16, 32)),
        make_mesh(MeshConfig()),
        seed=0,
    )


def _request(tokens, max_tokens=4, rid=None, temperature=0.0, seed=0):
    return PreprocessedRequest(
        request_id=rid or uuid.uuid4().hex,
        token_ids=list(tokens),
        sampling=SamplingOptions(max_tokens=max_tokens,
                                 temperature=temperature, seed=seed),
        stop=StopConditions(ignore_eos=True),
    )


class TestPagePool:
    def test_allocate_and_release_roundtrip(self):
        pool = PagePool(16)
        alloc = pool.allocate([1, 2, 3], total_pages=5)
        assert alloc is not None
        assert len(alloc.new_pages) == 5 and alloc.cached_blocks == 0
        assert pool.free_count() == 10
        pool.release(alloc, [1, 2, 3])
        # 3 pages cached under hashes, 2 freed
        assert pool.cached_count() == 3
        assert pool.free_count() == 12

    def test_prefix_reuse(self):
        stored = []
        pool = PagePool(16, on_stored=lambda h, p: stored.append((h, p)))
        a1 = pool.allocate([1, 2], total_pages=3)
        pool.release(a1, [1, 2])
        assert stored == [([1, 2], None)]
        a2 = pool.allocate([1, 2, 3], total_pages=4)
        assert a2.cached_blocks == 2
        assert len(a2.new_pages) == 2
        pool.release(a2, [1, 2, 3])
        assert stored[-1] == ([3], 2)

    def test_eviction_lru(self):
        removed = []
        pool = PagePool(8, on_removed=lambda h: removed.extend(h))
        a1 = pool.allocate([1, 2, 3], 3)
        pool.release(a1, [1, 2, 3])
        a2 = pool.allocate([4, 5, 6], 3)
        pool.release(a2, [4, 5, 6])
        assert pool.free_count() == 1
        # Allocating 4 new pages must evict the LRU hashes (1,2,3 first).
        a3 = pool.allocate([7, 8], 4)
        assert a3 is not None
        assert removed[:3] == [1, 2, 3]

    def test_pinned_pages_not_evicted(self):
        pool = PagePool(8)
        a1 = pool.allocate([1, 2, 3], 3)
        pool.release(a1, [1, 2, 3])
        a2 = pool.allocate([1, 2, 3], 4)  # pins 1,2,3
        assert a2.cached_blocks == 3
        # Only 3 free pages (+0 evictable) left; a request needing 5 fails.
        assert pool.allocate([9], 5) is None

    def test_oversize_returns_none(self):
        pool = PagePool(4)
        assert pool.allocate([], 10) is None

    def test_eviction_never_frees_just_matched_prefix(self):
        """Regression: allocate() must pin the matched prefix before
        evicting, or eviction can free the pages the request reuses."""
        pool = PagePool(8)  # 7 usable pages
        a1 = pool.allocate([1, 2, 3], 3)
        pool.release(a1, [1, 2, 3])
        a2 = pool.allocate([4, 5, 6, 7], 4)
        pool.release(a2, [4, 5, 6, 7])
        assert pool.free_count() == 0
        # Matches [1,2,3] (the LRU-oldest cached blocks) and needs 3 more
        # pages, which forces eviction while the match is live.
        a3 = pool.allocate([1, 2, 3], 6)
        assert a3 is not None
        assert a3.cached_blocks == 3
        assert set(a3.cached_pages).isdisjoint(set(a3.new_pages))
        # the matched hashes must still be cached (not evicted)
        assert pool.match_prefix([1, 2, 3]) == 3

    def test_failed_allocate_unpins_prefix(self):
        pool = PagePool(6)  # 5 usable
        a1 = pool.allocate([1, 2], 2)
        pool.release(a1, [1, 2])
        # needs 8 new pages: impossible -> None, and [1,2] must be unpinned
        assert pool.allocate([1, 2], 10) is None
        a2 = pool.allocate([9, 10], 5)  # evicting 1,2 must be possible
        assert a2 is not None

    def test_evict_clears_refcount_entries(self):
        pool = PagePool(8)
        a1 = pool.allocate([1, 2, 3], 3)
        pool.release(a1, [1, 2, 3])
        pool._evict(3)
        assert all(h not in pool._refcount for h in (1, 2, 3))

    def test_release_clamps_to_computed_blocks(self):
        """Regression: a cancelled sequence must not register blocks whose
        KV was never computed."""
        stored = []
        pool = PagePool(16, on_stored=lambda h, p: stored.append(list(h)))
        alloc = pool.allocate([1, 2, 3, 4], 6)
        pool.release(alloc, [1, 2, 3, 4], computed_blocks=2)
        assert stored == [[1, 2]]
        assert pool.match_prefix([1, 2, 3, 4]) == 2
        # all non-registered pages returned to the free list
        assert pool.free_count() + pool.cached_count() == 15


class TestWindowPool:
    """The window page group's free list (a model with window AND full
    attention layers): reserved at admission, allocated ahead of a
    launch, freed behind the window while the sequence lives."""

    PAGE, WINDOW = 16, 1024

    def pool(self, pages=5120):
        from dynamo_tpu.engine.pages import WindowPool

        return WindowPool(pages, self.PAGE, self.WINDOW)

    def test_the_bounds_a_row_is_held_to(self):
        pool = self.pool()
        # a decoding row: the window and the 16 positions a fused block
        # and its pipelined second may write, wherever a page starts
        assert pool.bound(16) == 1024 // 16 + 2 == 66
        assert pool.bound(1) == 65
        # a row inside a prefill chunk of C tokens: (1024 + C) / 16 + 1
        assert [pool.bound(c) for c in (256, 1024, 2048)] == [
            81, 129, 193]

    @pytest.mark.parametrize("chunk,lookahead", [(2048, 16), (512, 8),
                                                 (1000, 1)])
    def test_a_row_never_holds_more_than_its_bound(self, chunk, lookahead):
        """A 7,000-token prompt in chunks, then 500 decode launches of
        `lookahead` positions: the pages held at every launch against the
        bound of its phase, every freed page back on the free list."""
        pool = self.pool(400)
        lease = pool.reserve(pool.bound(lookahead))
        pos = 0
        while pos < 7000:
            c = min(chunk, 7000 - pos)
            assert pool.advance(lease, max(0, pos - self.WINDOW + 1),
                                pos + c - 1, "prefill")
            assert len(lease.pages) <= pool.bound(c)
            assert lease.first * 16 <= max(0, pos - self.WINDOW + 1)
            pos += c
            # behind the launch, what its next one cannot see goes back
            pool.advance(lease, max(0, pos - self.WINDOW + 1), pos - 1,
                         "prefill")
            assert len(lease.pages) <= 65
        for _ in range(500):
            assert pool.advance(lease, pos - self.WINDOW + 1,
                                pos + lookahead - 1, "decode")
            held = len(lease.pages)
            assert held <= pool.bound(lookahead) <= 66
            # the table's frame: column 0 holds the window's oldest token
            assert lease.first == (pos - self.WINDOW + 1) // 16
            assert held + pool.free_count() == 399
            pos += lookahead
        assert len(set(lease.pages)) == len(lease.pages)
        # a page behind for every 16 positions the window's edge moved
        for phase in ("prefill", "decode"):
            per = pool.freed_behind[phase] / pool.edge_tokens[phase]
            assert abs(per - 1 / 16) < 0.003, (phase, per)
        pool.release(lease)
        assert pool.free_count() == pool.unreserved() == 399
        assert sorted(pool._free) == list(range(1, 400))

    def test_admission_reserves_and_a_decoding_row_never_waits(self):
        """64 rows x 66 reserved of 5,119: the 65th finds no room; a
        prefill chunk takes from the remainder and goes without when it
        is spent; a decoding row's pages are always there."""
        pool = self.pool()
        leases = [pool.reserve(66) for _ in range(77)]
        assert pool.unreserved() == 5119 - 77 * 66 == 37
        assert pool.reserve(66) is None and pool.alloc_fail == 1
        # a chunk of 2,048 from position 0 needs 128 pages: 66 are its
        # own, 62 come from the 37 that are left: refused, nothing taken
        assert not pool.advance(leases[0], 0, 2047, "prefill")
        assert leases[0].pages == [] and pool.alloc_fail == 2
        assert pool.advance(leases[0], 0, 16 * (66 + 37) - 1, "prefill")
        assert pool.unreserved() == 0
        # every other row still decodes from its reservation
        for lease in leases[1:]:
            assert pool.advance(lease, 0, 1039, "decode")
            assert len(lease.pages) == 65
        assert not pool.advance(leases[1], 0, 1024 + 32, "prefill")
        # row 0 decodes on: its surplus returns as its window moves
        pos = 16 * (66 + 37)
        assert pool.advance(leases[0], pos - 1023, pos + 15, "decode")
        assert len(leases[0].pages) <= 66 and pool.unreserved() > 30

    def test_release_and_preemption_leave_the_free_list_whole(self):
        pool = self.pool(300)
        a, b = pool.reserve(66), pool.reserve(40)
        assert pool.advance(a, 0, 2999, "prefill")
        assert pool.advance(b, 0, 500, "prefill")
        pool.release(b)  # preempted (cooperative migrate) mid-prefill
        assert b.pages == [] and b.reserved == 0
        pool.release(a)
        assert pool.free_count() == pool.unreserved() == 299
        assert len(set(pool._free)) == 299 and 0 not in pool._free
        c = pool.reserve(66)  # and what was freed is allocated again
        assert pool.advance(c, 0, 1055, "decode") and len(c.pages) == 66


@pytest.fixture(scope="module")
def runner():
    return _runner()


class TestModelRunner:
    def test_greedy_decode_deterministic(self, runner):
        bt = np.zeros(16, np.int32)
        bt[:4] = [1, 2, 3, 4]
        tok1 = runner.prefill_chunk(np.arange(8, dtype=np.int32), 0, bt, 8,
                                    (0.0, 1.0, 0, 0))
        tok2 = runner.prefill_chunk(np.arange(8, dtype=np.int32), 0, bt, 8,
                                    (0.0, 1.0, 0, 0))
        assert tok1 == tok2
        assert 0 <= tok1 < 512

    def test_sampled_decode_varies_with_seed(self, runner):
        bt = np.zeros(16, np.int32)
        bt[:4] = [5, 6, 7, 8]
        toks = {
            runner.prefill_chunk(np.arange(8, dtype=np.int32), 0, bt, 8,
                                 (5.0, 1.0, 0, seed))
            for seed in range(12)
        }
        assert len(toks) > 1  # high temperature: not all identical

    def test_seeded_sampling_reproducible_across_runner_state(self, runner):
        """Regression: the sampling key must depend only on (seed, per-slot
        step index), not on the runner-global decode counter."""
        bt = np.zeros((1, 16), np.int32)
        bt[0, :4] = [9, 10, 11, 12]
        args = dict(
            positions=np.array([7], np.int32),
            block_tables=bt, kv_lens=np.array([8], np.int32),
            active=np.array([True]),
            temperature=np.array([5.0], np.float32),
            top_p=np.array([1.0], np.float32),
            top_k=np.array([0], np.int32),
            seeds=np.array([42], np.uint32),
            steps=np.array([3], np.int32),
        )
        t1 = runner.decode(np.array([5], np.int32), **args)
        # interleave unrelated decode steps to advance global state
        for _ in range(3):
            runner.decode(np.array([1], np.int32), **{
                **args, "seeds": np.array([7], np.uint32),
                "steps": np.array([9], np.int32)})
        t2 = runner.decode(np.array([5], np.int32), **args)
        assert int(t1[0]) == int(t2[0])


class TestScheduler:
    def test_single_request_stream(self, run, runner):
        async def body():
            sched = InferenceScheduler(runner)
            sched.start()
            loop = asyncio.get_running_loop()
            queue = asyncio.Queue()
            sched.submit(
                _request(range(10), max_tokens=5),
                lambda o: loop.call_soon_threadsafe(queue.put_nowait, o),
            )
            tokens = []
            while True:
                out = await asyncio.wait_for(queue.get(), 30)
                tokens.extend(out.token_ids)
                if out.finish_reason is not None:
                    assert out.finish_reason == "length"
                    break
            assert len(tokens) == 5
            sched.stop()

        run(body(), timeout=120)

    def test_concurrent_requests_and_page_reuse(self, run, runner):
        async def body():
            sched = InferenceScheduler(runner)
            sched.start()
            loop = asyncio.get_running_loop()

            async def one(prompt, n):
                queue = asyncio.Queue()
                sched.submit(
                    _request(prompt, max_tokens=n),
                    lambda o: loop.call_soon_threadsafe(queue.put_nowait, o),
                )
                toks = []
                while True:
                    out = await asyncio.wait_for(queue.get(), 60)
                    toks.extend(out.token_ids)
                    if out.finish_reason is not None:
                        return toks

            shared = list(range(40, 52))  # 3 full pages of 4
            results = await asyncio.gather(
                one(shared, 3), one(shared, 3), one(list(range(9)), 3),
            )
            assert all(len(r) == 3 for r in results)
            # Shared prefix must be cached after completion.
            assert sched.pool.cached_count() >= 3
            sched.stop()

        run(body(), timeout=120)

    def test_greedy_result_matches_with_and_without_cache_hit(self, run, runner):
        async def body():
            sched = InferenceScheduler(runner)
            sched.start()
            loop = asyncio.get_running_loop()

            async def one(prompt):
                queue = asyncio.Queue()
                sched.submit(
                    _request(prompt, max_tokens=4),
                    lambda o: loop.call_soon_threadsafe(queue.put_nowait, o),
                )
                toks = []
                while True:
                    out = await asyncio.wait_for(queue.get(), 60)
                    toks.extend(out.token_ids)
                    if out.finish_reason is not None:
                        return toks

            prompt = list(range(100, 113))
            first = await one(prompt)
            second = await one(prompt)  # prefix-cache hit path
            assert first == second
            sched.stop()

        run(body(), timeout=120)

    def test_oversize_request_rejected(self, run, runner):
        async def body():
            sched = InferenceScheduler(runner)
            sched.start()
            loop = asyncio.get_running_loop()
            queue = asyncio.Queue()
            sched.submit(
                _request(range(10), max_tokens=100000),
                lambda o: loop.call_soon_threadsafe(queue.put_nowait, o),
            )
            out = await asyncio.wait_for(queue.get(), 30)
            assert out.finish_reason == "error"
            sched.stop()

        run(body(), timeout=60)


class _CountingLoop:
    """Stands in for the event loop: counts `call_soon_threadsafe`, runs
    the callback at once, and notes what the scheduler still held."""

    def __init__(self, sched):
        self.sched = sched
        self.queues = {}  # request id -> _Inbox
        self.calls = []  # per hand-over: [(queue tag, EngineOutput)]
        self.slots_at_call = []

    def call_soon_threadsafe(self, fn, *args):
        before = {tag: len(q.items) for tag, q in self.queues.items()}
        fn(*args)
        self.calls.append([(tag, out) for tag, q in self.queues.items()
                           for out in q.items[before[tag]:]])
        self.slots_at_call.append(
            {s.request.request_id for s in self.sched._slots
             if s is not None})


class _Inbox:
    def __init__(self):
        self.items = []

    def put_nowait(self, item):
        self.items.append(item)


class TestOutbox:
    """The worker's out-tray behind a hand-driven scheduler: how often
    the scheduler thread wakes the event loop."""

    def _setup(self, runner, n, max_tokens=20, block=8):
        from dynamo_tpu.engine.worker import Outbox

        sched = InferenceScheduler(runner)
        sched.decode_block = block
        sched.decode_pipeline = 1
        outbox = Outbox()
        sched.on_emit_end = outbox.flush
        loop = _CountingLoop(sched)
        for i in range(n):
            rid = f"r{i}"
            inbox = loop.queues[rid] = _Inbox()
            sched.submit(
                _request(range(1 + i, 9 + i), max_tokens=max_tokens,
                         rid=rid),
                lambda o, q=inbox: outbox.post(loop, q, o))
        return sched, outbox, loop

    @staticmethod
    def _step(sched):
        sched._drain_control()
        sched._drain_incoming()
        sched._step()

    def test_one_wake_up_for_a_drained_block_of_many_sequences(self, runner):
        sched, outbox, loop = self._setup(runner, 3)
        self._step(sched)  # prefill dispatched, nothing to hand over
        assert loop.calls == []
        self._step(sched)  # the three first tokens: ONE hand-over
        assert len(loop.calls) == 1
        assert sorted(tag for tag, _ in loop.calls[0]) == ["r0", "r1", "r2"]
        for _ in range(2):  # two fused blocks: one hand-over each,
            before = len(loop.calls)  # three frames of eight tokens
            self._step(sched)
            assert len(loop.calls) == before + 1
            assert [len(out.token_ids) for _, out in loop.calls[-1]] == [8] * 3
        assert outbox.handovers == len(loop.calls) == 3
        assert sched.stats.emit_frames == 9
        sched.stop()

    def test_the_finish_frame_is_handed_over_before_the_pages_go(self, runner):
        sched, outbox, loop = self._setup(runner, 2, max_tokens=5)
        for _ in range(3):
            self._step(sched)
        finishing = [i for i, call in enumerate(loop.calls)
                     if any(out.finish_reason for _, out in call)]
        assert len(finishing) == 1  # both rows end in the same block
        call = loop.calls[finishing[0]]
        assert [(out.finish_reason, len(out.token_ids))
                for _, out in call] == [("length", 4)] * 2
        # when the loop was woken the scheduler still held both slots:
        # `_reap_finished` released their pages after the hand-over
        assert loop.slots_at_call[finishing[0]] == {"r0", "r1"}
        assert all(s is None for s in sched._slots)
        sched.stop()

    @pytest.mark.parametrize("path", ["abort", "fail", "drain", "death"])
    def test_one_wake_up_at_once_when_sequences_are_vacated(self, runner,
                                                            path):
        """Abort, failure, drain and engine death each finish every
        live, waiting and queued request in ONE hand-over, before they
        return (no frame waits for a step that may never come)."""
        sched, outbox, loop = self._setup(runner, 3)
        self._step(sched)
        self._step(sched)  # three rows decoding
        extra = _Inbox()   # and one request still in the incoming queue
        loop.queues["late"] = extra
        sched._incoming.put((_request(range(8), rid="late"),
                             lambda o: outbox.post(loop, extra, o),
                             type("H", (), {"seq": None,
                                            "_cancelled": False})(), {}))
        before = len(loop.calls)
        if path == "abort":
            assert sched.abort_all("reshard") == 3
            want, late = "migrate", False
        elif path == "fail":
            assert sched._finish_all("deadline") == 3
            want, late = "error", False
        elif path == "drain":
            report = sched.drain_sweep()
            assert len(report["replay"]) == 3
            want, late = "migrate", False
        else:
            sched._die(RuntimeError("device lost"))
            want, late = "error", True
        assert len(loop.calls) == before + 1
        got = {tag: out.finish_reason for tag, out in loop.calls[-1]}
        assert got == {**{f"r{i}": want for i in range(3)},
                       **({"late": "error"} if late else {})}
        assert all(s is None for s in sched._slots)
        if path == "death":
            # a submit that follows the death is failed on its caller's
            # thread, and handed over there and then
            after = _Inbox()
            loop.queues["after"] = after
            sched.submit(_request(range(8), rid="after"),
                         lambda o: outbox.post(loop, after, o))
            assert len(loop.calls) == before + 2
            assert [out.finish_reason for out in after.items] == ["error"]
        sched.stop()

    def test_nothing_is_lost_when_threads_post_and_flush_together(self, run):
        """The engine's death can put a `submit` caller's thread beside
        the scheduler thread on the out-tray: every frame posted is
        delivered once, each poster's in its own order."""
        import sys
        import threading

        from dynamo_tpu.engine.worker import Outbox

        posters, each = 16, 400

        async def body():
            loop = asyncio.get_running_loop()
            outbox, queues = Outbox(), [asyncio.Queue() for _ in range(posters)]

            def work(i):
                for n in range(each):
                    outbox.post(loop, queues[i], EngineOutput(token_ids=[n]))
                    if n % 7 == i % 7:
                        outbox.flush()
                outbox.flush()

            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(posters)]
            interval = sys.getswitchinterval()
            sys.setswitchinterval(1e-5)
            try:
                for t in threads:
                    t.start()
                for t in threads:
                    await loop.run_in_executor(None, t.join, 30)
            finally:
                sys.setswitchinterval(interval)
            assert not any(t.is_alive() for t in threads)
            for q in queues:
                got = [(await asyncio.wait_for(q.get(), 10)).token_ids[0]
                       for _ in range(each)]
                assert got == list(range(each))
                assert q.empty()
            assert 1 <= outbox.handovers <= posters * each

        run(body(), timeout=60)


class TestTpuWorkerE2E:
    def test_worker_serves_and_publishes_events(self, run, mem_runtime_config):
        async def body():
            from dynamo_tpu.runtime import DistributedRuntime

            rt = await DistributedRuntime(mem_runtime_config()).start()
            ns = uuid.uuid4().hex
            sub = await rt.event_subscriber(ns, topic_prefix="kv_events")
            worker = TpuWorker(
                rt, model_name="tiny-test", namespace=ns,
                runner_config=RunnerConfig(
                    page_size=4, num_pages=64, max_batch=4,
                    max_pages_per_seq=16, prefill_buckets=(8, 16, 32)),
                warmup=False,
            )
            await worker.start()
            client = rt.namespace(ns).component("backend").endpoint("generate").client()
            await client.wait_for_instances(1, timeout=10)
            req = _request(list(range(16)), max_tokens=3).to_wire()
            outs = [EngineOutput.from_wire(o) async for o in client.direct(
                req, worker.instance_id)]
            toks = [t for o in outs for t in o.token_ids]
            assert len(toks) == 3
            # KV events for the cached prompt blocks arrive on the plane.
            topic, payload = await asyncio.wait_for(sub.__anext__(), 10)
            assert topic == "kv_events"
            assert payload.get("s") is not None
            await worker.close()
            await rt.shutdown()

        run(body(), timeout=120)


class TestEmbeddings:
    def test_runner_embed_deterministic_and_normalized(self):
        runner = _runner()
        v1 = runner.embed(np.arange(10, dtype=np.int32))
        v2 = runner.embed(np.arange(10, dtype=np.int32))
        v3 = runner.embed(np.arange(1, 11, dtype=np.int32))
        assert v1.shape == (runner.model_config.hidden,)
        assert np.allclose(v1, v2)
        assert not np.allclose(v1, v3)
        assert abs(float(np.linalg.norm(v1)) - 1.0) < 1e-4
        # Bucketing must not change the result: the same tokens padded into
        # a larger bucket (a runner whose only bucket is 32 forces 10 tokens
        # into 22 extra pad positions) must embed identically.
        wide = ModelRunner(
            get_config("tiny-test"),
            RunnerConfig(page_size=4, num_pages=64, max_batch=4,
                         max_pages_per_seq=16, prefill_buckets=(32,)),
            make_mesh(MeshConfig()), seed=0,
        )
        v4 = wide.embed(np.arange(10, dtype=np.int32))
        assert np.allclose(v1, v4, atol=1e-5)
        # Over the largest bucket -> clear error, not a broadcast crash.
        with pytest.raises(ValueError, match="exceeds"):
            runner.embed(np.zeros(100, np.int32))

    def test_worker_embed_endpoint(self, run, mem_runtime_config):
        async def body():
            from dynamo_tpu.runtime import DistributedRuntime

            rt = await DistributedRuntime(mem_runtime_config()).start()
            ns = uuid.uuid4().hex
            worker = TpuWorker(
                rt, model_name="tiny-test", namespace=ns,
                runner_config=RunnerConfig(
                    page_size=4, num_pages=64, max_batch=4,
                    max_pages_per_seq=16, prefill_buckets=(8, 16, 32)),
                warmup=False,
            )
            await worker.start()
            client = rt.namespace(ns).component("backend").endpoint("generate").client()
            await client.wait_for_instances(1, timeout=10)
            req = _request(list(range(12)), max_tokens=1)
            req.annotations = {"embed": True}
            outs = [EngineOutput.from_wire(o) async for o in client.direct(
                req.to_wire(), worker.instance_id)]
            assert outs[-1].finish_reason == "stop"
            emb = outs[-1].embedding
            assert emb is not None
            assert len(emb) == worker.runner.model_config.hidden
            await worker.close()
            await rt.shutdown()

        run(body(), timeout=120)
