"""Mocker: chip-free engine simulator.

The linchpin of CI-scale testing (ref: lib/mocker — vLLM-style continuous
batching sim scheduler/vllm/core.rs, paged KV with prefix cache + LRU
kv_manager/vllm_backend.rs + cache/radix_cache.rs, `--speedup-ratio` timing,
KV event publishing; docs/mocker/mocker.md). Simulates a TPU inference
engine: paged KV pool with prefix caching and LRU eviction, continuous
batching with chunked prefill, a timing model, KV-cache events, and load
metrics — so routing / planner / disagg logic is testable with zero chips.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from collections import OrderedDict
from typing import AsyncIterator, Optional

import numpy as np

from ..kv_router.protocols import (
    KV_EVENT_TOPIC,
    LOAD_TOPIC,
    KvCacheRemoved,
    KvCacheStored,
    LoadMetrics,
    RouterEvent,
)
from ..llm.protocols import EngineOutput, PreprocessedRequest
from ..runtime.logging import get_logger
from ..tokens import compute_block_hashes

log = get_logger("mocker")


@dataclasses.dataclass
class MockerConfig:
    block_size: int = 16
    num_blocks: int = 1024
    max_batch: int = 32
    max_prefill_tokens_per_step: int = 2048  # chunked prefill budget
    prefill_us_per_token: float = 300.0
    decode_base_ms: float = 8.0
    # Echo mode: generated tokens replay the prompt (protocol/parser E2E
    # testing — lets a test drive exact output text through the frontend).
    echo: bool = False
    decode_us_per_seq: float = 100.0
    # Paged-attention cost: per active KV block of decoding sequences per
    # step (the context-length-dependent term the reference's mocker
    # models — ref: lib/mocker/src/scheduler/vllm/core.rs timing).
    decode_us_per_kv_block: float = 0.0
    speedup_ratio: float = 1.0
    watermark: float = 0.01  # keep this fraction of blocks free
    vocab_size: int = 512
    dp_rank: int = 0
    # Speculative-worker profile (acceptance-rate-parameterized
    # multi-token steps, mirroring the real engine's draftless
    # speculation — docs/speculative-decoding.md): each decode step per
    # sequence emits 1 + accepted tokens, where each of spec_k draft
    # positions accepts independently with p=spec_acceptance until the
    # first rejection (the verified-prefix rule). The verification
    # forward scores k+1 positions, so the per-seq step cost scales by
    # (1 + spec_k * spec_verify_overhead) — FLOPs-for-latency, nearly
    # free on a memory-bound step. spec_k = 0 disables.
    spec_k: int = 0
    spec_acceptance: float = 0.0
    spec_verify_overhead: float = 0.15
    # Disagg KV handoff cost (host-relay DCN / ICI): time to move one KV
    # block prefill->decode. Consumed by the offline replay's transfer
    # timeline (loadgen._transfer_delay_s): serial handoffs pay it in
    # full after the prompt pass, the chunked pipeline only for the
    # unoverlapped tail. 0 = free transfers (the pre-overlap model).
    kv_transfer_us_per_block: float = 0.0
    # -- cold-start model (fast-start plane, docs/elasticity.md) ----------
    # With coldstart=True, MockerWorker.start() walks the real arrival
    # ladder (boot -> fetch -> load -> compile -> register) with the modeled
    # latencies below before registering endpoints, stamping the same
    # dynamo_coldstart_* metric families TpuWorker does — so cold-start
    # A/Bs (striped vs single-source fetch, warm vs cold compile cache)
    # and the chaos-spot evict+replace scenario run chip-free. Sleeps
    # divide by speedup_ratio like every other mocker latency.
    coldstart: bool = False
    boot_ms: float = 0.0                 # process start -> engine build
    weight_bytes: float = 1.4e9          # weight tree size to fetch
    fetch_striped: bool = True           # peer-striped vs single-source
    fetch_donors: int = 4
    fetch_gbps_per_donor: float = 12.0   # effective per-donor stripe rate
    fetch_gbps_single: float = 6.0       # one-source (G4 / single peer)
    load_ms: float = 4000.0              # host->HBM device_put + pools
    compile_cache_warm: bool = False     # warm persistent compile cache?
    compile_cold_ms: float = 70000.0     # full prewarm key space, cold
    compile_warm_ms: float = 3000.0      # same keys replayed from cache
    register_ms: float = 300.0           # endpoints + card + first canary

    @classmethod
    def from_timing_preset(cls, name: str, **overrides) -> "MockerConfig":
        params = dict(TIMING_PRESETS[name])
        params.update(overrides)
        return cls(**params)


def coldstart_phases(cfg: MockerConfig) -> dict[str, float]:
    """Modeled arrival-ladder phase seconds for a mocker cold start —
    the SAME closed-form both the worker walk and the bench.py
    `cold_start` A/B block evaluate, so assertions about the model
    (striped strictly faster than single-source, warm cache strictly
    faster than cold) are deterministic and chip-free. Fetch bandwidth
    adds across donors (each stripe is an independent TCP stream off an
    independent host NIC); compile collapses to the warm replay time
    when the persistent cache is warm."""
    if cfg.fetch_striped:
        rate_gbps = cfg.fetch_gbps_per_donor * max(1, cfg.fetch_donors)
    else:
        rate_gbps = cfg.fetch_gbps_single
    compile_ms = (cfg.compile_warm_ms if cfg.compile_cache_warm
                  else cfg.compile_cold_ms)
    return {
        "boot": cfg.boot_ms / 1e3,
        "fetch": cfg.weight_bytes * 8 / (rate_gbps * 1e9),
        "load": cfg.load_ms / 1e3,
        "compile": compile_ms / 1e3,
        "register": cfg.register_ms / 1e3,
    }


# Step-time coefficients fit from one 2026-07 decode probe on a v5e
# (the r3/r4 tables of `git show 6b5a9d4:BASELINE.md`; the probe script
# left with them at PR 21) and not rechecked since — ROADMAP C6:
#   us/step = decode_base + decode_us_per_seq * batch
#             + decode_us_per_kv_block * active_kv_blocks
# Least-squares over the ctx~0 floor points (bs 8/16/32 -> 2580/3298/
# 5241 us) gives base=1608us, per_seq=112.4us (fit error <3.3% on all
# three); the attention term is measured directly (+620us for 128
# blocks at bs=8 ctx=256 -> 4.84us/block). The prefill rate comes from
# the on-chip chunked-prefill bench. These make planner/mocker CI
# validate SLA math against real step-time physics, not placeholders.
TIMING_PRESETS: dict[str, dict] = {
    "tpu-v5e-qwen3-0.6b": dict(
        decode_base_ms=1.608,
        decode_us_per_seq=112.4,
        decode_us_per_kv_block=4.84,
        # bench.py prefill headline (r4): 8,852 tok/s pipelined at chunk
        # 1024 on the v5e chip -> 113 us/token.
        prefill_us_per_token=113.0,
        block_size=16,
        # Host-relay DCN handoff: a qwen3-0.6b universal block (28 layers
        # x 2 x 16 tok x 8 kv heads x 128 hd x bf16 ~= 1.75 MiB) over a
        # ~4.5 GB/s host relay -> ~400 us/block.
        kv_transfer_us_per_block=400.0,
    ),
    # Speculative-worker profile (ROADMAP item 1: router/planner layers
    # must see speculation in chip-free scenario tests): the same
    # measured v5e step physics with draftless speculation at k=4. The
    # 0.7 acceptance default models repetitive/agentic traffic (the
    # workloads prompt-lookup targets); override spec_acceptance per
    # scenario for low-repetition sweeps.
    "tpu-v5e-qwen3-0.6b-spec": dict(
        decode_base_ms=1.608,
        decode_us_per_seq=112.4,
        decode_us_per_kv_block=4.84,
        prefill_us_per_token=113.0,
        block_size=16,
        spec_k=4,
        spec_acceptance=0.7,
    ),
    # Cold-start profile for the fast-start plane (docs/elasticity.md):
    # the v5e bring-up's qwen3-0.6b serving stack, modeled — ~1.4 GB
    # bf16 weight tree; stripes ride independent donor NICs at an
    # effective ~12 Gbps each vs ~6 Gbps for one G4/object-store stream;
    # XLA compile of the full prewarm key space (decode + 5 prefill
    # buckets + spec verify) is tens of seconds cold and a seconds-scale
    # disk replay with a warm persistent cache; device_put + pool init
    # is a few seconds. Serving step physics are the measured r3/r4
    # coefficients above.
    "tpu-v5e-coldstart": dict(
        decode_base_ms=1.608,
        decode_us_per_seq=112.4,
        decode_us_per_kv_block=4.84,
        prefill_us_per_token=113.0,
        block_size=16,
        coldstart=True,
        boot_ms=8000.0,
        weight_bytes=1.4e9,
        fetch_donors=4,
        fetch_gbps_per_donor=12.0,
        fetch_gbps_single=6.0,
        load_ms=4000.0,
        compile_cold_ms=70000.0,
        compile_warm_ms=3000.0,
        register_ms=300.0,
    ),
}


def derive_decode_profile(preset: str, num_blocks: int = 2048,
                          batches=(1, 2, 4, 8, 16, 32),
                          contexts=(128, 256, 512, 1024, 2048)) -> dict:
    """Sample a (kv_usage, context) -> ITL/throughput decode profile from
    a timing preset, in the planner interpolator's raw_data schema — so
    planner replica math can be validated (and bootstrapped) against the
    same measured step-time physics the mocker simulates, without a
    profiling sweep (ref: planner pre_swept_results NPZ role)."""
    params = TIMING_PRESETS[preset]
    bs_block = params["block_size"]
    kv, ctx_out, itl, thpt = [], [], [], []
    for ctx in contexts:
        blocks_per_seq = -(-ctx // bs_block)
        for bs in batches:
            if bs * blocks_per_seq > num_blocks:
                # Infeasible operating point (KV would not fit) —
                # clamping it onto kv_usage=1.0 would collide with a
                # feasible point at ~2x throughput and bias the
                # interpolator optimistic at full KV.
                continue
            step_us = (params["decode_base_ms"] * 1e3
                       + params["decode_us_per_seq"] * bs
                       + params["decode_us_per_kv_block"]
                       * bs * blocks_per_seq)
            kv.append(bs * blocks_per_seq / num_blocks)
            ctx_out.append(float(ctx))
            itl.append(step_us / 1e3)  # ms per token per sequence
            thpt.append(bs / (step_us / 1e6))  # tokens/s/chip
    return {
        "x_kv_usage": kv,
        "y_context_length": ctx_out,
        "z_itl": itl,
        "z_thpt_per_chip": thpt,
        "max_kv_tokens": [num_blocks * bs_block],
    }


class _PagedKvCache:
    """Prefix cache over sequence-hash-identified blocks with LRU eviction
    of unreferenced blocks (ref: kv_manager/vllm_backend.rs + radix_cache.rs)."""

    def __init__(self, num_blocks: int) -> None:
        self.capacity = num_blocks
        self.used = 0  # blocks held by running requests (non-cached)
        self.cached: OrderedDict[int, None] = OrderedDict()  # hash -> LRU
        self.refcount: dict[int, int] = {}

    def free_blocks(self) -> int:
        return self.capacity - self.used - len(self.cached)

    def evictable_blocks(self) -> int:
        """Cached blocks no running request references — reclaimable by
        allocate() on demand, so admission math must count them as free
        capacity (ref: vllm_backend.rs inactive pool — eviction source
        during allocation). Counting them as occupied would stall
        admission on exactly the cache-rich workers KV-affinity routing
        prefers."""
        return sum(1 for h in self.cached if self.refcount.get(h, 0) == 0)

    def match_prefix(self, block_hashes: list[int]) -> int:
        """Longest cached prefix; touches LRU and pins the blocks."""
        matched = 0
        for block_hash in block_hashes:
            if block_hash in self.cached:
                self.cached.move_to_end(block_hash)
                matched += 1
            else:
                break
        return matched

    def pin(self, block_hashes: list[int]) -> None:
        for h in block_hashes:
            self.refcount[h] = self.refcount.get(h, 0) + 1

    def unpin(self, block_hashes: list[int]) -> None:
        for h in block_hashes:
            n = self.refcount.get(h, 0) - 1
            if n <= 0:
                self.refcount.pop(h, None)
            else:
                self.refcount[h] = n

    def allocate(self, n: int, evict_cb) -> bool:
        """Reserve n uncached blocks, evicting LRU cached blocks if needed."""
        while self.free_blocks() < n and self.cached:
            evicted = []
            for h in list(self.cached):
                if self.refcount.get(h, 0) == 0:
                    self.cached.pop(h)
                    evicted.append(h)
                    if self.free_blocks() >= n:
                        break
            if evicted:
                evict_cb(evicted)
            else:
                break  # everything pinned
        if self.free_blocks() < n:
            return False
        self.used += n
        return True

    def release(self, n: int) -> None:
        self.used = max(0, self.used - n)

    def insert_cached(self, block_hashes: list[int], from_used: int) -> list[int]:
        """Move `from_used` request-held blocks into the reusable cache under
        their hashes; returns the hashes newly added."""
        new = []
        for h in block_hashes:
            if h not in self.cached:
                self.cached[h] = None
                new.append(h)
            else:
                self.cached.move_to_end(h)
        self.used = max(0, self.used - from_used)
        return new

    def usage(self) -> float:
        return (self.used + len(self.cached)) / max(1, self.capacity)


@dataclasses.dataclass
class _Sequence:
    request: PreprocessedRequest
    queue: asyncio.Queue
    block_hashes: list[int]
    cached_blocks: int  # prefix hit
    new_blocks: int  # allocated for the remainder
    prefilled_tokens: int = 0
    generated: int = 0
    # Tokens actually DELIVERED to the consumer: deliveries lag
    # `generated` by up to one modeled step (the step loop flushes
    # frames after sleeping the step time), and a drain handoff must
    # carry exactly the delivered history — resume state covering an
    # undelivered token would skip it from the client's stream.
    delivered: int = 0
    done: bool = False
    cancelled: bool = False
    pinned: list[int] = dataclasses.field(default_factory=list)
    prefill_chunks: int = 0  # steps that advanced this prompt (chunking)
    # Simulated device-time attribution (mirrors the real scheduler's
    # perf/steptrace.py plane): device = modeled step compute, host =
    # measured loop bookkeeping, bucketed as "prefill" until the first
    # token is DELIVERED (so the TTFT decomposition sums to the
    # timeline's TTFT), "decode" after. Flushed onto the flight
    # recorder at those two boundaries.
    device_prefill_ms: float = 0.0
    host_prefill_ms: float = 0.0
    device_decode_ms: float = 0.0
    host_decode_ms: float = 0.0
    prefill_flushed: bool = False

    @property
    def rank(self) -> int:
        from ..llm.protocols import class_rank

        return class_rank(self.request.priority)


class MockerEngine:
    """Continuous-batching simulator; `generate` is a worker handler."""

    def __init__(
        self,
        config: Optional[MockerConfig] = None,
        worker_id: int = 0,
        event_publisher=None,
    ) -> None:
        self.config = config or MockerConfig()
        self.worker_id = worker_id
        from ..kv_router.local_indexer import LocalKvIndexer

        self.local_index = LocalKvIndexer(worker_id, self.config.dp_rank)
        self.kv = _PagedKvCache(self.config.num_blocks)
        self._waiting: list[_Sequence] = []
        self._running: list[_Sequence] = []
        # Multi-tenant QoS (docs/multi-tenancy.md): preempted batch
        # sequences parked off their slots/blocks (the chip-free analog
        # of the real scheduler's preempt-to-KVBM), resumed when
        # interactive pressure clears. Mirrors the real engine's
        # dynamo_preempt_total counters so chaos scenarios assert the
        # plane without silicon.
        from ..runtime.config import env

        self._parked: list[_Sequence] = []
        self.preempt_enabled = bool(env("DYNT_PREEMPT_ENABLE"))
        self.preempt_parked = 0
        self.preempt_resumed = 0
        # Graceful drain plane (engine/drain.py simulated chip-free;
        # docs/fault-tolerance.md departure ladder): while draining,
        # raced arrivals bounce with an in-band migrate; counters mirror
        # the real scheduler's SchedulerStats.drain_* so the chaos proof
        # asserts the ladder without silicon.
        self.draining = False
        self.drain_handoff = 0
        self.drain_replayed = 0
        self.drain_errored = 0
        self.drain_resumed = 0
        self.drain_bounced = 0
        self._publisher = event_publisher
        self._event_id = 0
        self._step_task: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()
        self._closed = False
        self.steps = 0
        # Cumulative prompt tokens this engine actually prefilled —
        # ground truth for the chaos-overload assertion that requests
        # refused at admission never burned prefill work.
        self.prefill_tokens_total = 0
        self._pending_stored: list[tuple[list[int], Optional[int]]] = []
        # Speculative-worker profile accounting (spec_k > 0): mirrors the
        # real engine's dynamo_spec_* proposed/accepted counters so
        # scenario tests can assert acceptance stats chip-free.
        self.spec_proposed = 0
        self.spec_accepted = 0
        self._spec_rng = np.random.default_rng(0x5BEC ^ worker_id)
        # Simulated step decomposition (the perf/steptrace.py analog):
        # device = modeled compute, host = measured loop bookkeeping.
        self.last_step_device_ms = 0.0
        self.last_step_host_ms = 0.0
        self.last_step_wall_ms = 0.0
        self.device_ms_total = 0.0
        self.host_ms_total = 0.0

    # -- events ------------------------------------------------------------

    async def _publish_stored(self, hashes: list[int], parent: Optional[int]) -> None:
        if not hashes:
            return
        self.local_index.on_stored(self._event_id, list(hashes), parent)
        event = RouterEvent(
            worker_id=self.worker_id, event_id=self._event_id,
            dp_rank=self.config.dp_rank,
            stored=KvCacheStored(block_hashes=hashes, parent_hash=parent),
        )
        self._event_id += 1
        if self._publisher is not None:
            await self._publisher.publish(KV_EVENT_TOPIC, event.to_wire())

    async def _publish_removed(self, hashes: list[int]) -> None:
        if not hashes:
            return
        self.local_index.on_removed(self._event_id, list(hashes))
        event = RouterEvent(
            worker_id=self.worker_id, event_id=self._event_id,
            dp_rank=self.config.dp_rank,
            removed=KvCacheRemoved(block_hashes=hashes),
        )
        self._event_id += 1
        if self._publisher is not None:
            await self._publisher.publish(KV_EVENT_TOPIC, event.to_wire())

    async def publish_load(self) -> None:
        if self._publisher is None:
            return
        metrics = self.load_metrics()
        await self._publisher.publish(LOAD_TOPIC, metrics.to_wire())

    async def clear_prefix_cache(self) -> int:
        """Drop every unpinned cached block and publish their removal
        (the clear_kv_blocks endpoint; ref: vllm worker
        clear_kv_blocks + mocker kv_manager reset)."""
        dropped = [h for h in list(self.kv.cached)
                   if self.kv.refcount.get(h, 0) == 0]
        for h in dropped:
            self.kv.cached.pop(h, None)
        await self._publish_removed(dropped)
        return len(dropped)

    def load_metrics(self) -> LoadMetrics:
        return LoadMetrics(
            worker_id=self.worker_id,
            dp_rank=self.config.dp_rank,
            active_blocks=self.kv.used,
            total_blocks=self.kv.capacity,
            active_requests=len(self._running),
            # Parked (preempted) sequences are backlog the admission
            # estimators must see, exactly like the real scheduler.
            waiting_requests=len(self._waiting) + len(self._parked),
            kv_usage=self.kv.usage(),
            step_wall_ms=self.last_step_wall_ms,
            device_ms_in_step=self.last_step_device_ms,
            host_ms_in_step=self.last_step_host_ms,
            draining=self.draining,
        )

    # -- public handler ----------------------------------------------------

    async def generate(self, body: dict, ctx=None) -> AsyncIterator[dict]:
        request = PreprocessedRequest.from_wire(body)
        if request.annotations.get("embed"):
            # Deterministic pseudo-embedding: seeded by the token content so
            # identical inputs embed identically (router/E2E testability).
            import numpy as np

            seed = abs(hash(tuple(request.token_ids))) & 0xFFFFFFFF
            vec = np.random.default_rng(seed).standard_normal(64)
            vec /= max(float(np.linalg.norm(vec)), 1e-9)
            yield EngineOutput(
                finish_reason="stop",
                prompt_tokens=len(request.token_ids),
                embedding=[float(x) for x in vec],
            ).to_wire()
            return
        if self.draining:
            # Vacating (engine/drain.py): anything that raced the
            # router's draining flip bounces with an in-band migrate —
            # the Migration operator replays it on a peer.
            self.drain_bounced += 1
            yield EngineOutput(
                finish_reason="migrate",
                error="worker draining; replay on a peer").to_wire()
            return
        queue: asyncio.Queue = asyncio.Queue()
        block_hashes = compute_block_hashes(request.token_ids,
                                            self.config.block_size)
        seq = _Sequence(request=request, queue=queue, block_hashes=block_hashes,
                        cached_blocks=0, new_blocks=0)
        self._ensure_stepper()
        self._waiting.append(seq)
        self._wake.set()
        try:
            while True:
                item = await queue.get()
                if item is None:
                    return
                yield item
        finally:
            seq.cancelled = True

    def _ensure_stepper(self) -> None:
        if self._step_task is None or self._step_task.done():
            self._step_task = asyncio.create_task(self._step_loop())

    async def close(self) -> None:
        self._closed = True
        self._wake.set()
        if self._step_task is not None:
            self._step_task.cancel()
            try:
                await self._step_task
            except asyncio.CancelledError:
                pass

    # -- scheduler ---------------------------------------------------------

    async def _step_loop(self) -> None:
        """One iteration = admit + (chunked) prefill progress + one decode
        token per running sequence, then sleep the modeled step time."""
        while not self._closed:
            if not self._running and not self._waiting and not self._parked:
                self._wake.clear()
                await self._wake.wait()
                continue
            step_start = time.monotonic()
            evicted_total: list[int] = []
            self._admit(evicted_total.extend)
            prefill_tokens, prefilled = self._prefill_step()
            decoded, decode_seqs, progressed, deliveries = \
                self._decode_step()
            try:
                if evicted_total:
                    await self._publish_removed(evicted_total)
                await self._flush_stored()
                self.steps += 1
                elapsed = time.monotonic() - step_start
                target = self._step_time(prefill_tokens, decode_seqs,
                                         self._active_kv_blocks())
                delay = max(0.0, target - elapsed)
                # Simulated step decomposition (the mocker analog of
                # perf/steptrace.py): device = the modeled compute time,
                # host = the loop's measured bookkeeping residual;
                # device + host == the step wall the sleeps realize.
                wall_ms = (elapsed + delay) * 1e3
                device_ms = min(target * 1e3, wall_ms)
                host_ms = max(0.0, wall_ms - device_ms)
                self.last_step_device_ms = device_ms
                self.last_step_host_ms = host_ms
                self.last_step_wall_ms = wall_ms
                self.device_ms_total += device_ms
                self.host_ms_total += host_ms
                seen_ids: set[int] = set()
                for seq in prefilled + progressed + self._running:
                    # Wall attribution to EVERY admitted live sequence
                    # (each one waited this step's wall out, whether it
                    # progressed or sat behind the shared prefill
                    # budget — contention is part of its burn, exactly
                    # like the real scheduler's shared block windows),
                    # deduped, and bucketed as prefill until its first
                    # token DELIVERS so the TTFT decomposition sums to
                    # the timeline's TTFT.
                    if id(seq) in seen_ids or seq.cancelled:
                        continue
                    seen_ids.add(id(seq))
                    if not seq.prefill_flushed:
                        seq.device_prefill_ms += device_ms
                        seq.host_prefill_ms += host_ms
                    else:
                        seq.device_decode_ms += device_ms
                        seq.host_decode_ms += host_ms
                if delay:
                    await asyncio.sleep(delay)
                elif not prefill_tokens and not decoded:
                    # Nothing progressed (all waiting on blocks): back off
                    # instead of busy-spinning the loop.
                    await asyncio.sleep(0.005)
                else:
                    await asyncio.sleep(0)
            finally:
                # Deliver AFTER sleeping the modeled step time: the step's
                # outputs become visible at step end, so TTFT/ITL include
                # the compute they rode on. finally: sequences finalized
                # in _decode_step are already off _running, so dropping
                # their frames on cancellation/publish failure would hang
                # consumers waiting on the terminal None.
                for seq, item in deliveries:
                    self._deliver(seq, item)

    def _step_time(self, prefill_tokens: int, decode_seqs: int,
                   kv_blocks: int = 0) -> float:
        cfg = self.config
        t = 0.0
        if prefill_tokens:
            t += prefill_tokens * cfg.prefill_us_per_token / 1e6
        if decode_seqs:
            # Speculative verification scores spec_k extra positions per
            # sequence inside the same weight stream: the per-seq compute
            # term scales by the overhead factor, the (dominant) base +
            # KV-streaming terms do not — which is exactly why accepted
            # tokens come out cheaper than full steps.
            per_seq = cfg.decode_us_per_seq * (
                1.0 + cfg.spec_k * cfg.spec_verify_overhead)
            t += (cfg.decode_base_ms / 1e3) + decode_seqs * per_seq / 1e6
            t += kv_blocks * cfg.decode_us_per_kv_block / 1e6
        return t / max(1e-6, cfg.speedup_ratio)

    def _active_kv_blocks(self) -> int:
        """KV blocks attended by currently-DECODING sequences (the paged
        attention streams these every step)."""
        bs = self.config.block_size
        total = 0
        for seq in self._running:
            if seq.done or seq.cancelled:
                continue
            if seq.prefilled_tokens >= len(seq.request.token_ids):
                total += -(-(seq.prefilled_tokens + seq.generated) // bs)
        return total

    def _admit(self, evict_cb) -> None:
        cfg = self.config
        # Class-strict admission (docs/multi-tenancy.md): stable sort
        # keeps FIFO within a class, a fresh interactive arrival
        # overtakes every waiting batch request.
        self._waiting.sort(key=lambda s: -s.rank)
        while self._waiting:
            seq = self._waiting[0]
            if seq.cancelled:
                self._waiting.pop(0)
                continue
            # A parked sequence of the head's class or better resumes
            # before the head admits (it was admitted first).
            if self._resume_parked(evict_cb, limit=1, min_rank=seq.rank):
                continue
            if len(self._running) >= cfg.max_batch:
                # Slot pressure: preempt a lower-class decode slot (the
                # chip-free park-to-KVBM analog) and retry.
                if self._try_preempt_for(seq, "slot"):
                    continue
                break
            cached = self.kv.match_prefix(seq.block_hashes)
            # Pin the matched prefix BEFORE allocating: allocation may evict
            # unreferenced cached blocks, and it must not evict the ones we
            # just counted as reusable.
            prefix = seq.block_hashes[:cached]
            self.kv.pin(prefix)
            total_blocks = (
                len(seq.request.token_ids) + seq.request.sampling.max_tokens
            ) // cfg.block_size + 1
            if total_blocks > self.kv.capacity:
                # Can never fit, even with an empty pool: reject instead of
                # wedging the queue (ref: engines reject over-capacity
                # requests rather than deadlock the scheduler).
                self.kv.unpin(prefix)
                self._waiting.pop(0)
                seq.queue.put_nowait(EngineOutput(
                    finish_reason="error",
                    error=(f"request needs {total_blocks} KV blocks, pool has "
                           f"{self.kv.capacity}"),
                ).to_wire())
                seq.queue.put_nowait(None)
                continue
            need = max(0, total_blocks - cached)
            reserve = int(self.kv.capacity * cfg.watermark)
            reclaimable = self.kv.free_blocks() + self.kv.evictable_blocks()
            if (reclaimable - need < reserve and self._running) \
                    or not self.kv.allocate(need, evict_cb):
                self.kv.unpin(prefix)
                # Block pressure is the other preemption trigger: a
                # parked batch slot returns its blocks.
                if self._try_preempt_for(seq, "full"):
                    continue
                break  # wait for blocks to free up
            seq.cached_blocks = cached
            seq.new_blocks = need
            seq.prefilled_tokens = cached * cfg.block_size
            seq.pinned = prefix
            # Admission = end of queue wait (no-op without an open
            # timeline; first write wins like the real scheduler).
            from ..runtime.flight_recorder import get_recorder

            get_recorder().stamp(seq.request.request_id, "scheduled")
            if seq.request.disaggregated_params is not None:
                # Disagg decode side: the KV "arrived" via transfer — skip
                # the prefill pass entirely (ref §3.4 decode leg).
                seq.prefilled_tokens = len(seq.request.token_ids)
                handoff = seq.request.disaggregated_params.get("handoff")
                if handoff is not None:
                    # Drain-handoff destination (engine/drain.py): the
                    # committed history rides the params; decode
                    # continues at the next index — the token function
                    # is deterministic in (prompt, index), so the
                    # continuation is bit-identical to an undrained
                    # run, with ZERO tokens through the prefill ledger
                    # (the chaos proof's re-prefill assertion).
                    seq.generated = len(handoff.get("generated") or [])
                    # The inherited history counts as DELIVERED too: a
                    # second drain of this worker (rolling restart) must
                    # ship the full committed history, or the next peer
                    # would re-emit the inherited tokens to the client.
                    seq.delivered = seq.generated
                    self.drain_resumed += 1
                    get_recorder().event(seq.request.request_id,
                                         "drain_resume",
                                         tokens_preserved=seq.generated)
            self._waiting.pop(0)
            self._running.append(seq)
        self._resume_parked(evict_cb)

    # -- preemption (docs/multi-tenancy.md; the real engine's
    # preempt-to-KVBM plane, simulated chip-free) -------------------------

    def _try_preempt_for(self, head: "_Sequence", short_of: str) -> bool:
        """Park the cheapest lower-class decode slot so `head` can
        admit; `short_of` is what admission ran out of ("slot" or the
        "full" block pool: the `group` label of `dynamo_preempt_total`,
        as in the real scheduler). Returns True when a victim was
        parked."""
        if not self.preempt_enabled:
            return False
        victim = None
        vkey = None
        for seq in self._running:
            if seq.done or seq.cancelled:
                continue
            if seq.prefilled_tokens < len(seq.request.token_ids):
                continue
            if seq.request.annotations.get("prefill_only"):
                continue
            if seq.generated < 1 or seq.rank >= head.rank:
                continue
            key = (seq.rank, seq.generated)
            if vkey is None or key < vkey:
                victim, vkey = seq, key
        if victim is None:
            return False
        self._running.remove(victim)
        self._park_seq(victim)
        self._parked.append(victim)
        self.preempt_parked += 1
        try:
            from ..runtime.metrics import PREEMPT_TOTAL

            PREEMPT_TOTAL.labels(kind="park", group=short_of).inc()
        except Exception:  # noqa: BLE001 — metrics must not break sims
            pass
        from ..runtime.conformance import observe
        from ..runtime.flight_recorder import get_recorder

        observe("preemption",
                f"{id(self)}:{victim.request.request_id}", "park")
        get_recorder().event(victim.request.request_id, "preempt",
                             kind="park",
                             tokens_preserved=victim.generated)
        return True

    def _park_seq(self, seq: "_Sequence") -> None:
        """Return the victim's blocks to the pool, keeping the sequence
        (prefill position, generated count) live for resume — the mock
        analog of gathering pages into the KVBM park store. Prefilled
        full prompt blocks enter the reusable cache (the offloaded KV
        stays matchable, so resume onload is ~free exactly like a KVBM
        hit)."""
        cfg = self.config
        self.kv.unpin(seq.pinned)
        prefilled_blocks = seq.prefilled_tokens // cfg.block_size
        full_prompt_blocks = min(len(seq.block_hashes), prefilled_blocks)
        new_cached = seq.block_hashes[seq.cached_blocks:full_prompt_blocks]
        newly = self.kv.insert_cached(
            new_cached, from_used=min(len(new_cached), seq.new_blocks))
        leftover = seq.new_blocks - min(len(new_cached), seq.new_blocks)
        self.kv.release(leftover)
        if newly:
            parent = (seq.block_hashes[seq.cached_blocks - 1]
                      if seq.cached_blocks > 0 else None)
            self._pending_stored.append((newly, parent))
        seq.pinned = []
        seq.new_blocks = 0

    def _resume_parked(self, evict_cb, limit=None, min_rank=-1) -> int:
        """Re-admit parked sequences when slots and blocks are back and
        nothing higher-class is still waiting (higher class first, park
        order within a class). Returns how many resumed."""
        if not self._parked:
            return 0
        cfg = self.config
        waiting_rank = max(
            (s.rank for s in self._waiting if not s.cancelled), default=-1)
        resumed = 0
        for seq in sorted(self._parked, key=lambda s: -s.rank):
            if limit is not None and resumed >= limit:
                break
            if seq.cancelled:
                self._parked.remove(seq)
                from ..runtime.conformance import observe

                observe("preemption",
                        f"{id(self)}:{seq.request.request_id}", "drop")
                continue
            if seq.rank < waiting_rank or seq.rank < min_rank:
                continue  # pressure persists: stay parked
            if len(self._running) >= cfg.max_batch:
                break
            cached = self.kv.match_prefix(seq.block_hashes)
            prefix = seq.block_hashes[:cached]
            self.kv.pin(prefix)
            total_blocks = (
                len(seq.request.token_ids)
                + seq.request.sampling.max_tokens
            ) // cfg.block_size + 1
            need = max(0, total_blocks - cached)
            if not self.kv.allocate(need, evict_cb):
                self.kv.unpin(prefix)
                break
            seq.cached_blocks = cached
            seq.new_blocks = need
            seq.pinned = prefix
            self._parked.remove(seq)
            self._running.append(seq)
            resumed += 1
            self.preempt_resumed += 1
            try:
                from ..runtime.metrics import PREEMPT_TOTAL

                PREEMPT_TOTAL.labels(kind="resume", group="full").inc()
            except Exception:  # noqa: BLE001 — metrics must not break
                pass
            from ..runtime.conformance import observe
            from ..runtime.flight_recorder import get_recorder

            observe("preemption",
                    f"{id(self)}:{seq.request.request_id}", "resume")
            get_recorder().event(seq.request.request_id, "preempt",
                                 kind="resume",
                                 tokens_preserved=seq.generated)
        return resumed

    def _prefill_step(self) -> tuple[int, list["_Sequence"]]:
        """Advance prefills within the chunked budget; returns (tokens
        prefilled, the sequences that advanced)."""
        from ..runtime.flight_recorder import get_recorder

        budget = self.config.max_prefill_tokens_per_step
        total = 0
        advanced: list[_Sequence] = []
        for seq in self._running:
            if seq.done or seq.cancelled:
                continue
            remaining = len(seq.request.token_ids) - seq.prefilled_tokens
            if remaining <= 0:
                continue
            chunk = min(remaining, budget - total)
            if chunk <= 0:
                break
            seq.prefilled_tokens += chunk
            seq.prefill_chunks += 1
            if seq.prefill_chunks == 1:
                # First chunk of real prefill compute (no-op for
                # requests with no open timeline — bare-mocker tests).
                get_recorder().stamp(seq.request.request_id,
                                     "prefill_start")
            total += chunk
            advanced.append(seq)
        self.prefill_tokens_total += total
        return total, advanced

    def _spec_tokens_this_step(self, remaining: int) -> int:
        """Tokens a speculative step emits for one sequence: 1 (the
        always-emitted target) + leading draft acceptances, each draft
        position accepting independently with p=spec_acceptance until
        the first rejection. Bounded by the sequence's token budget."""
        cfg = self.config
        k = min(cfg.spec_k, max(0, remaining - 1))
        accepted = 0
        for _ in range(k):
            if self._spec_rng.random() >= cfg.spec_acceptance:
                break
            accepted += 1
        self.spec_proposed += k
        self.spec_accepted += accepted
        return 1 + accepted

    def _token_at(self, req: PreprocessedRequest, index: int) -> int:
        """Deterministic pseudo-output — echo the prompt, or cycle
        through printable ASCII. A pure function of (prompt, index):
        what makes drain-handoff continuations bit-identical to an
        undrained run by construction, and lets the drain sweep
        reconstruct the committed history for the handoff frame."""
        if self.config.echo and index < len(req.token_ids):
            return int(req.token_ids[index])
        return 97 + ((len(req.token_ids) + index) % 26)

    # -- graceful drain (engine/drain.py, simulated chip-free;
    # docs/fault-tolerance.md departure ladder) ---------------------------

    def drain_sweep(self, handoff: bool = True) -> dict:
        """Vacate live sequences for a graceful departure, mirroring
        InferenceScheduler.drain_sweep. Rung 1 — eligible decode
        sequences (fully prefilled, committed tokens, not prefill-only)
        emit a migrate frame whose kv_transfer_params carry the mock
        pull route + resume state; the destination mocker skips its
        prefill pass and continues the deterministic token function at
        the next index. Rung 2 — everything else (waiting, parked,
        mid-prefill) emits a plain migrate for a peer replay. Returns
        the same {"handoff": [...], "replay": [...], "pending": [...]}
        report shape as the real scheduler."""
        self.draining = True
        report: dict = {"handoff": [], "replay": [], "pending": []}
        from ..runtime.flight_recorder import get_recorder

        def _replay(seq: _Sequence) -> None:
            self.drain_replayed += 1
            report["replay"].append(seq.request.request_id)
            get_recorder().event(seq.request.request_id, "drain",
                                 rung="replay",
                                 tokens_preserved=seq.generated)
            self._deliver(seq, EngineOutput(
                finish_reason="migrate",
                error="worker draining").to_wire())
            self._deliver(seq, None)

        for seq in list(self._waiting):
            if not seq.cancelled:
                _replay(seq)
            seq.cancelled = True
        self._waiting.clear()
        for seq in list(self._parked):
            if not seq.cancelled:
                _replay(seq)
            seq.cancelled = True
        self._parked.clear()
        for seq in list(self._running):
            if seq.done or seq.cancelled:
                continue
            req = seq.request
            if req.annotations.get("prefill_only"):
                # Its decode peer is mid-"pull" of the mock transfer;
                # the step loop finishes it on its own.
                report["pending"].append(req.request_id)
                continue
            if (handoff and seq.delivered > 0
                    and seq.prefilled_tokens >= len(req.token_ids)):
                # Resume state covers the DELIVERED history only:
                # tokens committed this step but still waiting on the
                # modeled step sleep never reached the client, so the
                # destination must regenerate them (bit-identically).
                self.drain_handoff += 1
                report["handoff"].append(req.request_id)
                get_recorder().event(req.request_id, "drain",
                                     rung="handoff",
                                     tokens_preserved=seq.delivered)
                self._deliver(seq, EngineOutput(
                    finish_reason="migrate",
                    error="worker draining (kv handoff)",
                    kv_transfer_params={
                        "mock": True,
                        "handoff": {
                            "seed": 0,
                            "generated": [self._token_at(req, g)
                                          for g in range(seq.delivered)],
                            "prompt_len": len(req.token_ids),
                        },
                    }).to_wire())
                self._deliver(seq, None)
            else:
                _replay(seq)
            seq.done = True
            self._running.remove(seq)
            self._release(seq)
        try:
            from ..runtime.metrics import DRAIN_SEQUENCES

            for outcome, count in (("handoff", len(report["handoff"])),
                                   ("replay", len(report["replay"]))):
                if count:
                    DRAIN_SEQUENCES.labels(outcome=outcome).inc(count)
        except Exception:  # noqa: BLE001 — metrics must not break sims
            pass
        return report

    def drain_expire(self, reason: str) -> int:
        """Deadline rung: finish anything still live with an honest
        in-band error (mirrors InferenceScheduler.drain_expire)."""
        n = 0
        for seq in list(self._waiting) + list(self._parked) \
                + list(self._running):
            if seq.done or seq.cancelled:
                continue
            self._deliver(seq, EngineOutput(
                finish_reason="error", error=reason).to_wire())
            self._deliver(seq, None)
            seq.done = True
            seq.cancelled = True
            n += 1
            if seq in self._running:
                self._running.remove(seq)
                self._release(seq)
        self._waiting.clear()
        self._parked.clear()
        self.drain_errored += n
        try:
            from ..runtime.metrics import DRAIN_SEQUENCES

            if n:
                DRAIN_SEQUENCES.labels(outcome="error").inc(n)
        except Exception:  # noqa: BLE001
            pass
        return n

    def _decode_step(self) -> tuple[int, int, list, list]:
        """Generate tokens for each fully-prefilled sequence — one per
        step, or 1 + accepted under a speculative-worker profile
        (spec_k > 0). Returns (tokens, decoding_seqs, progressed
        sequences, deliveries).

        Outputs are COLLECTED, not delivered: a step's tokens exist only
        once the step's modeled compute time has elapsed, so the step
        loop sleeps the step time first and then flushes the deliveries
        (otherwise TTFT on an uncontended worker measures ~0 instead of
        the prefill cost — ref: the real engine returns step outputs at
        step end)."""
        deliveries: list[tuple[_Sequence, object]] = []
        decoded = 0
        decode_seqs = 0
        progressed: list[_Sequence] = []
        finished: list[_Sequence] = []
        for seq in self._running:
            if seq.cancelled:
                finished.append(seq)
                continue
            if seq.prefilled_tokens < len(seq.request.token_ids):
                continue
            req = seq.request
            if req.annotations.get("prefill_only"):
                # Disagg prefill side: answer with kv_transfer_params
                # instead of decoding (the mock transfer carries no data;
                # the decode mocker just skips its prefill pass).
                first = 97 + (len(req.token_ids) % 26)
                seq.done = True
                progressed.append(seq)
                deliveries.append((seq, EngineOutput(
                    token_ids=[], finish_reason="stop",
                    prompt_tokens=len(req.token_ids),
                    kv_transfer_params={
                        "mock": True, "first_token": first,
                        "prompt_len": len(req.token_ids),
                        # Transfer-timeline inputs for the offline
                        # replay's handoff model (loadgen).
                        "prompt_blocks": -(-len(req.token_ids)
                                           // self.config.block_size),
                        "chunks": seq.prefill_chunks,
                    },
                ).to_wire()))
                deliveries.append((seq, None))
                finished.append(seq)
                continue
            decode_seqs += 1
            progressed.append(seq)
            n_tokens = 1
            if self.config.spec_k > 0:
                n_tokens = self._spec_tokens_this_step(
                    req.sampling.max_tokens - seq.generated)
            tokens: list[int] = []
            for _ in range(n_tokens):
                tokens.append(self._token_at(req, seq.generated))
                seq.generated += 1
            decoded += len(tokens)
            finish = None
            if seq.generated >= req.sampling.max_tokens:
                finish = "length"
            output = EngineOutput(
                token_ids=tokens,
                finish_reason=finish,
                prompt_tokens=(len(req.token_ids)
                               if seq.generated == len(tokens) else None),
            )
            deliveries.append((seq, output.to_wire()))
            if finish is not None:
                seq.done = True
                deliveries.append((seq, None))
                finished.append(seq)
        for seq in finished:
            self._running.remove(seq)
            self._release(seq)
        return decoded, decode_seqs, progressed, deliveries

    def _deliver(self, seq: _Sequence, item) -> None:
        """Flush the simulated device/host attribution onto the flight
        recorder at the two bucket boundaries — first token delivered
        (prefill burn becomes the request's device-time TTFT) and
        stream end (decode burn) — then hand the frame to the consumer.
        Flushes run BEFORE the frame so the consumer closing the
        timeline can never race them."""
        from ..runtime.flight_recorder import get_recorder

        rid = seq.request.request_id
        if item is None:
            if seq.device_decode_ms or seq.host_decode_ms:
                get_recorder().device(rid, "decode",
                                      seq.device_decode_ms,
                                      seq.host_decode_ms)
                seq.device_decode_ms = seq.host_decode_ms = 0.0
            seq.queue.put_nowait(None)
            return
        if isinstance(item, dict) and item.get("t"):
            seq.delivered += len(item["t"])
        if not seq.prefill_flushed and isinstance(item, dict) \
                and (item.get("t") or item.get("kv")):
            seq.prefill_flushed = True
            get_recorder().device(rid, "prefill", seq.device_prefill_ms,
                                  seq.host_prefill_ms)
            if seq.device_prefill_ms \
                    and not seq.request.annotations.get("canary"):
                try:
                    from ..runtime.metrics import TTFT_DEVICE_MS
                    from ..runtime.otel import trace_id_of

                    trace_id = trace_id_of(
                        seq.request.annotations.get("traceparent"))
                    TTFT_DEVICE_MS.labels(
                        model=seq.request.model).observe(
                        seq.device_prefill_ms,
                        exemplar={"trace_id": trace_id}
                        if trace_id else None)
                except Exception:  # noqa: BLE001 — metrics must not
                    # break a chip-free simulation environment
                    pass
        seq.queue.put_nowait(item)

    def _release(self, seq: _Sequence) -> None:
        """On completion: completed full blocks become reusable cache entries;
        the rest free (and generated-token blocks beyond the prompt free)."""
        cfg = self.config
        self.kv.unpin(seq.pinned)
        # Only blocks actually prefilled may enter the reusable cache — a
        # cancelled sequence must not register (and advertise) blocks whose
        # KV was never computed.
        prefilled_blocks = seq.prefilled_tokens // cfg.block_size
        full_prompt_blocks = min(len(seq.block_hashes), prefilled_blocks)
        new_cached = seq.block_hashes[seq.cached_blocks:full_prompt_blocks]
        newly = self.kv.insert_cached(
            new_cached, from_used=min(len(new_cached), seq.new_blocks)
        )
        leftover = seq.new_blocks - min(len(new_cached), seq.new_blocks)
        self.kv.release(leftover)
        if newly:
            parent = (
                seq.block_hashes[seq.cached_blocks - 1]
                if seq.cached_blocks > 0 else None
            )
            self._pending_stored.append((newly, parent))

    async def _flush_stored(self) -> None:
        pending, self._pending_stored = self._pending_stored, []
        for hashes, parent in pending:
            await self._publish_stored(hashes, parent)
