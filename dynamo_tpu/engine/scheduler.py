"""Continuous-batching scheduler driving the ModelRunner.

The engine-side scheduler the reference delegates to vLLM/SGLang (and
simulates in lib/mocker): slot-based continuous batching with chunked
prefill, paged-KV prefix reuse, per-token streaming, cancellation, and stop
conditions. Runs in a dedicated thread because compiled JAX steps block;
results cross into asyncio via call_soon_threadsafe.

Scheduling policy per iteration (vLLM-style, decode-priority), with the
host/device overlap the TPU dispatch model rewards — device work is
issued asynchronously and read back as late as possible:
  1. admit waiting requests into free slots while pages allocate
  2. DISPATCH a fused decode block (lax.scan over K steps, optionally
     depth-pipelined on device-resident tokens) for all decode-ready
     slots — no readback yet
  3. advance at most `prefill_chunk` prefill tokens (chunked prefill
     keeps decode ITL protected during long prompts); the chunk executes
     behind the decode block on the device stream, and its host-side
     prep/dispatch overlaps the block's compute
  4. admit again — arrivals that landed during dispatch are admitted
     while the device is still stepping
  5. drain the decode block (the only blocking readback of the loop)

Fused blocks run even while prefill work is pending: each sequence's
page allocation carries block*depth tokens of speculative slack, so a
sequence stopping mid-block can never write into a neighbour's pages,
and the surplus tokens are discarded at drain.
"""

from __future__ import annotations

import dataclasses
import queue as thread_queue
import threading
import time
from typing import Callable, Optional

import numpy as np

from ..llm.protocols import EngineOutput, PreprocessedRequest
from ..perf.steptrace import StepTrace, annotation
from ..runtime.flight_recorder import get_recorder
from ..runtime.logging import get_logger
from ..tokens import TokenBlockSequence, compute_block_hashes
from ..models.config import CachePlan, cache_plan
from .model_runner import (
    ModelRunner,
    PrefillRow,
    bucket_table_width,
    take_builds,
)
from .pages import PageAllocation, PagePool, WindowLease, WindowPool
from .spec import BlockLookahead, NGramProposer, SlotSpec, propose_for

log = get_logger("engine.scheduler")


def _section(name: str):
    """A named host section of the loop (`sched.*`) on the profiler's
    clock: a capture's idle gaps are named by it. No-op outside a
    profiler session (perf/steptrace.py `annotation`)."""
    return annotation(name, section=True)


def _observe_preempt(instance: str, event: str) -> None:
    """Feed the `preemption` lifecycle to the conformance monitor. The
    instance key is scheduler-scoped (id(self) prefix) so a migrated
    request replayed on a peer starts a fresh lifecycle there instead of
    tripping park-after-migrated on the old one."""
    from ..runtime.conformance import observe

    observe("preemption", instance, event)


@dataclasses.dataclass
class _Seq:
    request: PreprocessedRequest
    emit: Callable[[EngineOutput], None]
    block_hashes: list[int]
    alloc: PageAllocation
    block_table: np.ndarray
    slot: int
    prompt_len: int
    prefill_pos: int  # next prompt position to prefill
    generated: list[int] = dataclasses.field(default_factory=list)
    last_token: int = 0
    cancelled: bool = False
    finished: bool = False
    seed: int = 0
    # Disagg: prefill-only sequences stop after the first sampled token and
    # hand their pages to the transfer table instead of releasing them.
    prefill_only: bool = False
    on_prefill_done: Optional[Callable[["_Seq", int, list[int]], dict]] = None
    keep_pages: bool = False  # reap skips pool.release (transfer owns them)
    # Disagg chunked handoff (docs/disaggregation.md): called on the
    # scheduler thread after each NON-final prefill chunk with the newly
    # completed page ids; the first call returns kv_transfer_params which
    # are emitted mid-stream so the decode worker starts pulling while
    # later chunks compute. Called with None on abort (cancel/error
    # before on_prefill_done) so the streaming transfer can fail fast.
    on_prefill_chunk: Optional[Callable[["_Seq", Optional[list[int]]],
                                        Optional[dict]]] = None
    streamed_pages: int = 0  # full pages already parked with the transfer
    stream_started: bool = False  # transfer registered (pages parked)
    stream_done: bool = False  # on_prefill_done ran (clean finish)
    # Disagg decode side: KV blocks pulled from the prefill pool + the
    # token it sampled; admission scatters instead of prefilling.
    onboard_blocks: Optional[np.ndarray] = None
    onboard_first_token: Optional[int] = None
    # Multi-LoRA: adapter slot in the runner's pack (0 = base model)
    lora_idx: int = 0
    # Multimodal: encoder rows spliced at image-placeholder positions,
    # consumed in token order across prefill chunks
    media_embeds: Optional[np.ndarray] = None  # [total_rows, H]
    # Logits processors (llm/logits_processing.py): instantiated per
    # request at _prepare; non-empty routes this sequence through the
    # host-sampling decode path (block=1 + raw-logits readback)
    processors: Optional[list] = None
    # Processor sequences defer their FIRST token past prefill (prefill
    # samples on device without logits readback): the first decode step
    # re-attends at prompt_len-1 (idempotent KV rewrite of the last
    # prompt token) and produces it through the host path.
    first_deferred: bool = False
    # Whether this sequence's allocation includes the speculative slack
    # pages fused decode overruns into. False only when the slacked span
    # would exceed engine capacity (tiny configs / max-length requests);
    # such sequences fuse only while their remaining token budget covers
    # the block, else the batch degrades to per-token.
    slack_ok: bool = True
    # Flight-recorder timeline key (worker.generate qualifies prefill
    # legs); None for bare-scheduler callers — stamps then no-op.
    record_id: Optional[str] = None
    # The worker.generate span's context: scheduler-side spans (kvbm
    # onload) parent here so their wall time lands inside the worker
    # subtree, not as a sibling of the dispatch under the frontend span.
    traceparent: Optional[str] = None
    # prefill_start stamped (keeps the hot chunk loop from taking the
    # recorder lock once per iteration per prefilling sequence)
    prefill_stamped: bool = False
    # Speculative decoding state (engine/spec.py): proposer index over
    # this sequence's history + acceptance EMA. None when speculation is
    # off or the sequence can't speculate.
    spec: Optional[SlotSpec] = None
    # Device-time attribution (perf/steptrace.py): monotonic timestamp
    # of this sequence's FIRST prefill dispatch submit, and the
    # accumulated device windows per phase. Flushed onto the flight
    # recorder at first_token (prefill) and reap (decode).
    prefill_submit_ts: Optional[float] = None
    device_prefill_ms: float = 0.0
    device_decode_ms: float = 0.0
    # Multi-tenant QoS (docs/multi-tenancy.md): the request's priority
    # class orders admission (class-strict, stable within class) and
    # marks batch slots as preemption donors; parked_pages records how
    # many leading block-table pages the park bundle covers so resume
    # scatters exactly what preemption gathered.
    priority_class: str = "standard"
    parked_pages: int = 0
    # Graceful-drain handoff destination (docs/fault-tolerance.md): the
    # resume state a draining peer shipped alongside onboard_blocks —
    # seed, step count and generated tokens — so decode continues the
    # committed stream bit-identically instead of re-prefilling.
    resume_state: Optional[dict] = None
    # The open frame: tokens `_append_token` committed in the emitting
    # section under way, not yet emitted. One EngineOutput a sequence
    # goes out when the section ends (`_end_emit`), or at once with a
    # finish; never held across sections.
    frame: Optional[EngineOutput] = None
    # A model with window layers: this sequence's hold on the window
    # group (engine/pages.py), from admission to release.
    window: Optional[WindowLease] = None

    @property
    def rank(self) -> int:
        from ..llm.protocols import class_rank

        return class_rank(self.priority_class)

    @property
    def decode_ready(self) -> bool:
        return self.prefill_pos >= self.prompt_len

    @property
    def kv_len(self) -> int:
        return self.prompt_len + len(self.generated)


@dataclasses.dataclass
class SchedulerStats:
    steps: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    last_step_wall_ms: float = 0.0
    prefill_tokens_last_step: int = 0
    decode_tokens_last_step: int = 0
    kvbm_onboarded_blocks: int = 0
    # Overlap instrumentation (tested by tests/test_serving_overlap.py):
    # fused decode blocks dispatched while prefill work was pending, and
    # sequences admitted while a decode block was in flight on device.
    fused_steps_with_prefill: int = 0
    admitted_during_inflight: int = 0
    # Cross-sequence prefill batching + disagg chunked handoff
    # (tests/test_serving_overlap.py, test_disagg.py): iterations whose
    # prefill chunks from SEVERAL sequences went out in one dispatch, and
    # KV pages parked with the transfer table before their prompt
    # finished prefilling.
    prefill_batched_steps: int = 0
    disagg_streamed_pages: int = 0
    # Counts at the boundaries the tokens are counted at
    # (dynamo_engine_launches, dynamo_kv_reserved_page_ms;
    # docs/metrics.md): prefill programs and fused decode blocks
    # launched (device decode steps are the runner's decode_steps), and
    # the sum over committed steps of pages held by sequences in a slot
    # x the step's wall ms.
    prefill_launches: int = 0
    decode_block_launches: int = 0
    reserved_page_ms: float = 0.0
    # The window group's twin (dynamo_kv_window_reserved_page_ms): pages
    # of the second pool held by sequences in a slot x the step's wall
    # ms; 0 for a model without window layers.
    window_reserved_page_ms: float = 0.0
    # Token frames closed (dynamo_engine_emit_frames_total): one a
    # sequence an emitting section, so decode tokens over it is the
    # tokens a frame.
    emit_frames: int = 0
    # Beside it for a model with recurrent state
    # (dynamo_ssm_state_slot_ms): the sum over committed steps of slots
    # held (each holds one fixed-size state) x the step's wall ms. And
    # what the dropless expert layer counted on the device
    # (dynamo_moe_*_total; `ModelRunner.moe_stats`): by phase, tokens per
    # held expert, dropped slots, experts touched, calls; None without one.
    state_slot_ms: float = 0.0
    moe_counts: Optional[np.ndarray] = None
    # Rows of prefill launches by whether the row's chunk was its
    # prompt's last (dynamo_prefill_cross_decoder_rows_total: a stack
    # whose tail runs on one position a row runs it for every row, and
    # only a last chunk's logits are read)
    prefill_rows_last: int = 0
    prefill_rows_earlier: int = 0
    # Speculative decoding (dynamo_spec_* metrics; docs/metrics.md):
    # proposed/accepted count MINED drafts only (static-shape padding is
    # excluded), spec_ema is the mean acceptance EMA over the slots that
    # proposed in the latest speculative step.
    spec_steps: int = 0
    spec_proposed: int = 0
    spec_accepted: int = 0
    spec_last_k: int = 0
    spec_ema: float = 0.0
    # Step decomposition of the latest committed step
    # (perf/steptrace.py): device window vs host residual, mirrored
    # into LoadMetrics. device + host == wall by construction; the
    # full sample (dispatch/drain/prep) and cumulative totals live on
    # scheduler.steptrace.
    device_ms_last_step: float = 0.0
    host_ms_last_step: float = 0.0
    # Multi-tenant QoS preemption plane (docs/multi-tenancy.md):
    # batch decode slots parked to KVBM / cooperatively migrated under
    # interactive pressure, and parked sequences resumed.
    preempt_parked: int = 0
    preempt_migrated: int = 0
    preempt_resumed: int = 0
    # Graceful drain plane (engine/drain.py; docs/fault-tolerance.md
    # departure ladder): sequences vacated per rung on the SOURCE
    # (handoff / replay / error), handoff sequences resumed on the
    # DESTINATION, and new arrivals bounced while draining.
    drain_handoff: int = 0
    drain_replayed: int = 0
    drain_errored: int = 0
    drain_resumed: int = 0
    drain_bounced: int = 0


class InferenceScheduler:
    def __init__(
        self,
        runner: ModelRunner,
        on_stored: Optional[Callable[[list[int], Optional[int]], None]] = None,
        on_removed: Optional[Callable[[list[int]], None]] = None,
        kvbm=None,  # Optional[block_manager.KvBlockManager]
    ) -> None:
        self.runner = runner
        cfg = runner.config
        self.page_size = cfg.page_size
        self.kvbm = kvbm
        from ..runtime.config import env

        # Multi-step decode block (DYNT_DECODE_BLOCK): >1 fuses K decode
        # steps into one compiled call when conditions allow — tokens then
        # stream in blocks of K.
        self.decode_block = max(1, int(env("DYNT_DECODE_BLOCK") or 1))
        self.decode_pipeline = max(1, int(env("DYNT_DECODE_PIPELINE") or 1))
        # Speculative decoding (DYNT_SPEC_*; docs/speculative-decoding.md):
        # draftless n-gram proposals verified in one batched forward.
        # Gated off for runners without the multi-token verification
        # forward (MLA/gpt-oss) and for mirrored multihost drivers (the
        # spec step is not on the mirrored-launch protocol).
        self.spec_enabled = (
            bool(env("DYNT_SPEC_ENABLE"))
            and getattr(runner, "supports_spec", False)
            and not getattr(runner, "is_mirrored", False))
        self.spec_k = max(1, int(env("DYNT_SPEC_MAX_K")))
        self.spec_min_ema = float(env("DYNT_SPEC_MIN_EMA"))
        self.spec_cutoff = max(0, int(env("DYNT_SPEC_BATCH_CUTOFF")))
        # Cross-request continuation store keyed by the same chained
        # block hashes the prefix cache registers (engine/spec.py).
        self.spec_lookahead = (BlockLookahead(cfg.page_size)
                               if self.spec_enabled else None)
        # Disagg chunked handoff: streamed-chunk token budget for
        # prefill-only sequences (0 = the engine's prefill chunk).
        self.disagg_chunk = max(0, int(env("DYNT_DISAGG_CHUNK") or 0))
        # Multi-tenant QoS preemption (docs/multi-tenancy.md): under
        # interactive pressure, batch decode slots park-to-KVBM (or
        # cooperatively migrate when no park store is attached).
        self.preempt_enabled = bool(env("DYNT_PREEMPT_ENABLE"))
        self.preempt_max_parked = max(0, int(env("DYNT_PREEMPT_MAX_PARKED")))
        self._parked: list[_Seq] = []
        # Graceful drain (engine/drain.py): while draining, new arrivals
        # bounce with an in-band migrate (the router has been told to
        # stop selecting this worker; anything that raced the flip
        # replays on a peer instead of being admitted into a pool that
        # is vacating).
        self.draining = False

        def _stored(hashes: list[int], parent: Optional[int]) -> None:
            # Fan out G1 registrations to the router event buffer AND the
            # KVBM offload queue (ref §3.5: connector offload trigger).
            if on_stored is not None:
                on_stored(hashes, parent)
            if kvbm is not None:
                kvbm.notify_stored(hashes, parent)

        # What the model's cache is and cannot do (`models.config.
        # cache_plan`; a stub runner without a configuration has the
        # plain one). Per-slot state lives in the runner, indexed by the
        # slot a sequence holds here: a row that prefills from position 0
        # starts from zero state, so admission resets nothing, and a
        # preempted request resumes by recomputation (cooperative
        # migrate), never from parked pages. Where a prefix hit cannot
        # be taken (it would skip tokens a state has to see, or need a
        # window group's last positions, which nothing keeps) no pool
        # has a prefix cache and no `stored` event is published. A
        # second page group is a second pool (`self.pool` stays the full
        # group's), allocated ahead of each launch and freed behind the
        # window while a sequence lives.
        model_config = getattr(runner, "model_config", None)
        self.cache_plan = (cache_plan(model_config)
                           if model_config is not None else CachePlan())
        self.pool = PagePool(cfg.num_pages, on_stored=_stored,
                             on_removed=on_removed,
                             prefix_cache=not self.cache_plan.reuse_prefix)
        self.win_pool: Optional[WindowPool] = None
        if "window" in self.cache_plan.groups:
            self.win_pool = WindowPool(cfg.window_pages, cfg.page_size,
                                       model_config.sliding_window)
            # positions a decode launch may write past the one it reads
            self._win_lookahead = (self.decode_block
                                   * max(1, self.decode_pipeline))
            if (self.win_pool.bound(self._win_lookahead)
                    > runner.window_table_width):
                raise ValueError(
                    f"DYNT_DECODE_BLOCK x DYNT_DECODE_PIPELINE = "
                    f"{self._win_lookahead} positions ahead do not fit "
                    f"the window group's {runner.window_table_width}-page "
                    "decode table")
        if kvbm is not None:
            # Offload gathers ride the dispatch/drain gap (run_in_gap):
            # they execute while the decode block is busy on device, and
            # the bandwidth budget reads our step wall time to back off
            # under serving pressure (docs/kvbm.md).
            kvbm.attach_engine(
                lookup_pages=lambda hs: [self.pool.lookup(h) for h in hs],
                gather=runner.gather_pages_device,
                run_in_step=self.run_in_gap,
                step_pressure=self._offload_pressure,
            )
        self.max_batch = cfg.max_batch
        self._slots: list[Optional[_Seq]] = [None] * cfg.max_batch
        self._waiting: list[_Seq] = []
        self._incoming: thread_queue.Queue = thread_queue.Queue()
        self._control: thread_queue.Queue = thread_queue.Queue()
        # Gap-window control queue (run_in_gap): drained between a decode
        # block's dispatch and its drain, so maintenance device work
        # (KVBM offload gathers, disagg transfer gathers) runs while the
        # device is busy on the block instead of stealing step time.
        self._gap_control: thread_queue.Queue = thread_queue.Queue()
        # Final-chunk prefill tokens whose host readback is deferred one
        # iteration: (seq, device token array). The readback then sits
        # BEHIND the next decode block on the device queue, so prefill
        # never blocks the serving loop.
        self._pending_prefill: list = []
        self._wake = threading.Event()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # Set (once) when an exception escapes a step: the loop has
        # ended, every request was failed with it, and `on_fatal` told
        # the owner — a worker exits non-zero rather than idle
        # registered with nothing behind its endpoints.
        self.failed: Optional[BaseException] = None
        self.on_fatal: Optional[Callable[[BaseException], None]] = None
        # Called where the loop stops emitting (`_end_emit`): an owner
        # whose `emit` callbacks only collect hands what they collected
        # to its consumers here, once a section instead of once a token.
        self.on_emit_end: Optional[Callable[[], None]] = None
        self._open_frames: list[_Seq] = []
        self.stats = SchedulerStats()
        # Device-time attribution (perf/steptrace.py): per-step
        # decomposition stamps around every dispatch/drain below, plus
        # the jax.profiler StepTraceAnnotation scopes an on-demand
        # /debug/profile capture attributes device ops to.
        self.steptrace = StepTrace()
        # decode input buffers (reused)
        b, p = cfg.max_batch, cfg.max_pages_per_seq
        self._tokens = np.zeros(b, np.int32)
        self._positions = np.zeros(b, np.int32)
        self._tables = np.zeros((b, p), np.int32)
        # behind the full group's table in every decode launch: a second
        # page group's table and its base
        self._win_tables: tuple = ()
        if self.win_pool is not None:
            self._win_tables = (
                np.zeros((b, runner.window_table_width), np.int32),
                np.zeros(b, np.int32))
        self._kv_lens = np.zeros(b, np.int32)
        self._active = np.zeros(b, bool)
        self._temp = np.ones(b, np.float32)
        self._top_p = np.ones(b, np.float32)
        self._top_k = np.zeros(b, np.int32)
        self._seeds = np.zeros(b, np.uint32)
        self._steps = np.zeros(b, np.int32)
        self._lora_idx = np.zeros(b, np.int32)

    # -- public (thread-safe) ---------------------------------------------

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="engine-scheduler")
            self._thread.start()

    @property
    def thread_ident(self) -> Optional[int]:
        """The engine thread's ident (None before start)."""
        return self._thread.ident if self._thread is not None else None

    def stop(self) -> None:
        self._stop = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)

    def submit(
        self,
        request: PreprocessedRequest,
        emit: Callable[[EngineOutput], None],
        *,
        prefill_only: bool = False,
        on_prefill_done: Optional[Callable] = None,
        on_prefill_chunk: Optional[Callable] = None,
        onboard_blocks: Optional[np.ndarray] = None,
        onboard_first_token: Optional[int] = None,
        resume_state: Optional[dict] = None,
        lora_idx: int = 0,
        media_embeds: Optional[np.ndarray] = None,
        record_id: Optional[str] = None,
        traceparent: Optional[str] = None,
    ) -> "_SubmitHandle":
        handle = _SubmitHandle()
        self._incoming.put((request, emit, handle, {
            "prefill_only": prefill_only,
            "on_prefill_done": on_prefill_done,
            "on_prefill_chunk": on_prefill_chunk,
            "onboard_blocks": onboard_blocks,
            "onboard_first_token": onboard_first_token,
            "resume_state": resume_state,
            "lora_idx": lora_idx,
            "media_embeds": media_embeds,
            "record_id": record_id,
            "traceparent": traceparent,
        }))
        self._wake.set()
        if self.failed is not None:
            # Raced (or followed) the engine's death: nobody drains the
            # queue any more, so fail what is in it here. (The caller's
            # thread: the hook alone, never the loop's open frames.)
            self._fail_incoming()
            if self.on_emit_end is not None:
                self.on_emit_end()
        return handle

    def run_in_step(self, fn: Callable[[], object]) -> "thread_queue.Queue":
        """Run `fn` on the scheduler thread between steps (the KV cache
        buffer is donated through every compiled step, so any gather/
        scatter/release must be serialized with stepping). Returns a
        1-item queue carrying (result, exception)."""
        out: thread_queue.Queue = thread_queue.Queue(1)
        if self.failed is not None:
            out.put((None, RuntimeError(self._failure_reason())))
            return out

        def wrapped() -> None:
            try:
                out.put((fn(), None))
            except Exception as exc:  # noqa: BLE001 — delivered to caller
                out.put((None, exc))

        self._control.put(wrapped)
        self._wake.set()
        return out

    def run_in_gap(self, fn: Callable[[], object]) -> "thread_queue.Queue":
        """Like run_in_step, but the callback executes inside the step's
        dispatch/drain gap — after the decode block is issued (device
        busy on it) and before its blocking drain — so maintenance device
        work (KVBM offload gathers, streaming transfer gathers) queues
        behind the in-flight block instead of delaying the next dispatch.
        Same serialization guarantee (scheduler thread); when the engine
        is idle the gap queue drains on the loop's idle path."""
        out: thread_queue.Queue = thread_queue.Queue(1)
        if self.failed is not None:
            out.put((None, RuntimeError(self._failure_reason())))
            return out

        def wrapped() -> None:
            try:
                out.put((fn(), None))
            except Exception as exc:  # noqa: BLE001 — delivered to caller
                out.put((None, exc))

        self._gap_control.put(wrapped)
        self._wake.set()
        return out

    def _offload_pressure(self) -> float:
        """Step-time pressure signal for the KVBM offload budget: the
        recent step wall time while sequences are live, 0 when idle (an
        idle engine's step thread is free — offload at full rate)."""
        if self._waiting or any(s is not None for s in self._slots):
            return self.stats.last_step_wall_ms
        return 0.0

    def queue_depth(self) -> tuple[int, int]:
        # Parked (preempted) sequences count as waiting: they hold live
        # client streams the admission estimators must see as backlog.
        active = sum(1 for s in self._slots if s is not None)
        return active, len(self._waiting) + len(self._parked)

    def active_kv_tokens(self) -> int:
        """KV tokens attended by live decode slots — the working-set
        input of the live roofline gauges. Read cross-thread without
        the scheduler lock: a slightly stale sum only skews a gauge."""
        total = 0
        for seq in list(self._slots):
            if seq is not None and not seq.finished and not seq.cancelled:
                total += seq.kv_len
        return total

    def reserved_pages(self) -> int:
        """Pages allocated to sequences that hold a slot: what live
        sequences have reserved of the pool, prefix-cache residue left
        out (dynamo_kv_usage_ratio counts that too). Zero once every
        sequence is reaped."""
        return sum(len(seq.alloc.cached_pages) + len(seq.alloc.new_pages)
                   for seq in list(self._slots) if seq is not None)

    def window_reserved_pages(self) -> int:
        """The same for the window group: pages its sequences HOLD (a
        reservation not yet taken is not a page)."""
        return sum(len(seq.window.pages) for seq in list(self._slots)
                   if seq is not None and seq.window is not None)

    def lora_in_flight(self, lora_slot: int) -> int:
        """Sequences (admitted, waiting, or just submitted) still bound to
        an adapter slot. Scheduler-thread only (run via run_in_step): drains
        the incoming queue first so submissions that already resolved the
        adapter are counted."""
        self._drain_incoming()
        live = [s for s in self._slots if s is not None] + self._waiting
        return sum(1 for s in live
                   if s.lora_idx == lora_slot
                   and not s.finished and not s.cancelled)

    # -- scheduler thread --------------------------------------------------

    def _loop(self) -> None:
        log.info("scheduler loop up (max_batch=%d pages=%d)",
                 self.max_batch, self.pool.num_pages)
        try:
            while not self._stop:
                self._drain_control()
                with _section("sched.drain_incoming"):
                    self._drain_incoming()
                progressed = self._step()
                if not progressed:
                    # Idle: gap work has no dispatch/drain window to ride
                    # — run it here so offload/transfer gathers never
                    # stall on an idle engine.
                    with _section("sched.gap"):
                        self._drain_gap()
                    with _section("sched.idle"):
                        self._wake.wait(timeout=0.05)
                    self._wake.clear()
        except Exception as exc:  # noqa: BLE001 — the engine's last
            # boundary: a compiler refusal or device error inside a step
            # must end the worker loudly, not leave it idle and healthy
            self._die(exc)
        # Final drain: run_in_step/run_in_gap callers block on their
        # result queue, so callbacks queued during shutdown must still
        # execute (or their waiters would hang forever).
        self._drain_control()
        self._drain_gap()

    def _failure_reason(self) -> str:
        return (f"engine thread died: {type(self.failed).__name__}: "
                f"{self.failed}")

    def _die(self, exc: BaseException) -> None:
        """An exception escaped a step (scheduler thread): fail every
        queued and in-flight request with it, then tell the owner."""
        log.exception("engine thread died", exc_info=exc)
        self.failed = exc
        try:
            self._close_frames()  # tokens the torn step had committed
            self._fail_incoming()
            self._finish_all(self._failure_reason())  # hands all over
        except Exception:  # noqa: BLE001 — state may be torn mid-step;
            # the owner must still hear about the death
            log.exception("failing live requests after engine death")
            self._end_emit()
        if self.on_fatal is not None:
            self.on_fatal(exc)

    def _fail_incoming(self) -> None:
        reason = self._failure_reason()
        while True:
            try:
                _request, emit, _handle, _extra = self._incoming.get_nowait()
            except thread_queue.Empty:
                return
            emit(EngineOutput(finish_reason="error", error=reason))

    def _drain_control(self) -> None:
        while True:
            try:
                fn = self._control.get_nowait()
            except thread_queue.Empty:
                self._end_emit()  # e.g. an abandoned transfer's frame
                return
            try:
                fn()
            except Exception:  # noqa: BLE001 — a bad control callback (e.g.
                # a deferred page release) must not kill the engine loop
                log.exception("control callback failed")

    def _drain_gap(self) -> None:
        while True:
            try:
                fn = self._gap_control.get_nowait()
            except thread_queue.Empty:
                return
            try:
                fn()
            except Exception:  # noqa: BLE001 — a bad gap callback must
                # not kill the engine loop (same contract as _drain_control)
                log.exception("gap callback failed")

    def _drain_incoming(self) -> None:
        added = False
        while True:
            try:
                request, emit, handle, extra = self._incoming.get_nowait()
            except thread_queue.Empty:
                if added:
                    # Class-strict admission order
                    # (docs/multi-tenancy.md): ONE stable sort per drain
                    # batch keeps FIFO within a class while a fresh
                    # interactive arrival overtakes every waiting batch
                    # request.
                    self._waiting.sort(key=lambda s: -s.rank)
                self._end_emit()  # refusals, drain bounces
                return
            if self.draining:
                # Vacating: anything that raced the router's draining
                # flip bounces with an in-band migrate — the Migration
                # operator replays it on a peer, tokens preserved
                # (docs/fault-tolerance.md departure ladder).
                self.stats.drain_bounced += 1
                emit(EngineOutput(finish_reason="migrate",
                                  error="worker draining; replay on a "
                                        "peer"))
                continue
            seq = self._prepare(request, emit)
            if seq is not None:
                seq.prefill_only = extra.get("prefill_only", False)
                seq.on_prefill_done = extra.get("on_prefill_done")
                seq.on_prefill_chunk = extra.get("on_prefill_chunk")
                seq.onboard_blocks = extra.get("onboard_blocks")
                seq.onboard_first_token = extra.get("onboard_first_token")
                seq.resume_state = extra.get("resume_state")
                seq.lora_idx = extra.get("lora_idx", 0)
                seq.media_embeds = extra.get("media_embeds")
                seq.record_id = extra.get("record_id")
                seq.traceparent = extra.get("traceparent")
                handle.seq = seq
                if handle._cancelled:  # cancelled before the seq existed
                    seq.cancelled = True
                self._waiting.append(seq)
                added = True

    def _page_span(self, prompt_len: int, max_tokens: int,
                   with_slack: bool = True) -> int:
        """Pages to allocate for a sequence. With slack: fused/pipelined
        decode writes up to block*depth - 1 tokens past a sequence's stop
        position before the host observes the stop, so those positions
        must land in pages this sequence owns (never a neighbour's); the
        surplus tokens are discarded at drain. Speculative verification
        overruns the same way (up to spec_k rejected-draft KV writes past
        the committed stop), so its chunk rides the same slack. Capacity
        CHECKS use the slack-free span (slack must never reject a request
        that fits) — a sequence whose slacked span exceeds capacity is
        admitted without slack and gated per-seq in _decode_block_for /
        _maybe_dispatch_spec."""
        slack = (self.decode_block * max(1, self.decode_pipeline)
                 if with_slack and self.decode_block > 1 else 0)
        if with_slack and self.spec_enabled:
            slack = max(slack, self.spec_k + 1)
        return -(-(prompt_len + max_tokens + slack) // self.page_size)

    def _prepare(self, request: PreprocessedRequest, emit) -> Optional[_Seq]:
        prompt_len = len(request.token_ids)
        total_pages = self._page_span(prompt_len,
                                      request.sampling.max_tokens,
                                      with_slack=False)
        if (prompt_len >= self.runner.config.max_context
                or total_pages > self.runner.config.max_pages_per_seq
                or total_pages > self.pool.num_pages - 1):
            emit(EngineOutput(
                finish_reason="error",
                error=(f"request needs {total_pages} pages / "
                       f"{prompt_len} prompt tokens; exceeds engine capacity"),
            ))
            return None
        block_hashes = compute_block_hashes(
            request.token_ids, self.page_size,
            lora_id=request.kv_salt())
        seed = request.sampling.seed
        if seed is None:
            seed = abs(hash(request.request_id)) & 0xFFFFFFFF
        try:
            processors = self._build_processors(request)
        except (ValueError, TypeError, KeyError) as exc:
            emit(EngineOutput(finish_reason="error",
                              error=f"logits processors: {exc}"))
            return None
        if processors and self.cache_plan.state:
            # The host-sampling path regenerates the first token by
            # running the last prompt token AGAIN (an idempotent KV
            # rewrite); a recurrent state would absorb it twice.
            emit(EngineOutput(
                finish_reason="error",
                error="logits processors (logit_bias, penalties, "
                      "min_tokens, min_p, guided decoding) are not "
                      "supported on a model with recurrent state"))
            return None
        seq = _Seq(
            request=request, emit=emit, block_hashes=block_hashes,
            alloc=PageAllocation([], [], 0),
            block_table=np.zeros(self.runner.config.max_pages_per_seq,
                                 np.int32),
            slot=-1, prompt_len=prompt_len, prefill_pos=0, seed=seed,
            processors=processors,
            priority_class=request.priority or "standard",
        )
        if self.spec_enabled:
            stop_ids = set(request.stop.stop_token_ids)
            if not request.stop.ignore_eos:
                stop_ids |= set(request.eos_token_ids)
            hasher = TokenBlockSequence(self.page_size,
                                        lora_id=request.kv_salt())
            hasher.extend(request.token_ids)
            seq.spec = SlotSpec(
                proposer=NGramProposer(request.token_ids),
                stop_ids=frozenset(stop_ids), hasher=hasher)
        return seq

    def _build_processors(self, request: PreprocessedRequest):
        """Instantiate the request's logits processors (explicit specs +
        implicit ones for logit_bias and penalties). Non-empty switches
        the sequence onto the host-sampling decode path."""
        from ..llm.logits_processing import (
            LogitBiasProcessor,
            MinPProcessor,
            MinTokensProcessor,
            PenaltyProcessor,
            RepetitionPenaltyProcessor,
            resolve_processors,
        )

        procs: list = []
        s = request.sampling
        if s.logit_bias:
            procs.append(LogitBiasProcessor(
                {int(k): float(v) for k, v in s.logit_bias.items()}))
        if s.frequency_penalty or s.presence_penalty:
            procs.append(PenaltyProcessor(s.frequency_penalty,
                                          s.presence_penalty))
        if getattr(s, "repetition_penalty", 1.0) != 1.0:
            # HF semantics penalize prompt AND generated tokens
            procs.append(RepetitionPenaltyProcessor(
                s.repetition_penalty, prompt_ids=request.token_ids))
        if request.stop.min_tokens:
            procs.append(MinTokensProcessor(
                request.stop.min_tokens,
                list(request.eos_token_ids)
                + list(request.stop.stop_token_ids)))
        if request.logits_processors:
            procs.extend(resolve_processors(
                request.logits_processors,
                tokenizer=getattr(self, "logits_tokenizer", None)))
        if s.min_p and s.temperature > 0:
            # temperature 0 is argmax — min_p can never change it, and
            # building the processor would force the per-step host
            # readback path for nothing. LAST: the min_p floor is
            # relative to the max probability of the distribution
            # actually sampled from — after guided/user processors have
            # masked it. Ordered before them it would prune against the
            # unconstrained distribution and could mask every
            # grammar-legal token (all -inf row).
            procs.append(MinPProcessor(s.min_p, s.temperature))
        return procs or None

    def _admit(self, allow_preempt: bool = False) -> int:
        admitted = 0
        while self._waiting:
            seq = self._waiting[0]
            if seq.cancelled:
                self._waiting.pop(0)
                if seq.window is not None:  # reserved, never admitted
                    self.win_pool.release(seq.window)
                    seq.window = None
                continue
            # A parked sequence of the head's class or better resumes
            # BEFORE the head admits (it was admitted first — letting a
            # waiting batch request grab the slot ahead of a parked
            # standard sequence would be the parked-entry inversion all
            # over again, on the engine).
            if allow_preempt and self._resume_parked(limit=1,
                                                     min_rank=seq.rank):
                admitted += 1
                continue
            free_slots = [i for i, s in enumerate(self._slots) if s is None]
            if not free_slots:
                # Interactive pressure, no slot: preempt a lower-class
                # decode slot (park-to-KVBM or cooperative migrate) and
                # retry. allow_preempt only on the step's FIRST admit
                # pass — the late pass runs with a decode block in
                # flight whose drain would append tokens to a victim
                # that no longer owns its pages.
                if allow_preempt and self._try_preempt_for(seq, "slot"):
                    continue
                break
            total_pages = self._page_span(seq.prompt_len,
                                          seq.request.sampling.max_tokens)
            seq.slack_ok = (
                total_pages <= self.runner.config.max_pages_per_seq
                and total_pages <= self.pool.num_pages - 1)
            if not seq.slack_ok:
                total_pages = self._page_span(
                    seq.prompt_len, seq.request.sampling.max_tokens,
                    with_slack=False)
            if self.win_pool is not None and seq.window is None:
                # The window group first (it has nothing to undo): what
                # a decoding row holds at most, or the whole sequence
                # where that is less, set aside for as long as it lives.
                seq.window = self.win_pool.reserve(min(
                    total_pages,
                    self.win_pool.bound(self._win_lookahead)))
                if seq.window is None:
                    if allow_preempt and self._try_preempt_for(seq,
                                                               "window"):
                        continue
                    break
            alloc = self.pool.allocate(seq.block_hashes, total_pages)
            if alloc is None:
                # Page starvation is the other preemption trigger: a
                # parked batch slot returns its pages to the pool.
                if allow_preempt and self._try_preempt_for(seq, "full"):
                    continue
                break  # no pages; retry next iteration
            # Never skip the whole prompt: recompute at least the last token
            # so we have logits to sample from (cached pages stay correct —
            # recomputed KV values are identical).
            cached_tokens = min(alloc.cached_blocks * self.page_size,
                                seq.prompt_len - 1)
            seq.alloc = alloc
            pages = alloc.pages
            seq.block_table[: len(pages)] = pages
            seq.prefill_pos = cached_tokens
            # Disagg-decode sequences carry their KV in onboard_blocks; the
            # KVBM lookup would be redundant (and overwritten) for them.
            if self.kvbm is not None and seq.onboard_blocks is None:
                self._onboard_from_kvbm(seq)
            seq.slot = free_slots[0]
            self._slots[seq.slot] = seq
            self._waiting.pop(0)
            if seq.record_id is not None:
                # Admission = end of queue wait (first write wins, so a
                # page-starved retry next iteration can't move it).
                get_recorder().stamp(seq.record_id, "scheduled")
            admitted += 1
            if seq.onboard_blocks is not None:
                if seq.resume_state is not None:
                    self._onboard_resume(seq)
                else:
                    self._onboard(seq)
        if allow_preempt:
            # Pressure check ran: parked sequences resume when slots and
            # pages are back and nothing higher-class is still waiting.
            admitted += self._resume_parked()
        # An onboarded first token, a victim's migrate, a parked
        # sequence's deadline: out now, not behind the step's drain.
        self._end_emit()
        return admitted

    # -- preempt-to-KVBM (docs/multi-tenancy.md) ---------------------------

    def _park_capacity_ok(self) -> bool:
        return (self.kvbm is not None
                and hasattr(self.kvbm, "park_sequence")
                and len(self._parked) < self.preempt_max_parked)

    def _preempt_victim(self, head_rank: int) -> Optional[_Seq]:
        """The cheapest lower-class decode slot to evict: lowest class
        first, then fewest generated tokens (least KV to move / least
        work to replay), then slot index for determinism. Only plain
        decode-ready slots qualify — prefill-only / transfer-owning /
        first-token-deferred sequences hold state a park cannot carry."""
        best = None
        for seq in self._slots:
            if seq is None or seq.finished or seq.cancelled:
                continue
            if seq.prefill_only or seq.keep_pages or seq.first_deferred:
                continue
            if not seq.decode_ready or not seq.generated:
                continue
            if seq.rank >= head_rank:
                continue
            key = (seq.rank, len(seq.generated), seq.slot)
            if best is None or key < best[0]:
                best = (key, seq)
        return best[1] if best is not None else None

    def _try_preempt_for(self, head: _Seq, short_of: str) -> bool:
        """Free a slot (and its pages) for `head` by preempting a
        lower-class victim; `short_of` is what admission ran out of (a
        `slot`, the `full` page group, the `window` group: the counter's
        `group` label). Returns True only when the park path freed
        capacity NOW (caller retries admission); a migrate fallback
        returns False — its slot and pages come back at reap, END of
        this step, so retrying inside this pass would only cascade into
        migrating every lower-class slot for one waiting head."""
        if not self.preempt_enabled:
            return False
        victim = self._preempt_victim(head.rank)
        if victim is None:
            return False
        return self._preempt_seq(victim, short_of)

    def _preempt_seq(self, victim: _Seq, short_of: str = "slot") -> bool:
        """Preempt one decode slot: gather its computed pages into the
        KVBM park store and park the sequence (resume continues the
        committed stream bit-identically — seed, step count, processor
        and spec state all stay live on the _Seq), or fall back to the
        cooperative in-band migrate the frontend Migration operator
        replays on a peer worker. Returns whether the PARK path freed
        the slot and pages immediately (migrate frees them at reap)."""
        from ..runtime.metrics import PREEMPT_TOTAL
        from ..runtime.otel import get_tracer

        rid = victim.request.request_id
        # KV present on device: positions 0..kv_len-2 (the last
        # generated token's KV is written by its NEXT decode step).
        computed = max(0, victim.kv_len - 1)
        n_pages = -(-computed // self.page_size) if computed else 0
        span = get_tracer().start_span(
            "scheduler.preempt",
            parent=victim.traceparent
            or (victim.request.annotations or {}).get("traceparent"),
            **{"request.id": rid, "class": victim.priority_class,
               "pages": n_pages,
               "tokens.preserved": len(victim.generated)})
        parked = False
        try:
            if self._park_capacity_ok() and n_pages > 0:
                ids = np.asarray(victim.block_table[:n_pages], np.int32)
                # One blocking D2H per preemption: preemption is rare
                # and the pages must be on host BEFORE they return to
                # the pool (a release-then-gather would race the next
                # allocation).
                bundle = np.asarray(self.runner.gather_pages_device(ids))  # dynalint: disable=DL201 -- park bundle must land on host before the pages free # dynajit: disable=DJ201 -- designed preemption drain: pages are released right after
                parked = bool(self.kvbm.park_sequence(rid, bundle))
            span.set_attribute("kind", "park" if parked else "migrate")
            if parked:
                self.pool.release(
                    victim.alloc, victim.block_hashes,
                    computed_blocks=victim.prefill_pos // self.page_size)
                self._slots[victim.slot] = None
                victim.slot = -1
                victim.alloc = PageAllocation([], [], 0)
                victim.parked_pages = n_pages
                self._parked.append(victim)
                self.stats.preempt_parked += 1
                PREEMPT_TOTAL.labels(kind="park", group=short_of).inc()
                _observe_preempt(f"{id(self)}:{rid}", "park")
                get_recorder().event(victim.record_id, "preempt",
                                     kind="park", pages=n_pages,
                                     tokens_preserved=len(victim.generated))
                log.info("preempted %s to KVBM (%d pages, %d tokens kept)",
                         rid, n_pages, len(victim.generated))
            else:
                # Cooperative migrate: the Migration operator replays
                # prompt+generated on a peer (or here, later) under the
                # DYNT_PREEMPT_MIGRATION_LIMIT bound. Reap releases the
                # pages.
                victim.finished = True
                self.stats.preempt_migrated += 1
                PREEMPT_TOTAL.labels(kind="migrate", group=short_of).inc()
                _observe_preempt(f"{id(self)}:{rid}", "migrate")
                get_recorder().event(victim.record_id, "preempt",
                                     kind="migrate",
                                     tokens_preserved=len(victim.generated))
                victim.emit(EngineOutput(
                    finish_reason="migrate",
                    error="preempted under interactive pressure"))
                log.info("preempted %s via cooperative migrate", rid)
        finally:
            span.end(ok=True)
        return parked

    def _resume_parked(self, limit: Optional[int] = None,
                       min_rank: int = -1) -> int:
        """Resume parked sequences when pressure clears: a free slot,
        pages available, and no higher-class request still waiting
        (`min_rank` additionally restricts candidates — the admit loop
        uses it to resume only entries that outrank the waiting head).
        Deadline budgets kept burning across the park — an expired
        sequence is finished honestly instead of resumed into a reply
        nobody is waiting for."""
        from ..runtime.metrics import PREEMPT_TOTAL

        if not self._parked:
            return 0
        waiting_rank = max(
            (s.rank for s in self._waiting if not s.cancelled), default=-1)
        resumed = 0
        # Higher class resumes first; park order (FIFO) within a class.
        for seq in sorted(self._parked, key=lambda s: -s.rank):
            if limit is not None and resumed >= limit:
                break
            rid = seq.request.request_id
            if seq.cancelled:
                self._parked.remove(seq)
                self._drop_parked(rid)
                _observe_preempt(f"{id(self)}:{rid}", "drop")
                continue
            deadline = seq.request.deadline
            if deadline is not None and deadline.expired():
                self._parked.remove(seq)
                self._drop_parked(rid)
                seq.finished = True
                get_recorder().event(seq.record_id, "preempt",
                                     kind="expired")
                _observe_preempt(f"{id(self)}:{rid}", "expire")
                seq.emit(EngineOutput(
                    finish_reason="error",
                    error="deadline exceeded while preempted"))
                continue
            if seq.rank < waiting_rank or seq.rank < min_rank:
                continue  # pressure persists: stay parked
            free_slots = [i for i, s in enumerate(self._slots)
                          if s is None]
            if not free_slots:
                break
            total_pages = self._page_span(seq.prompt_len,
                                          seq.request.sampling.max_tokens)
            seq.slack_ok = (
                total_pages <= self.runner.config.max_pages_per_seq
                and total_pages <= self.pool.num_pages - 1)
            if not seq.slack_ok:
                total_pages = self._page_span(
                    seq.prompt_len, seq.request.sampling.max_tokens,
                    with_slack=False)
            alloc = self.pool.allocate(seq.block_hashes, total_pages)
            if alloc is None:
                break
            bundle = self.kvbm.claim_parked(rid)
            if bundle is None:
                # Park store lost the bundle (should not happen — the
                # store is eviction-free — but a resume MUST NOT scatter
                # garbage): degrade to cooperative migrate.
                self.pool.release(alloc, seq.block_hashes,
                                  computed_blocks=0)
                self._parked.remove(seq)
                seq.finished = True
                self.stats.preempt_migrated += 1
                PREEMPT_TOTAL.labels(kind="migrate", group="full").inc()
                _observe_preempt(f"{id(self)}:{rid}", "migrate")
                seq.emit(EngineOutput(
                    finish_reason="migrate",
                    error="park bundle lost; replay elsewhere"))
                continue
            self._parked.remove(seq)
            seq.alloc = alloc
            pages = alloc.pages
            seq.block_table[: len(pages)] = pages
            # Cached prompt-prefix pages already hold identical KV
            # (same hash chain => same bytes); scatter only the
            # non-cached span of the park bundle, like _onboard.
            cached_n = min(alloc.cached_blocks, seq.parked_pages)
            target = seq.block_table[cached_n: seq.parked_pages]
            if len(target):
                self.runner.scatter_pages(
                    np.asarray(target, np.int32),  # dynalint: disable=DL201 -- host block-table slice to int32, no device transfer
                    bundle[cached_n:])
            seq.slot = free_slots[0]
            self._slots[seq.slot] = seq
            seq.parked_pages = 0
            self.stats.preempt_resumed += 1
            PREEMPT_TOTAL.labels(kind="resume", group="full").inc()
            _observe_preempt(f"{id(self)}:{rid}", "resume")
            get_recorder().event(seq.record_id, "preempt", kind="resume",
                                 tokens_preserved=len(seq.generated))
            log.info("resumed parked %s (%d tokens preserved)",
                     rid, len(seq.generated))
            resumed += 1
        return resumed

    def _drop_parked(self, rid: str) -> None:
        if self.kvbm is not None and hasattr(self.kvbm, "drop_parked"):
            self.kvbm.drop_parked(rid)

    def _onboard_from_kvbm(self, seq: _Seq) -> None:
        """KVBM onboard at admission (ref §3.5 onboard flows): prompt
        blocks missed in the G1 prefix cache but present in G2/G3/G4 are
        scattered into the freshly allocated pages instead of prefilled.
        Keeps at least one prompt token for recompute (logits source)."""
        cached_n = seq.alloc.cached_blocks
        # Only blocks fully inside prompt_len - 1 can skip compute.
        max_blocks = (seq.prompt_len - 1) // self.page_size
        candidates = seq.block_hashes[cached_n:max_blocks]
        if not candidates:
            return
        n = self.kvbm.match_prefix(candidates)
        if n == 0:
            return
        from ..runtime.otel import get_tracer

        # Onload is synchronous on the request's critical path (it
        # replaces prefill compute): parent it under the worker span so
        # the trade shows up inside the worker leg that performed it
        # (annotation fallback for bare-scheduler callers).
        span = get_tracer().start_span(
            "kvbm.onload",
            parent=seq.traceparent
            or (seq.request.annotations or {}).get("traceparent"),
            **{"request.id": seq.request.request_id, "blocks": n})
        ok = False
        miss = False
        try:
            target = seq.block_table[cached_n : cached_n + n]
            if hasattr(self.kvbm, "onboard_direct"):
                # Distributed KVBM: the bytes never assemble on one host —
                # every rank scatters its own shards (mirrored call).
                if not self.kvbm.onboard_direct(
                        candidates[:n], np.asarray(target, np.int32),
                        self.runner):
                    miss = True
                    return
            else:
                bundle = self.kvbm.read_blocks(candidates[:n])
                if bundle is None:
                    miss = True
                    return
                self.runner.scatter_pages(np.asarray(target, np.int32),
                                          bundle)
            ok = True
        finally:
            if miss:
                # Block evicted between match and read (or a rank
                # declined): a designed degrade to recompute, not an
                # error — a healthy request must export no ERROR spans.
                span.add_event("miss")
                span.end(ok=True)
            else:
                span.end(ok=ok)
        if seq.record_id is not None:
            get_recorder().event(seq.record_id, "kvbm_onload", blocks=n,
                                 tokens=n * self.page_size)
        seq.prefill_pos = (cached_n + n) * self.page_size
        self.stats.kvbm_onboarded_blocks += n
        log.info("kvbm onboard: %d blocks (skipping %d prefill tokens) for %s",
                 n, n * self.page_size, seq.request.request_id)

    def _onboard(self, seq: _Seq) -> None:
        """Disagg decode side: scatter pulled prefill KV into this pool and
        enter decode directly (no prefill pass). Cached prefix pages already
        hold identical KV (same hash chain => same tokens); only the
        non-cached suffix is written."""
        n_prompt_pages = -(-seq.prompt_len // self.page_size)
        blocks = seq.onboard_blocks
        cached_n = min(seq.alloc.cached_blocks, n_prompt_pages)
        target_pages = seq.block_table[cached_n:n_prompt_pages]
        part = blocks[cached_n:n_prompt_pages]
        if len(target_pages):
            self.runner.scatter_pages(np.asarray(target_pages, np.int32),
                                      part)
        seq.onboard_blocks = None  # free host memory
        seq.prefill_pos = seq.prompt_len
        if seq.processors:
            # The prefill worker sampled the first token on device with
            # no processors applied — discard it and let the first
            # decode step regenerate its logits through the host path
            # (same idempotent-rewrite trick as _defer_first_token).
            self._defer_first_token(seq)
            return
        self._append_token(seq, int(seq.onboard_first_token),
                           prompt_tokens=seq.prompt_len)

    def _onboard_resume(self, seq: _Seq) -> None:
        """Drain-handoff destination (docs/fault-tolerance.md): the
        pulled bundle covers every COMPUTED position — prompt AND
        generated tokens up to kv_len-2 (the last generated token's KV
        is written by its next decode step, exactly as on the source).
        Scatter it, restore seed / step count / generated history, and
        continue decoding: the (seed, step) sampler fold-in keys pick up
        where the source stopped, so greedy, temperature, and
        spec-active streams all continue byte-for-byte. Nothing is
        emitted here — the already-delivered tokens stay delivered; the
        source reported prompt_tokens on ITS first frame, so re-emitting
        usage here would double-count."""
        state = seq.resume_state or {}
        blocks = seq.onboard_blocks
        gen = [int(t) for t in (state.get("generated") or [])]
        n_pages = int(blocks.shape[0])
        # Cached prompt-prefix pages already hold identical KV (same
        # chained hashes => same bytes); scatter only the rest, like
        # _onboard. The cache can only ever cover prompt blocks, so
        # cached_n never reaches into the generated span.
        cached_n = min(seq.alloc.cached_blocks, n_pages)
        target = seq.block_table[cached_n:n_pages]
        if len(target):
            self.runner.scatter_pages(np.asarray(target, np.int32),  # dynalint: disable=DL201 -- host block-table slice to int32, no device transfer
                                      blocks[cached_n:])
        seq.onboard_blocks = None  # free host memory
        seq.prefill_pos = seq.prompt_len
        seq.generated = gen
        seq.last_token = (gen[-1] if gen
                          else int(seq.request.token_ids[-1]))
        if state.get("seed") is not None:
            seq.seed = int(state["seed"]) & 0xFFFFFFFF
        if seq.spec is not None and gen:
            # The proposer index and block-hash chain must reflect the
            # full committed history before the next proposal.
            seq.spec.extend(gen)
        self.stats.drain_resumed += 1
        if seq.record_id is not None:
            get_recorder().event(seq.record_id, "drain_resume",
                                 pages=n_pages,
                                 tokens_preserved=len(gen))
        log.info("resumed drained %s (%d tokens preserved, %d pages "
                 "pulled)", seq.request.request_id, len(gen), n_pages)

    def _step(self) -> bool:
        start = time.monotonic()
        self.steptrace.begin()
        # Preemption/resume only on this first admit pass: no decode
        # block is in flight yet, so a victim's pages can be gathered
        # and released without racing a pending drain.
        with _section("sched.admit"):
            admitted = self._admit(allow_preempt=True)
        # Deferred prefill tokens from the PREVIOUS iteration: their
        # device work was queued before this iteration's dispatches, so
        # by the time we materialize them below the result is (nearly)
        # always already sitting in host-visible memory.
        ripe = self._pending_prefill
        self._pending_prefill = []
        # Dispatch decode FIRST (async — no readback): the fused block(s)
        # execute on device while the host runs prefill prep + dispatch
        # and admits fresh arrivals below. The readback in _drain_decode
        # is the loop's only blocking device sync.
        pending = self._dispatch_decode()
        prefill_tokens = self._prefill_some()
        # First tokens of rows that read back in the call (logprobs,
        # prefill-only, ring) and a streamed transfer's first params.
        self._end_emit()
        # Overlap window: arrivals that landed during dispatch are
        # admitted while the device is still stepping the decode block.
        with _section("sched.drain_incoming"):
            self._drain_incoming()
        with _section("sched.admit"):
            late = self._admit()
        admitted += late
        # Gap work (KVBM offload gathers, streaming transfer gathers)
        # runs HERE — the decode block is in flight on device, the host
        # would otherwise idle until the drain, and the dispatched device
        # ops queue behind the block so they never delay it.
        with _section("sched.gap"):
            self._drain_gap()
        # "blocks" handles are genuinely in flight here; a "count" handle
        # means _decode_single already read back (host-sampling path).
        if pending is not None and pending[0] == "blocks" and late:
            self.stats.admitted_during_inflight += late
        finalized = 0
        with _section("sched.finalize_prefill"):
            for seq, tok_dev in ripe:
                finalized += self._finalize_prefill(seq, tok_dev)
            # First tokens leave here, before the decode block's drain.
            with self.steptrace.emit(section=False):
                self._end_emit()
        decode_tokens = self._drain_decode(pending)
        # Pages the step's sequences held while it ran: taken before the
        # reap returns the finished ones' (at most max_batch slots).
        reserved = self.reserved_pages()
        win_reserved = (self.window_reserved_pages()
                        if self.win_pool is not None else 0)
        held_slots = sum(s is not None for s in self._slots)
        with _section("sched.reap"):
            self._reap_finished()
        if prefill_tokens or decode_tokens or admitted or finalized:
            self.stats.steps += 1
            self.stats.prefill_tokens += prefill_tokens
            self.stats.decode_tokens += decode_tokens
            self.stats.prefill_tokens_last_step = prefill_tokens
            self.stats.decode_tokens_last_step = decode_tokens
            self.stats.last_step_wall_ms = (time.monotonic() - start) * 1e3
            self.stats.reserved_page_ms += (
                reserved * self.stats.last_step_wall_ms)
            self.stats.window_reserved_page_ms += (
                win_reserved * self.stats.last_step_wall_ms)
            if self.cache_plan.state:
                self.stats.state_slot_ms += (
                    held_slots * self.stats.last_step_wall_ms)
            moe = getattr(self.runner, "moe_stats", None)
            if moe is not None:
                self.stats.moe_counts = moe()
            sample = self.steptrace.commit(self.stats.last_step_wall_ms)
            self.stats.device_ms_last_step = sample.device_ms
            self.stats.host_ms_last_step = sample.host_ms
            return True
        return False

    def _prefill_some(self) -> int:
        """Advance one sequence's prefill by up to one chunk (or, for long
        prompts on an sp>1 mesh, the WHOLE prompt in one sequence-parallel
        ring-attention step — ops/ring_attention.py)."""
        budget = self.runner.max_prefill_chunk

        def _ring_eligible(seq) -> bool:
            return (seq.prefill_pos == 0
                    and seq.prompt_len > budget
                    and seq.lora_idx == 0  # ring path has no adapter delta
                    and seq.media_embeds is None  # nor embed splicing
                    and getattr(self.runner, "sp_size", 1) > 1)

        # Long prompts on an sp>1 mesh: batch EVERY eligible sequence into
        # ONE ring step ([B, bucket] — long-prompt pools batch instead of
        # paying one full ring pass per sequence).
        ring = [seq for seq in self._slots
                if seq is not None and not seq.cancelled
                and not seq.decode_ready and _ring_eligible(seq)]
        if ring:
            tokens = 0
            for seq in ring:
                if seq.record_id is not None and not seq.prefill_stamped:
                    seq.prefill_stamped = True
                    get_recorder().stamp(seq.record_id, "prefill_start")
            for seq in ring:
                if seq.prefill_submit_ts is None:
                    seq.prefill_submit_ts = time.monotonic()
            # The ring step materializes its samples in-call: one
            # blocking device window covering the whole batched pass.
            self.stats.prefill_launches += 1
            with self.steptrace.sync("prefill", self.stats.steps) as rsc:
                result = self.runner.prefill_ring_batch(
                    [np.asarray(s.request.token_ids[: s.prompt_len],  # dynalint: disable=DL201 -- host token list to int32, no device transfer
                                np.int32)
                     for s in ring],
                    np.stack([s.block_table for s in ring]),
                    [(s.request.sampling.temperature,
                      s.request.sampling.top_p,
                      s.request.sampling.top_k, s.seed) for s in ring],
                )
            self._stamp_builds(ring)
            for seq in ring:
                seq.device_prefill_ms += rsc.device_ms
            samples = getattr(self.runner, "last_prefill_samples",
                              [None] * len(ring))
            for seq, token, info in zip(ring, result, samples):
                seq.prefill_pos = seq.prompt_len
                tokens += seq.prompt_len
                if seq.prefill_only:
                    self._finish_prefill_only(seq, token)
                elif seq.processors:
                    self._defer_first_token(seq)
                else:
                    self._append_token(seq, token,
                                       prompt_tokens=seq.prompt_len,
                                       sample_info=info)
            return tokens
        # One chunk per prefilling sequence, filling the SHARED token
        # budget across sequences (decode-ITL protection is the total
        # budget per iteration, not one-sequence-per-iteration). Several
        # sequences' chunks go out as ONE batched dispatch when possible
        # (prefill_chunk_batch) — the cross-sequence shape fix for
        # low-MFU small-model prefill (VERDICT item 10: a [1, chunk]
        # forward at 0.6B leaves the MXU idle; [B, chunk] restores the
        # arithmetic intensity without spending more step-time budget).
        with _section("sched.prefill_prep"):
            work = self._prefill_work(budget)
        if not work:
            return 0
        if len(work) > 1 and self._can_batch_prefill(work):
            return self._prefill_batch(work)
        total = 0
        for seq, chunk in work:
            total += self._prefill_single(seq, chunk)
        return total

    def _prefill_work(self, budget: int) -> list:
        """This iteration's (sequence, chunk) rows in slot order, filling
        the shared token budget; stamps `prefill_start` on a sequence's
        first chunk."""
        work: list[tuple[_Seq, int]] = []
        spent = 0
        # the runner pads each row to its smallest bucket: more rows
        # than the budget holds of those wait for the next launch
        max_rows = getattr(self.runner, "max_prefill_rows", len(self._slots))
        for seq in self._slots:
            if seq is None or seq.cancelled or seq.decode_ready:
                continue
            if (budget - spent < min(self.page_size, budget)
                    or len(work) >= max_rows):
                break  # leftover budget too small to be worth a dispatch,
                #        or the launch has its rows
            per = budget - spent
            if (seq.prefill_only and seq.on_prefill_chunk is not None
                    and self.disagg_chunk > 0):
                # Disagg handoff granularity: smaller chunks start the
                # KV stream earlier (docs/disaggregation.md).
                per = min(per, self.disagg_chunk)
            chunk = min(per, seq.prompt_len - seq.prefill_pos)
            if chunk <= 0:
                continue
            if not self._launch_takes(work, seq, chunk):
                continue
            if seq.record_id is not None and not seq.prefill_stamped:
                # First chunk of real prefill compute only.
                seq.prefill_stamped = True
                get_recorder().stamp(seq.record_id, "prefill_start")
            work.append((seq, chunk))
            spent += chunk
        return work

    def _launch_takes(self, work: list, seq: _Seq, chunk: int) -> bool:
        """Whether this prefill launch can hold one more row. Any, but
        where the runner bounds its launches (`ModelRunner.
        bounds_prefill_launches`): the launch's rows x bucket stay
        inside the token budget; and where there is a window group its
        pages for the chunk are taken here: the blocks before the oldest
        position the chunk's first query sees go back, those up to its
        last position are allocated. A row that gets none waits for a
        later launch."""
        if (getattr(self.runner, "bounds_prefill_launches", False)
                and not self.runner.prefill_launch_fits(
                    [c for _, c in work] + [chunk])):
            return False
        if self.win_pool is None:
            return True
        pos = seq.prefill_pos
        return self.win_pool.advance(
            seq.window, max(0, pos - self.win_pool.window + 1),
            pos + chunk - 1, "prefill")

    def _trim_window(self, seq: _Seq) -> None:
        """Behind a prefill chunk's launch: what its NEXT launch (a chunk
        or the first decode step, at `prefill_pos`) cannot see goes back
        at once, so that between launches a row holds the window and no
        more. Programs run in the order they were launched, so whoever
        gets these pages writes them after this chunk has read them."""
        if self.win_pool is not None:
            # one position more than the next chunk reads: a sequence
            # with logits processors runs its last prompt token again
            self.win_pool.advance(
                seq.window, max(0, seq.prefill_pos - self.win_pool.window),
                seq.prefill_pos - 1, "prefill")

    def _window_arg(self, seq: _Seq) -> Optional[tuple]:
        """A prefill row's window group: its pages from its first held
        block on, and that block's first position. None where the cache
        has one page group."""
        if seq.window is None:
            return None
        return (seq.window.pages, seq.window.first * self.page_size)

    def _can_batch_prefill(self, work: list) -> bool:
        """Cross-sequence chunk batching requires a runner with the
        batched entry point, no per-row embed splicing, and no mirrored
        multihost driver (the batch call is not on the mirrored-launch
        protocol, like the spec step)."""
        return (hasattr(self.runner, "prefill_chunk_batch")
                and not getattr(self.runner, "is_mirrored", False)
                and all(s.media_embeds is None for s, _ in work))

    def _prefill_single(self, seq: _Seq, chunk: int) -> int:
        tokens = np.asarray(  # dynalint: disable=DL201 -- host token list to int32, no device transfer
            seq.request.token_ids[seq.prefill_pos : seq.prefill_pos + chunk],
            np.int32,
        )
        is_final = seq.prefill_pos + chunk >= seq.prompt_len
        self._count_prefill_rows([is_final])
        sampling = seq.request.sampling
        chunk_embeds = None
        if seq.media_embeds is not None:
            chunk_embeds = self._chunk_media_embeds(seq, tokens)
        # Skip the host readback wherever the token is not needed NOW:
        # non-final chunks discard it, and plain final chunks defer it
        # one iteration (_pending_prefill) so the int() conversion
        # never serializes the loop on the in-flight decode block.
        # Sync only where the host needs more than the token id:
        # logprobs (sample info), prefill_only (transfer params), and
        # processor sequences (which discard it anyway but finish
        # through _defer_first_token immediately).
        defer = (is_final and not seq.prefill_only
                 and not seq.processors and not sampling.logprobs)
        deferred_readback = defer or not is_final
        # Async chunks stamp dispatch-submit only (their device window
        # closes at the deferred drain); sync chunks (prefill_only /
        # processors / logprobs need the token NOW) are one blocking
        # call — the whole duration is device window.
        scope = (self.steptrace.dispatch("prefill", self.stats.steps)
                 if deferred_readback
                 else self.steptrace.sync("prefill", self.stats.steps))
        if seq.prefill_submit_ts is None:
            seq.prefill_submit_ts = time.monotonic()
        self.stats.prefill_launches += 1
        with scope:
            token = self.runner.prefill_chunk(
                tokens, seq.prefill_pos, seq.block_table,
                kv_len_after=seq.prefill_pos + chunk,
                sampling=(sampling.temperature, sampling.top_p,
                          sampling.top_k, seq.seed),
                lora_idx=seq.lora_idx,
                chunk_embeds=chunk_embeds,
                return_device=deferred_readback,
                slot=seq.slot, window=self._window_arg(seq),
            )
        self._stamp_builds((seq,))
        if not deferred_readback:
            # Device-stream completion window of the whole prompt pass:
            # first chunk dispatched -> final token materialized.
            seq.device_prefill_ms = max(
                0.0, (time.monotonic() - seq.prefill_submit_ts) * 1e3)
        seq.prefill_pos += chunk
        self._trim_window(seq)
        if is_final:
            if defer:
                self._pending_prefill.append((seq, token))
            elif seq.prefill_only:
                self._finish_prefill_only(seq, token)
            elif seq.processors:
                self._defer_first_token(seq)
            else:
                self._append_token(
                    seq, token, prompt_tokens=seq.prompt_len,
                    sample_info=getattr(self.runner,
                                        "last_prefill_sample", None))
        else:
            self._stream_prefill_chunk(seq)
        return chunk

    def _stamp_builds(self, seqs) -> None:
        """After a dispatch: if it built a program (a first launch of its
        shape, or a retrace), the launch's requests are the cause, and
        each timeline says so (`program_built` in `/debug/requests`: why
        this request's stage took the seconds it did)."""
        built = take_builds()
        if not built:
            return
        ids = [s.record_id for s in seqs
               if s is not None and s.record_id is not None]
        for rec in built:
            rec["cause"] = ids or rec["cause"]
            seconds = round(
                rec["trace_s"] + rec["lower_s"] + rec["backend_s"], 3)
            for rid in ids:
                get_recorder().event(rid, "program_built", fn=rec["fn"],
                                     key=rec["key"], seconds=seconds)

    def _count_prefill_rows(self, finals: list) -> None:
        last = sum(finals)
        self.stats.prefill_rows_last += last
        self.stats.prefill_rows_earlier += len(finals) - last

    def _prefill_batch(self, work: list) -> int:
        """Dispatch several sequences' prefill chunks in ONE compiled
        call (ModelRunner.prefill_chunk_batch). Per-row results are
        bit-identical to the single-dispatch path (the sampler is
        row-independent), so final-chunk handling mirrors
        _prefill_single exactly."""
        finals = [seq.prefill_pos + chunk >= seq.prompt_len
                  for seq, chunk in work]
        self._count_prefill_rows(finals)
        rows = []
        with _section("sched.prefill_prep"):
            for seq, chunk in work:
                tokens = np.asarray(  # dynalint: disable=DL201 -- host token list to int32, no device transfer
                    seq.request.token_ids[
                        seq.prefill_pos : seq.prefill_pos + chunk],
                    np.int32,
                )
                s = seq.request.sampling
                rows.append(PrefillRow(
                    tokens, seq.prefill_pos, seq.block_table,
                    seq.prefill_pos + chunk,
                    (s.temperature, s.top_p, s.top_k, seq.seed),
                    seq.lora_idx, seq.slot, self._window_arg(seq)))
        want_samples = any(
            final and seq.request.sampling.logprobs
            for final, (seq, _) in zip(finals, work))
        now = time.monotonic()
        for seq, _chunk in work:
            if seq.prefill_submit_ts is None:
                seq.prefill_submit_ts = now
        self.stats.prefill_launches += 1
        with self.steptrace.dispatch("prefill", self.stats.steps):
            toks_dev = self.runner.prefill_chunk_batch(
                rows, want_samples=want_samples)
        self._stamp_builds([seq for seq, _chunk in work])
        samples = (self.runner.last_prefill_samples
                   if want_samples else [None] * len(work))
        self.stats.prefill_batched_steps += 1
        host_toks = None
        total = 0
        for row, ((seq, chunk), is_final) in enumerate(zip(work, finals)):
            seq.prefill_pos += chunk
            self._trim_window(seq)
            total += chunk
            if not is_final:
                self._stream_prefill_chunk(seq)
                continue
            defer = (not seq.prefill_only and not seq.processors
                     and not seq.request.sampling.logprobs)
            if defer:
                self._pending_prefill.append((seq, toks_dev[row]))
                continue
            if host_toks is None:
                with self.steptrace.drain("prefill"):
                    host_toks = np.asarray(toks_dev)  # dynalint: disable=DL201 -- sync rows need their token now (prefill_only/logprobs), same contract as the single-dispatch path # dynajit: disable=DJ201 -- same designed drain
            seq.device_prefill_ms = max(
                0.0, (time.monotonic() - seq.prefill_submit_ts) * 1e3)
            if seq.prefill_only:
                self._finish_prefill_only(seq, int(host_toks[row]))
            elif seq.processors:
                self._defer_first_token(seq)
            else:
                self._append_token(
                    seq, int(host_toks[row]), prompt_tokens=seq.prompt_len,
                    sample_info=samples[row])
        return total

    def _stream_prefill_chunk(self, seq: _Seq) -> None:
        """Disagg chunked handoff: park this sequence's newly completed
        FULL pages with the transfer table mid-prefill. The first parked
        chunk also emits kv_transfer_params (no finish_reason) so the
        router dispatches the decode leg — which starts pulling — while
        later chunks are still computing (docs/disaggregation.md)."""
        if not seq.prefill_only or seq.on_prefill_chunk is None:
            return
        ready = seq.prefill_pos // self.page_size
        if ready <= seq.streamed_pages:
            return
        new_pages = [int(p)
                     for p in seq.block_table[seq.streamed_pages:ready]]
        params = seq.on_prefill_chunk(seq, new_pages)
        seq.streamed_pages = ready
        self.stats.disagg_streamed_pages += len(new_pages)
        if params is not None and not seq.stream_started:
            seq.stream_started = True
            # The transfer owns the pages from here: reap must not
            # release them even if the sequence dies mid-stream (the
            # abort hook fails the transfer, which releases exactly once).
            seq.keep_pages = True
            seq.emit(EngineOutput(token_ids=[], kv_transfer_params=params))

    def _finalize_prefill(self, seq: _Seq, tok_dev) -> int:
        """Materialize a deferred final-chunk token and hand the sequence
        to decode. Returns 1 if a token was delivered (progress)."""
        if seq.cancelled or seq.finished:
            return 0
        # anchored=False: the chunk behind this token was SUBMITTED last
        # step — this step's prefill submit stamp (if any) belongs to a
        # different sequence's chunk, so only the blocked wait counts.
        with self.steptrace.drain("prefill", anchored=False):
            token = int(np.asarray(tok_dev).reshape(-1)[0])  # dynajit: disable=DJ201 -- deferred one iteration by design: the device work queued ahead of this readback last step
        if seq.prefill_submit_ts is not None:
            # First chunk dispatched -> first token materialized: the
            # device-stream completion window of the prompt pass.
            seq.device_prefill_ms = max(
                0.0, (time.monotonic() - seq.prefill_submit_ts) * 1e3)
        self._append_token(seq, token, prompt_tokens=seq.prompt_len)
        return 1

    def _defer_first_token(self, seq: _Seq) -> None:
        """Processor sequences discard the device-sampled prefill token;
        the first decode step (input = last prompt token at position
        prompt_len-1, an idempotent KV rewrite) regenerates its logits
        and the host path picks the token."""
        seq.first_deferred = True
        seq.last_token = int(seq.request.token_ids[-1])

    def _chunk_media_embeds(self, seq: _Seq,
                            chunk_tokens: np.ndarray) -> np.ndarray:
        """[chunk, H] splice rows for this prefill chunk: placeholder
        positions get consecutive encoder rows (consumption order = token
        order, robust to chunk boundaries and prefix-cache skips)."""
        img_id = self.runner.model_config.image_token_id
        prompt = np.asarray(seq.request.token_ids[: seq.prefill_pos],
                            np.int32)
        consumed = int(np.count_nonzero(prompt == img_id))
        out = np.zeros((len(chunk_tokens), seq.media_embeds.shape[1]),
                       np.float32)
        positions = np.nonzero(chunk_tokens == img_id)[0]
        n = len(positions)
        avail = seq.media_embeds[consumed: consumed + n]
        out[positions[: len(avail)]] = avail
        return out

    def _finish_prefill_only(self, seq: _Seq, first_token: int) -> None:
        """Disagg prefill side: park the prompt pages with the transfer
        table (via on_prefill_done) and answer with kv_transfer_params
        instead of decoding (ref §3.4: prefill returns
        disaggregated_params; decode pulls the blocks)."""
        n_prompt_pages = -(-seq.prompt_len // self.page_size)
        page_ids = [int(p) for p in seq.block_table[:n_prompt_pages]]
        params: dict = {}
        if seq.on_prefill_done is not None:
            params = seq.on_prefill_done(seq, first_token, page_ids)
            seq.keep_pages = True
            seq.stream_done = True  # clean finish: no abort hook at reap
        seq.finished = True
        if seq.record_id is not None:
            get_recorder().stamp(seq.record_id, "first_token")
            if seq.device_prefill_ms:
                get_recorder().device(seq.record_id, "prefill",
                                      seq.device_prefill_ms)
        seq.emit(EngineOutput(
            token_ids=[], finish_reason="stop",
            prompt_tokens=seq.prompt_len,
            kv_transfer_params={**params, "first_token": first_token},
        ))

    def release_transfer_pages(self, seq: _Seq) -> None:
        """Deferred release for a prefill-only sequence once its transfer
        completes/expires. Thread-safe (routed through the control queue).

        A STREAMING transfer can be released while the prompt pass is
        still running (the puller died / timed out mid-stream): the
        pages must NOT return to the pool yet — the remaining chunks are
        still writing into them, and a new request allocating them would
        be corrupted. Cancel the sequence instead and hand ownership
        back to the normal reap release, which runs only after the
        sequence has stopped stepping."""
        def _do() -> None:
            if not (seq.finished or seq.cancelled):
                # Reap releases once the sequence stops stepping; its
                # abort hook also cleans up the (already-claimed, so
                # never double-released) streaming transfer registry.
                # Emit a terminal frame: the prefill leg's stream is
                # still being consumed (router background drain) and a
                # silent drop would hang it until its deadline.
                seq.cancelled = True
                seq.keep_pages = False
                seq.emit(EngineOutput(
                    finish_reason="cancelled",
                    error="kv transfer abandoned; prefill cancelled"))
                return
            computed = seq.prefill_pos // self.page_size
            self.pool.release(seq.alloc, seq.block_hashes,
                              computed_blocks=computed)

        self._control.put(_do)
        self._wake.set()

    def _dispatch_decode(self):
        """Decode phase 1: fill the batch buffers and ISSUE the fused
        block(s) with no readback — the returned handle is drained by
        _drain_decode after prefill/admission have overlapped the device
        time. The host-sampling paths (logprobs / logits processors)
        need the readback before they can produce a token, so they run
        synchronously here and return a ("count", n) handle."""
        ready = [s for s in self._slots
                 if s is not None and s.decode_ready and not s.finished
                 and not s.cancelled
                 and (len(s.generated) > 0 or s.first_deferred)]
        # Sequences whose first token just came from prefill already have
        # generated[0]; they join decode from the next step. (Processor
        # sequences instead join with first_deferred set — their first
        # token is produced through the host path.)
        if not ready:
            return None
        with _section("sched.decode_prep"):
            self._active[:] = False
            # Neutralize params of inactive slots: sample()'s runtime
            # gate skips the full-vocab truncation sort only when NO slot
            # truncates, and a finished top_k/top_p request must not keep
            # forcing the expensive branch from a stale slot.
            self._temp[:] = 0.0
            self._top_p[:] = 1.0
            self._top_k[:] = 0
            for seq in ready:
                i = seq.slot
                self._tokens[i] = seq.last_token
                self._positions[i] = seq.kv_len - 1  # position of last_token
                self._tables[i] = seq.block_table
                if seq.window is not None:
                    self._advance_decode_window(seq)
                self._kv_lens[i] = seq.kv_len
                self._active[i] = True
                s = seq.request.sampling
                self._temp[i] = s.temperature
                self._top_p[i] = s.top_p
                self._top_k[i] = s.top_k
                self._seeds[i] = seq.seed
                self._steps[i] = len(seq.generated)
                self._lora_idx[i] = seq.lora_idx
        want_logprobs = any(s.request.sampling.logprobs for s in ready)
        want_logits = any(s.processors for s in ready)
        spec = self._maybe_dispatch_spec(ready, want_logprobs, want_logits)
        if spec is not None:
            return spec
        prefill_pending = any(
            s is not None and not s.decode_ready and not s.cancelled
            for s in self._slots)
        block, depth = self._decode_block_for(
            ready, want_logprobs or want_logits, prefill_pending)
        # Bucket the block-table width to the LIVE context: the decode
        # attention gather reads the full table extent, so a conversation
        # 300 tokens deep must not pay for max_pages_per_seq (e.g. 128
        # pages = 2048 tokens) of gather bandwidth every step. jit
        # specializes per width; power-of-two buckets keep variants finite.
        max_kv = max(s.kv_len for s in ready) + block * depth
        need = -(-max_kv // self.page_size)
        width = bucket_table_width(need,
                                   self.runner.config.max_pages_per_seq)
        tables = (self._tables[:, :width], *self._win_tables)
        if block > 1:
            if prefill_pending:
                self.stats.fused_steps_with_prefill += 1
            # Pipelined dispatch: issue block d+1 feeding on block d's
            # DEVICE tokens before reading block d back, so the host
            # readback (expensive on remote-attached chips) overlaps the
            # next block's compute. A sequence finishing inside block d
            # wastes its block-d+1 tokens — the same speculation the
            # in-block discard at drain already accepts.
            device_blocks = []
            toks_dev = None
            # Dispatch-submit stamp + profiler step annotation: the
            # submit wall here is host dispatch cost; the device window
            # runs from this scope's end to the drain in _drain_decode.
            with self.steptrace.dispatch("decode", self.stats.steps):
                for d in range(depth):
                    toks_dev = self.runner.decode_multi(
                        self._tokens if d == 0 else toks_dev[-1],
                        self._positions + d * block, tables,
                        self._kv_lens + d * block,
                        self._active, self._temp, self._top_p,
                        self._top_k,
                        self._seeds, self._steps + d * block, k=block,
                        lora_idx=self._lora_idx, return_device=True,
                    )
                    device_blocks.append(toks_dev)
            self._stamp_builds(ready)
            self.stats.decode_block_launches += depth
            return ("blocks", device_blocks, ready, block)
        return ("count",
                self._decode_single(ready, tables, want_logprobs,
                                    want_logits))

    def _advance_decode_window(self, seq: _Seq) -> None:
        """Before a decode launch: the window group's blocks the launch
        can no longer see go back to the pool, those it may write
        (`_win_lookahead` positions on) are allocated from the row's
        reservation, and its row of the group's table is written from
        its first held block."""
        pos = seq.kv_len - 1  # the position the launch's first step reads
        lease = seq.window
        if not self.win_pool.advance(
                lease, max(0, pos - self.win_pool.window + 1),
                pos + self._win_lookahead - 1, "decode"):
            raise RuntimeError(
                f"window group: a decoding row found no page inside its "
                f"reservation ({len(lease.pages)} held, {lease.reserved} "
                "reserved)")
        win_tables, win_base = self._win_tables
        row = win_tables[seq.slot]
        row[:] = 0
        row[:len(lease.pages)] = lease.pages
        win_base[seq.slot] = lease.first * self.page_size

    def _drain_decode(self, pending) -> int:
        """Decode phase 2: read the fused block(s) back and append tokens.
        Sequences that stopped (EOS/length/cancel) inside a block have
        their surplus speculated tokens discarded; the KV those tokens
        wrote lives in the sequence's own slack pages (_page_span) and is
        released with them."""
        if pending is None:
            return 0
        if pending[0] == "count":
            return pending[1]
        if pending[0] == "spec":
            return self._drain_spec(pending)
        _kind, device_blocks, ready, block = pending
        # Materialize EVERY block before emitting any token: a sequence
        # finishing in block d would otherwise deliver its finish_reason
        # while block d+1's readback still separates it from
        # _reap_finished's page release — consumers reacting to the
        # finish (KVBM flush, disagg transfer) would race a release that
        # hasn't happened yet.
        with self.steptrace.drain("decode") as drain:
            blocks_np = [np.asarray(t) for t in device_blocks]  # dynalint: disable=DL201 -- deliberate barrier: all blocks must land before any token emits (see comment above) # dynajit: disable=DJ201 -- the loop's ONE blocking drain
        # Wall attribution: every live slot waited this device window
        # out (the block served them all in one dispatch).
        for seq in ready:
            seq.device_decode_ms += drain.device_ms
        count = 0
        with self.steptrace.emit():
            for toks_k in blocks_np:
                for step in range(block):
                    for seq in ready:
                        if seq.finished or seq.cancelled:
                            continue  # EOS/stop inside: discard the rest
                        self._append_token(seq,
                                           int(toks_k[step][seq.slot]))
                        count += 1
            # One frame a sequence, one hand-over for the lot.
            self._end_emit()
        return count

    # -- speculative decoding (engine/spec.py; docs/speculative-decoding.md)

    def _maybe_dispatch_spec(self, ready: list, want_logprobs: bool,
                             want_logits: bool):
        """Try a speculative verification step instead of the fused /
        per-token decode. Returns a ("spec", ...) handle (drained by
        `_drain_decode`) or None to fall through.

        Policy: speculation trades FLOPs for latency — it wins when the
        MXU has headroom (small batch) and the text is predictable
        (acceptance EMA). Gated off batch-wide for logprobs requests
        (per-token logprob data needs per-step readbacks), per-iteration
        above the batch-pressure cutoff, and per-slot by the acceptance
        EMA with periodic probing. Logits-processor slots ride along via
        the raw-rows readback and are verified on host with their
        processors applied per position (`_commit_spec_host`), so the
        verification path applies them identically to the single-token
        path."""
        if not self.spec_enabled:
            return None
        # Every fall-through below means "no speculation this iteration":
        # zero the per-step k gauge up front so dynamo_spec_k never
        # reports a stale value through a non-speculating phase; the
        # drain of a dispatched step writes the real mined k.
        self.stats.spec_last_k = 0
        if want_logprobs:
            return None
        if any(s.first_deferred for s in ready):
            # First-token-deferred processor sequences re-derive their
            # first token through _decode_single; they speculate from
            # the next iteration.
            return None
        if self.spec_cutoff and len(ready) > self.spec_cutoff:
            return None
        need = self.spec_k + 1
        if not all(s.slack_ok
                   or (s.request.sampling.max_tokens - len(s.generated)
                       >= need)
                   for s in ready):
            return None
        drafts = np.zeros((self.max_batch, self.spec_k), np.int32)
        mined = 0
        expected = 0.0  # Σ ema·draft_len — expected accepted this step
        for seq in ready:
            sp = seq.spec
            if sp is None:
                continue
            sp.pending = 0
            remaining = (seq.request.sampling.max_tokens
                         - len(seq.generated))
            if (self.spec_min_ema > 0 and sp.ema < self.spec_min_ema
                    and not sp.wants_probe()):
                continue
            prop = propose_for(sp, self.spec_lookahead, self.spec_k,
                               remaining)
            if prop:
                sp.pending = len(prop)
                drafts[seq.slot, :len(prop)] = prop
                mined += len(prop)
                expected += sp.ema * len(prop)
        # A spec step is ONE dispatch emitting 1 + accepted tokens per
        # slot; the fused block it displaces is one dispatch emitting
        # `block` tokens per slot. Against the fused path the gain must
        # clear the dispatch amortization it forfeits for NON-proposing
        # slots, so require the expected accepted total to cover half a
        # token per ready slot (vs per-token alternatives — processor
        # batches, block=1 — any expected acceptance already wins).
        per_token_alt = self.decode_block <= 1 or want_logits
        threshold = 0.0 if per_token_alt else 0.5 * len(ready)
        if mined == 0 or expected < threshold:
            return None
        max_kv = max(s.kv_len for s in ready) + need
        width = bucket_table_width(-(-max_kv // self.page_size),
                                   self.runner.config.max_pages_per_seq)
        with self.steptrace.dispatch("spec", self.stats.steps):
            targets, n_acc = self.runner.decode_spec(
                self._tokens, drafts, self._positions,
                self._tables[:, :width],
                self._kv_lens, self._active, self._temp, self._top_p,
                self._top_k, self._seeds, self._steps,
                lora_idx=self._lora_idx, want_logits=want_logits,
                return_device=True,
            )
        self._stamp_builds(ready)
        return ("spec", targets, n_acc, ready, drafts, want_logits)

    def _drain_spec(self, pending) -> int:
        """Materialize a speculative step and commit per-slot token
        prefixes. Committed tokens are the per-position TARGET samples —
        bit-identical to sequential decode — so stop conditions, stream
        emission, and page release all flow through `_append_token`
        unchanged; surplus rejected-draft KV sits in the sequence's own
        slack pages and is rewritten by the next step."""
        _kind, targets_dev, n_acc_dev, ready, drafts, with_logits = pending
        with self.steptrace.drain("spec") as drain:
            targets = np.asarray(targets_dev)  # dynalint: disable=DL201 -- the drain point: spec commits need the verdict on host # dynajit: disable=DJ201 -- same spec drain
            n_acc = np.asarray(n_acc_dev)  # dynalint: disable=DL201 -- same drain point # dynajit: disable=DJ201 -- same spec drain
            logits = None
            if with_logits:
                logits = self.runner.last_spec_logits
                if logits is not None and not isinstance(logits,
                                                         np.ndarray):
                    logits = np.asarray(logits)  # dynalint: disable=DL201 -- same drain point # dynajit: disable=DJ201 -- same spec drain
        for seq in ready:
            seq.device_decode_ms += drain.device_ms
        count = 0
        emas = []
        self.stats.spec_steps += 1
        # Per-step k = the longest draft actually mined this step (the
        # static spec_k shape may be mostly padding).
        self.stats.spec_last_k = max(
            (s.spec.pending for s in ready if s.spec is not None),
            default=0)
        with self.steptrace.emit():
            for seq in ready:
                i = seq.slot
                if seq.finished or seq.cancelled:
                    continue
                if seq.processors:
                    count += self._commit_spec_host(seq, drafts[i],
                                                    logits[i])
                else:
                    n = int(n_acc[i])
                    toks = [int(t) for t in targets[i, : n + 1]]
                    count += self._commit_spec(seq, toks)
                if seq.spec is not None and seq.spec.pending:
                    emas.append(seq.spec.ema)
            # A sequence's verified tokens are one frame.
            self._end_emit()
        if emas:
            self.stats.spec_ema = float(np.mean(emas))
        return count

    def _commit_spec(self, seq: _Seq, tokens: list) -> int:
        """Commit verified tokens through the normal append path; update
        the slot's acceptance accounting against its MINED draft length
        (accidental matches on static-shape padding are committed — they
        are correct target samples — but never counted as acceptance)."""
        sp = seq.spec
        emitted = 0
        for tok in tokens:
            if seq.finished or seq.cancelled:
                break
            self._append_token(seq, int(tok))
            emitted += 1
        if sp is not None and sp.pending:
            accepted = min(max(emitted - 1, 0), sp.pending)
            sp.observe(sp.pending, accepted)
            self.stats.spec_proposed += sp.pending
            self.stats.spec_accepted += accepted
        return emitted

    def _commit_spec_host(self, seq: _Seq, draft_row: np.ndarray,
                          logits_rows: np.ndarray) -> int:
        """Host verification leg for logits-processor sequences: apply
        the slot's processors to each raw row exactly as the single-token
        path does (same input_ids prefix, same host_sample (seed, step)
        key), accept the draft only when it equals the processed sample.
        One processor call per committed token — identical call counts
        and mutation order to sequential decode, so stateful processors
        (guided-decoding DFAs, forced responses) stay in sync."""
        sp = seq.spec
        input_ids = list(seq.generated)
        k = len(draft_row)
        emitted = 0
        accepted = 0
        for i in range(k + 1):
            try:
                token = self._host_process_sample(seq, logits_rows[i],
                                                  input_ids)
            except Exception as exc:  # noqa: BLE001 — same contract as
                # the sequential host path in _decode_single
                self._fail_processor_seq(seq, exc)
                break
            self._append_token(seq, token)
            emitted += 1
            if seq.finished or seq.cancelled:
                break
            if not seq.processors:
                # Processors retired mid-chunk (min_tokens satisfied):
                # sequential decode would continue on the DEVICE sampler,
                # whose draws differ from host_sample — stop here so the
                # next iteration takes the device path like sequential.
                break
            input_ids.append(token)
            if i < k and int(draft_row[i]) == token:
                accepted += 1
                continue
            break
        if sp is not None and sp.pending:
            sp.observe(sp.pending, min(accepted, sp.pending))
            self.stats.spec_proposed += sp.pending
            self.stats.spec_accepted += min(accepted, sp.pending)
        return emitted

    def _decode_single(self, ready, tables, want_logprobs,
                       want_logits) -> int:
        # Host-sampling path: dispatch, execute, and readback happen
        # inside the one runner call — the whole duration is the
        # device window (the host was blocked on the chip throughout).
        with self.steptrace.sync("decode", self.stats.steps) as sc:
            next_tokens = self.runner.decode(
                self._tokens, self._positions, tables, self._kv_lens,
                self._active, self._temp, self._top_p, self._top_k,
                self._seeds,
                self._steps, lora_idx=self._lora_idx,
                want_logprobs=want_logprobs and not want_logits,
                want_logits=want_logits,
            )
        self._stamp_builds(ready)
        for seq in ready:
            seq.device_decode_ms += sc.device_ms
        lp_b, tid_b, tlp_b = getattr(self.runner, "last_decode_sample",
                                     (None, None, None))
        logits_rows = (getattr(self.runner, "last_decode_logits", None)
                       if want_logits else None)
        count = 0
        for seq in ready:
            i = seq.slot
            info = ((lp_b[i], tid_b[i], tlp_b[i])
                    if lp_b is not None else None)
            token = int(next_tokens[i])
            if logits_rows is not None:
                try:
                    token, info = self._host_sample_slot(
                        seq, logits_rows[i], token)
                except Exception as exc:  # noqa: BLE001 — same contract
                    # as the speculative host leg (_fail_processor_seq)
                    self._fail_processor_seq(seq, exc)
                    continue
            first = seq.first_deferred and not seq.generated
            seq.first_deferred = False
            self._append_token(
                seq, token, sample_info=info,
                prompt_tokens=seq.prompt_len if first else None)
            count += 1
        # Frames of one: the step's prefill is still to be dispatched.
        self._end_emit()
        return count

    def _host_process_sample(self, seq: _Seq, raw_row: np.ndarray,
                             input_ids: list) -> int:
        """The host sampling leg shared by the sequential processor path
        (_host_sample_slot) and the speculative verification leg
        (_commit_spec_host): apply the sequence's processors to a copy of
        the raw logits row, then host_sample keyed by (seed,
        len(input_ids)) — ONE definition so the two paths can never
        desynchronize on processor order or sampling keys."""
        from ..llm.logits_processing import host_sample

        s = seq.request.sampling
        row = raw_row.astype(np.float32).copy()
        for proc in seq.processors:
            proc(input_ids, row)
        return host_sample(row, s.temperature, s.top_p, s.top_k,
                           seq.seed, len(input_ids))

    def _fail_processor_seq(self, seq: _Seq, exc: Exception) -> None:
        """A misbehaving user processor (bad token id, all-banned vocab)
        must error ITS request, not kill the scheduler thread and hang
        the whole engine."""
        log.warning("logits processor failed for %s: %r",
                    seq.request.request_id, exc)
        seq.finished = True
        if seq.frame is not None:
            # tokens a speculative step verified before the failure
            self._close_frame(seq)
        seq.emit(EngineOutput(
            finish_reason="error",
            error=f"logits processor failed: {exc}"))

    def _host_sample_slot(self, seq: _Seq, raw_row: np.ndarray,
                          device_token: int):
        """Host leg of the logits-processor path: apply the sequence's
        processors to its raw logits row and re-sample; sequences without
        processors keep the device-sampled token. Logprob data (when the
        request asks) is computed from the RAW distribution (OpenAI
        semantics — logprobs reflect the model, not the processors)."""
        s = seq.request.sampling
        token = device_token
        if seq.processors:
            token = self._host_process_sample(seq, raw_row,
                                              list(seq.generated))
        info = None
        if s.logprobs:
            from .sampler import TOP_LOGPROBS_K

            logp = raw_row.astype(np.float64)
            logp -= logp.max()
            logp -= np.log(np.exp(logp).sum())
            k = min(TOP_LOGPROBS_K, len(logp))
            top_ids = np.argpartition(logp, -k)[-k:]
            top_ids = top_ids[np.argsort(logp[top_ids])[::-1]]
            info = (float(logp[token]), top_ids.astype(np.int32),
                    logp[top_ids].astype(np.float32))
        return token, info

    def _decode_block_for(self, ready: list, want_host: bool,
                          prefill_pending: bool) -> tuple[int, int]:
        """(block, pipeline depth) for this iteration. Per-token (1, 1)
        only when fusing CANNOT work: a sequence wants logprobs or
        host-side logits processing — those need a readback per step to
        produce the next token.

        Prefill work pending no longer forces per-token (the round-4
        all-or-nothing bail): the chunk interleaves BETWEEN fused blocks
        — TTFT impact is bounded by one block of decode — and the chunk's
        own dispatch provides the readback overlap, so depth stays 1.
        Pure-decode phases chain `decode_pipeline` blocks on
        device-resident tokens. There is no token-budget bail either:
        _page_span allocates block*depth of speculative slack per
        sequence, so a sequence stopping mid-block overruns into its OWN
        pages and the surplus tokens are discarded at drain. A single
        fused k keeps the compiled-variant count at one (jit caches per
        k; varying k mid-serving would compile fresh scan programs).
        """
        if self.decode_block <= 1 or want_host:
            return 1, 1
        # Mirrored (multihost) runners: depth stays 1 — chained blocks
        # feed device-resident tokens, which cannot ride the step channel
        # to follower ranks (parallel/multihost.py MirroredRunner).
        depth = (1 if (prefill_pending or self._waiting
                       or getattr(self.runner, "is_mirrored", False))
                 else max(1, self.decode_pipeline))
        while depth >= 1:
            need = self.decode_block * depth
            if all(s.slack_ok
                   or (s.request.sampling.max_tokens - len(s.generated)
                       >= need)
                   for s in ready):
                return self.decode_block, depth
            depth -= 1
        return 1, 1

    def _append_token(self, seq: _Seq, token: int,
                      prompt_tokens: Optional[int] = None,
                      sample_info: Optional[tuple] = None) -> None:
        seq.generated.append(token)
        if len(seq.generated) == 1 and seq.record_id is not None:
            get_recorder().stamp(seq.record_id, "first_token")
            if seq.device_prefill_ms:
                # Device share of the TTFT the timeline just closed:
                # feeds /debug/requests, the planner's phase breakdown,
                # and dynamo_ttft_device_ms (worker-side).
                get_recorder().device(seq.record_id, "prefill",
                                      seq.device_prefill_ms)
        seq.last_token = token
        if seq.spec is not None:
            # Keep the n-gram index + block-hash chain current on EVERY
            # commit path (speculative, fused, per-token, prefill first
            # token) — sequences alternate between them freely.
            seq.spec.extend([token])
        request = seq.request
        finish = None
        if not request.stop.ignore_eos and token in request.eos_token_ids:
            finish = "stop"
        elif token in request.stop.stop_token_ids:
            finish = "stop"
        elif len(seq.generated) >= request.sampling.max_tokens:
            finish = "length"
        logprobs = None
        top_logprobs = None
        if request.sampling.logprobs and sample_info is not None:
            lp, top_ids, top_lps = sample_info
            logprobs = [float(lp)]
            n = min(int(request.sampling.top_logprobs or 0), len(top_ids))
            if n > 0:
                top_logprobs = [[[int(i), float(v)]
                                 for i, v in zip(top_ids[:n], top_lps[:n])]]
        if finish is not None and seq.device_decode_ms \
                and seq.record_id is not None:
            # Flush decode device burn BEFORE the finish frame goes
            # out: the worker closes the timeline as soon as it
            # consumes that frame, and a reap-time flush would race
            # it. Zeroed so reap cannot double-count.
            get_recorder().device(seq.record_id, "decode",
                                  seq.device_decode_ms)
            seq.device_decode_ms = 0.0
        frame = seq.frame
        if frame is not None and ((frame.logprobs is None)
                                  != (logprobs is None)):
            # logprob entries stay one a token inside a frame
            self._close_frame(seq)
            frame = None
        if frame is None:
            seq.frame = EngineOutput(
                token_ids=[token], prompt_tokens=prompt_tokens,
                logprobs=logprobs, top_logprobs=top_logprobs)
            self._open_frames.append(seq)
        else:
            frame.token_ids.append(token)
            if logprobs is not None:
                frame.logprobs.extend(logprobs)
                if top_logprobs is not None:  # asked for or not: a
                    # request's, so the frame's first token had them too
                    frame.top_logprobs.extend(top_logprobs)
        if finish is not None:
            self._close_frame(seq, finish)
            seq.finished = True
        elif seq.processors:
            self._maybe_retire_processors(seq)

    def _close_frame(self, seq: _Seq, finish: Optional[str] = None) -> None:
        """Emit the sequence's open frame: what one emitting section
        gave it, 1 to block x depth tokens, with the finish if its last
        token carried one."""
        frame, seq.frame = seq.frame, None
        frame.finish_reason = finish
        self.stats.emit_frames += 1
        seq.emit(frame)

    def _close_frames(self) -> None:
        """Emit every frame the section opened; a sequence cancelled
        meanwhile has no reader."""
        for seq in self._open_frames:
            if seq.frame is None:
                continue
            if seq.cancelled:
                seq.frame = None
            else:
                self._close_frame(seq)
        self._open_frames.clear()

    def _end_emit(self) -> None:
        """Where the loop stops emitting: close the open frames and tell
        the owner, so nothing emitted waits behind a blocking drain."""
        self._close_frames()
        if self.on_emit_end is not None:
            self.on_emit_end()

    def _maybe_retire_processors(self, seq: _Seq) -> None:
        """min_tokens is the only processor that EXPIRES: once the budget
        is met it is a no-op for the rest of the stream, so a sequence
        whose processors are all exhausted MinTokens drops them and
        rejoins the fused device-sampled decode path instead of paying a
        per-step logits readback for its whole life."""
        from ..llm.logits_processing import MinTokensProcessor

        if all(isinstance(p, MinTokensProcessor)
               and len(seq.generated) >= p.min_tokens
               for p in seq.processors):
            seq.processors = None

    def abort_all(self, reason: str) -> int:
        """Finish every waiting + in-flight sequence with finish_reason
        'migrate' so the frontend Migration operator re-prefills them on a
        (re)available worker with generated tokens preserved. Must run on
        the scheduler thread (e.g. inside a run_in_step callback) — used by
        elastic reshard, where the KV pool is about to be reinitialized."""
        n = 0
        for seq in self._waiting:
            if not seq.cancelled:
                seq.emit(EngineOutput(finish_reason="migrate", error=reason))
                seq.cancelled = True
                n += 1
        self._waiting.clear()
        for seq in self._parked:
            # Parked sequences migrate too: their park bundles reference
            # a KV pool that is about to be reinitialized.
            self._drop_parked(seq.request.request_id)
            if not seq.cancelled:
                seq.emit(EngineOutput(finish_reason="migrate", error=reason))
                seq.cancelled = True
                n += 1
        self._parked.clear()
        for seq in self._slots:
            if seq is not None and not seq.finished and not seq.cancelled:
                seq.emit(EngineOutput(finish_reason="migrate", error=reason))
                seq.finished = True
                n += 1
        self._end_emit()  # before the pages go
        self._reap_finished()
        return n

    # -- graceful drain (engine/drain.py; docs/fault-tolerance.md) ---------

    def drain_sweep(self, register_handoff=None) -> dict:
        """Vacate live sequences for a graceful departure. Scheduler
        thread only (run via run_in_step) — no decode block is in
        flight between steps, so pages can change ownership safely.

        Ladder rung 1 — KV handoff: an eligible decode sequence parks
        its computed pages with the worker's transfer table
        (`register_handoff(seq, page_ids, computed_tokens) -> params`)
        and emits a migrate frame carrying kv_transfer_params + resume
        state; the Migration operator re-dispatches it to a peer that
        PULLS the KV and resumes bit-identically instead of
        re-prefilling. Eligible = decode-ready with committed tokens and
        no host-sampler state (logits processors hold live Python state
        a handoff cannot carry — those take rung 2).

        Rung 2 — cooperative replay: everything else live (mid-prefill,
        processor slots, waiting, parked) emits a plain migrate; the
        peer replays prompt+generated (a re-prefill, tokens preserved).

        Prefill-only sequences that already handed pages to a transfer
        keep running — their decode peer is mid-pull; the drain
        deadline bounds them. Returns {"handoff": [...], "replay":
        [...], "pending": [...]} request-id lists."""
        self.draining = True
        report: dict = {"handoff": [], "replay": [], "pending": []}

        def _replay(seq: _Seq) -> None:
            self.stats.drain_replayed += 1
            report["replay"].append(seq.request.request_id)
            get_recorder().event(seq.record_id, "drain",
                                 rung="replay",
                                 tokens_preserved=len(seq.generated))
            seq.emit(EngineOutput(finish_reason="migrate",
                                  error="worker draining"))

        for seq in self._waiting:
            if not seq.cancelled:
                _replay(seq)
                seq.cancelled = True
        self._waiting.clear()
        for seq in self._parked:
            # Parked bundles reference a pool that is departing: replay.
            self._drop_parked(seq.request.request_id)
            if not seq.cancelled:
                _replay(seq)
                seq.cancelled = True
        self._parked.clear()
        for seq in self._slots:
            if seq is None or seq.finished or seq.cancelled:
                continue
            rid = seq.request.request_id
            if seq.prefill_only or seq.keep_pages:
                report["pending"].append(rid)
                continue
            params = None
            if (register_handoff is not None and seq.decode_ready
                    and seq.generated and not seq.processors
                    and not seq.first_deferred
                    and not self.cache_plan.move_pages):
                # KV present on device: positions 0..kv_len-2 (the same
                # computed-page math as preempt-to-KVBM).
                computed = seq.kv_len - 1
                n_pages = -(-computed // self.page_size)
                page_ids = [int(p) for p in seq.block_table[:n_pages]]
                try:
                    params = register_handoff(seq, page_ids, computed)
                except Exception:  # noqa: BLE001 — a failed handoff
                    # registration degrades to the replay rung
                    log.exception("handoff registration failed for %s",
                                  rid)
                    params = None
            seq.finished = True
            if params is not None:
                # The transfer owns the pages now; reap must not release
                # them (the claim/expiry path releases exactly once).
                seq.keep_pages = True
                self.stats.drain_handoff += 1
                report["handoff"].append(rid)
                get_recorder().event(seq.record_id, "drain",
                                     rung="handoff",
                                     tokens_preserved=len(seq.generated))
                seq.emit(EngineOutput(
                    finish_reason="migrate",
                    error="worker draining (kv handoff)",
                    kv_transfer_params=params))
            else:
                _replay(seq)
        self._end_emit()  # before the pages go
        self._reap_finished()
        return report

    def drain_expire(self, reason: str) -> int:
        """Deadline rung: finish every still-live sequence with an
        honest in-band error (scheduler thread). The ladder's last rung
        — better a truthful failure the client can retry than a stream
        that dies with the process."""
        n = self._finish_all(reason)
        self.stats.drain_errored += n
        return n

    def _finish_all(self, reason: str) -> int:
        """Finish every waiting, parked and in-flight sequence with an
        in-band error (scheduler thread)."""
        n = 0
        for seq in self._waiting:
            if not seq.cancelled:
                seq.emit(EngineOutput(finish_reason="error", error=reason))
                seq.cancelled = True
                n += 1
        self._waiting.clear()
        for seq in self._parked:
            self._drop_parked(seq.request.request_id)
            if not seq.cancelled:
                seq.emit(EngineOutput(finish_reason="error", error=reason))
                seq.cancelled = True
                n += 1
        self._parked.clear()
        for seq in self._slots:
            if seq is not None and not seq.finished and not seq.cancelled:
                seq.emit(EngineOutput(finish_reason="error", error=reason))
                seq.finished = True
                n += 1
        self._end_emit()  # before the pages go
        self._reap_finished()
        return n

    def _reap_finished(self) -> None:
        for i, seq in enumerate(self._slots):
            if seq is None:
                continue
            if seq.finished or seq.cancelled:
                if seq.device_decode_ms and seq.record_id is not None:
                    # Decode-phase device burn, flushed once at reap
                    # (per-step recorder traffic would tax the loop).
                    get_recorder().device(seq.record_id, "decode",
                                          seq.device_decode_ms)
                if (seq.stream_started and not seq.stream_done
                        and seq.on_prefill_chunk is not None):
                    # A prefill-only sequence died mid-stream (cancel or
                    # error before on_prefill_done): fail the streaming
                    # transfer so a waiting puller stops promptly and the
                    # parked pages release exactly once (worker-side).
                    try:
                        seq.on_prefill_chunk(seq, None)
                    except Exception:  # noqa: BLE001 — reap must proceed
                        log.exception("stream abort hook failed")
                if not seq.keep_pages:
                    # Only blocks whose KV was actually computed may enter
                    # the prefix cache (a cancel mid-prefill leaves later
                    # blocks unwritten).
                    computed = seq.prefill_pos // self.page_size
                    self.pool.release(seq.alloc, seq.block_hashes,
                                      computed_blocks=computed)
                if seq.window is not None:
                    self.win_pool.release(seq.window)
                    seq.window = None
                if seq.spec is not None:
                    if seq.spec.proposed and seq.record_id is not None:
                        # Where this request's speculated tokens were won
                        # or wasted (docs/observability.md `spec` event).
                        get_recorder().event(
                            seq.record_id, "spec",
                            proposed=seq.spec.proposed,
                            accepted=seq.spec.accepted)
                    if not seq.cancelled and self.spec_lookahead is not None:
                        # Teach the cross-request lookahead this
                        # sequence's block-hash -> continuation chain.
                        self.spec_lookahead.record(
                            seq.spec.hasher.block_hashes,
                            seq.spec.proposer.tokens)
                self._slots[i] = None


class _SubmitHandle:
    """Cancellation handle bridging asyncio-side aborts into the thread."""

    def __init__(self) -> None:
        self.seq: Optional[_Seq] = None
        self._cancelled = False

    def cancel(self) -> None:
        self._cancelled = True
        if self.seq is not None:
            self.seq.cancelled = True
