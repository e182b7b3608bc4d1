"""TPU worker: the real JAX engine registered into the distributed runtime.

The analog of `python -m dynamo.vllm` (ref: components/src/dynamo/vllm/
main.py:113 + handlers.py DecodeWorkerHandler) except the engine is ours:
create runtime -> build ModelRunner + InferenceScheduler -> serve `generate`
-> publish ModelDeploymentCard -> publish KV events + load metrics. The
KV-event publisher is embedded (no ZMQ bridge needed — we own the engine;
SURVEY section 2.6 "Engine->Dynamo KV events: in-process").
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import AsyncIterator, Optional

from ..kv_router.protocols import (
    KV_EVENT_TOPIC,
    LOAD_TOPIC,
    KvCacheRemoved,
    KvCacheStored,
    LoadMetrics,
    RouterEvent,
)
from ..llm.kv_transfer import (
    BlockAssembler,
    KvLayoutDescriptor,
    PendingTransfer,
    PendingTransferTable,
    StreamingTransfer,
    encode_block_chunks,
)
from ..llm.model_card import (
    CHAT,
    COMPLETIONS,
    PREFILL,
    ModelDeploymentCard,
    publish_card,
)
from ..llm.protocols import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from ..models import get_config
from ..models.config import cache_plan
from ..parallel import MeshConfig, make_mesh
from ..perf.steptrace import LiveRoofline
from ..runtime import DistributedRuntime, new_instance_id
from ..runtime.logging import get_logger
from ..runtime.metrics import KV_USAGE
from .model_runner import ModelRunner, RunnerConfig, compiling
from .scheduler import InferenceScheduler

log = get_logger("engine.worker")


def observe_stages(timeline, request, prefill_only: bool = False) -> None:
    """A request's closed flight-recorder timeline, observed into
    dynamo_stage_duration_seconds{stage} where it closes: ingress (only
    when the frontend's arrival time rode the annotations), queue,
    prefill_wait, prefill, decode (runtime/flight_recorder.py
    `stage_durations`; docs/observability.md)."""
    from ..runtime.flight_recorder import stage_durations
    from ..runtime.metrics import STAGE_DURATION

    received_at = request.annotations.get("received_at")
    if isinstance(received_at, bool) \
            or not isinstance(received_at, (int, float)):
        received_at = None
    for stage, seconds in stage_durations(
            timeline.phases, received_at, prefill_only).items():
        STAGE_DURATION.labels(stage=stage,
                              model=request.model).observe(seconds)


class Outbox:
    """The scheduler's out-tray. A request's `emit` only posts here;
    `flush` (`InferenceScheduler.on_emit_end`) hands everything one
    emitting section produced, a frame a sequence of a drained block,
    to the event loop in ONE `call_soon_threadsafe`, where each frame
    used to be one: a lock, a wake-up write on the loop's socket and,
    under load, a trip of the GIL to the loop thread and back while the
    device waits for its next dispatch. The scheduler thread posts and
    flushes; the lock is for `submit`, which fails a request on its
    caller's thread once the engine is dead."""

    def __init__(self) -> None:
        self._frames: dict = {}  # loop -> [(queue, frame)]: one loop,
        #                          outside tests
        self._lock = threading.Lock()
        self.handovers = 0  # dynamo_engine_emit_handovers_total

    def post(self, loop, queue: asyncio.Queue,
             output: EngineOutput) -> None:
        with self._lock:
            self._frames.setdefault(loop, []).append((queue, output))

    def flush(self) -> None:
        if not self._frames:
            return
        with self._lock:  # held to the hand-over: two flushers' batches
            # reach the loop in the order they were taken
            batches, self._frames = self._frames, {}
            for loop, frames in batches.items():
                self.handovers += 1
                loop.call_soon_threadsafe(self._deliver, frames)

    @staticmethod
    def _deliver(frames: list) -> None:
        """On the event loop: each frame to its request's queue, in the
        order the scheduler emitted them."""
        for queue, output in frames:
            queue.put_nowait(output)


class KvEventBuffer:
    """Thread-safe KV event buffer: the scheduler thread records stored /
    removed page hashes; an async drain task batches them onto the event
    plane (the reference batches publishes the same way,
    kv_router/publisher)."""

    def __init__(self, worker_id: int, dp_rank: int = 0) -> None:
        self.worker_id = worker_id
        self.dp_rank = dp_rank
        self._lock = threading.Lock()
        self._pending: list[RouterEvent] = []
        self._event_id = 0
        # Queryable record of this worker's blocks — the router's resync/
        # bootstrap source (kv_router/local_indexer.py).
        from ..kv_router.local_indexer import LocalKvIndexer

        self.local_index = LocalKvIndexer(worker_id, dp_rank)

    def on_stored(self, hashes: list[int], parent: Optional[int]) -> None:
        with self._lock:
            self._pending.append(RouterEvent(
                worker_id=self.worker_id, event_id=self._event_id,
                dp_rank=self.dp_rank,
                stored=KvCacheStored(block_hashes=list(hashes),
                                     parent_hash=parent),
            ))
            self.local_index.on_stored(self._event_id, list(hashes), parent)
            self._event_id += 1

    def on_removed(self, hashes: list[int]) -> None:
        with self._lock:
            self._pending.append(RouterEvent(
                worker_id=self.worker_id, event_id=self._event_id,
                dp_rank=self.dp_rank,
                removed=KvCacheRemoved(block_hashes=list(hashes)),
            ))
            self.local_index.on_removed(self._event_id, list(hashes))
            self._event_id += 1

    def on_cleared(self) -> None:
        """Whole-cache invalidation (clear_kv_blocks / elastic reshard)."""
        with self._lock:
            self._pending.append(RouterEvent(
                worker_id=self.worker_id, event_id=self._event_id,
                dp_rank=self.dp_rank, cleared=True,
            ))
            self.local_index.on_cleared(self._event_id)
            self._event_id += 1

    def drain(self) -> list[RouterEvent]:
        with self._lock:
            out, self._pending = self._pending, []
            return out


def recurrent_state_refusals(model_config, *, mode: str = "aggregated",
                             kvbm: bool = False, spec: bool = False,
                             weight_dtype: str = "model",
                             kv_dtype: str = "model",
                             devices: int = 1) -> None:
    """What a model's cache cannot be served with yet, refused at start
    with the flag and the reason, never answered wrongly later. The
    configuration says what its cache cannot do and why
    (`models.config.cache_plan`: recurrent state per slot, a second page
    group, a single-stack latent pool); here each flag that is set asks
    the one trait it needs. A dense stack's plan refuses nothing but an
    int8 pool of latents."""
    plan = cache_plan(model_config)
    for asked, trait, flag in (
            (weight_dtype != "model", "quantized_weights",
             f"--weight-dtype {weight_dtype}"),
            (kv_dtype != "model", "int8_pool", f"--kv-dtype {kv_dtype}"),
            (devices > 1, "shard",
             f"--tp/--sp/--dp over {devices} devices"),
            (mode != "aggregated", "move_pages",
             f"--mode {mode}: disaggregated prefill/decode hands over KV "
             "pages by block index (engine/ici_transfer.py, "
             "llm/kv_transfer.py)"),
            (kvbm, "move_pages",
             "--kvbm-host-blocks/--kvbm-disk-blocks: KVBM offloads and "
             "onboards KV pages by prefix hash"),
            (spec, "score_positions",
             "DYNT_SPEC_ENABLE: speculative verification (engine/spec.py) "
             "scores k+1 positions in one step and rolls rejected ones "
             "back by length")):
        why = getattr(plan, trait)
        if asked and why:
            raise ValueError(f"{flag}: {why}")


def _runner_config(args) -> RunnerConfig:
    """The worker CLI's flags as a RunnerConfig."""
    extra = {}
    if args.prefill_buckets:
        extra["prefill_buckets"] = tuple(
            sorted(int(b) for b in args.prefill_buckets.split(",")))
    return RunnerConfig(
        page_size=args.page_size, num_pages=args.num_pages,
        max_batch=args.max_batch,
        max_pages_per_seq=args.max_pages_per_seq,
        max_loras=args.max_loras, lora_rank=args.lora_rank,
        kv_dtype=args.kv_dtype, weight_dtype=args.weight_dtype,
        window_pages=args.window_pages, **extra)


class TpuWorker:
    def __init__(
        self,
        runtime: DistributedRuntime,
        model_name: str = "tiny-test",
        served_name: Optional[str] = None,
        namespace: str = "dynamo",
        component: str = "backend",
        runner_config: Optional[RunnerConfig] = None,
        mesh_config: Optional[MeshConfig] = None,
        attention_fn=None,
        warmup: bool = True,
        mode: str = "aggregated",  # aggregated | prefill | decode
        kvbm_config=None,  # Optional[block_manager.KvbmConfig]
        tool_parser: Optional[str] = None,
        reasoning_parser: Optional[str] = None,
        lora_adapters: Optional[dict[str, str]] = None,  # name -> npz path
        weight_service: Optional[str] = None,  # unix socket (GMS analog)
        weights_from_peer: bool = False,  # ModelExpress analog
        mesh=None,  # pre-built sub-mesh (co-meshed disagg split_mesh)
        ici_bridge=None,  # engine.ici_transfer.IciKvBridge, shared in-proc
        model_path: Optional[str] = None,  # HF checkpoint dir (safetensors)
        step_channel=None,  # parallel.multihost.StepChannel (driver rank)
        model_config=None,  # a preset cut to this chip's share (cut_config)
        prewarm: Optional[str] = None,  # off | buckets | full (--prewarm)
    ) -> None:
        self.runtime = runtime
        self.instance_id = new_instance_id()
        self.model_path = model_path
        if model_path:
            # Real checkpoint: architecture comes from its config.json
            # (ref: fetch_model + ModelDeploymentCard weight plumbing,
            # components/src/dynamo/vllm/main.py:133,
            # lib/llm/src/model_card.rs:183).
            from ..models.checkpoint import config_from_checkpoint

            self.model_config = config_from_checkpoint(model_path)
        else:
            self.model_config = model_config or get_config(model_name)
        self.runner_config = runner_config or RunnerConfig()
        self.mesh = mesh if mesh is not None else make_mesh(
            mesh_config or MeshConfig())
        from ..runtime.config import env as _cfg_env

        recurrent_state_refusals(
            self.model_config, mode=mode,
            kvbm=bool(kvbm_config is not None and kvbm_config.enabled),
            spec=bool(_cfg_env("DYNT_SPEC_ENABLE")),
            weight_dtype=self.runner_config.weight_dtype,
            kv_dtype=self.runner_config.kv_dtype,
            devices=self.mesh.devices.size)
        self.ici_bridge = ici_bridge
        if ici_bridge is not None and mode == "prefill":
            ici_bridge.attach_prefill(self)
        self._warmup = warmup
        self._prewarm = prewarm
        self.mode = mode
        self.transfers = PendingTransferTable()
        # Disagg chunked handoff (docs/disaggregation.md): live streaming
        # transfers keyed by request id, appended per prefill chunk on
        # the scheduler thread. 0 depth disables (serial handoff).
        self.disagg_pipeline = max(0, int(_cfg_env("DYNT_DISAGG_PIPELINE")
                                          or 0))
        self._stream_transfers: dict[str, StreamingTransfer] = {}
        self.events = KvEventBuffer(self.instance_id)
        self.runner: Optional[ModelRunner] = None
        self.scheduler: Optional[InferenceScheduler] = None
        self.kvbm_config = kvbm_config
        self.kvbm = None
        self.loras = None
        if self.runner_config.max_loras > 0:
            from ..llm.lora import LoraManager

            self.loras = LoraManager(self.model_config,
                                     self.runner_config.max_loras,
                                     self.runner_config.lora_rank)
        elif lora_adapters:
            raise ValueError(
                "LoRA adapters were given but max_loras=0 — set "
                "--max-loras to enable adapter slots")
        self._initial_loras = lora_adapters or {}
        model_types = ([PREFILL] if mode == "prefill"
                       else [CHAT, COMPLETIONS])
        import os as _os

        tokenizer_spec = {"kind": "byte"}
        if model_path and _os.path.exists(
                _os.path.join(model_path, "tokenizer.json")):
            tokenizer_spec = {"kind": "hf", "path": model_path}
        self.card = ModelDeploymentCard(
            name=served_name or self.model_config.name,
            model_types=model_types,
            namespace=namespace,
            component=component,
            endpoint="generate",
            context_length=min(self.model_config.max_context,
                               self.runner_config.max_context),
            kv_block_size=self.runner_config.page_size,
            total_kv_blocks=self.runner_config.num_pages,
            tokenizer=tokenizer_spec,
            tool_parser=tool_parser,
            reasoning_parser=reasoning_parser,
        )
        # Routers bootstrap/gap-resync from our local indexer (manager.py
        # gates resync RPCs on this flag).
        self.card.runtime_config["kv_blocks_endpoint"] = True
        if self.model_config.image_token_id >= 0:
            # Frontends expand image parts into these placeholder tokens
            # (preprocessor._preprocess_multimodal).
            self.card.runtime_config["multimodal"] = {
                "image_token_id": self.model_config.image_token_id,
                "n_image_tokens": self.model_config.n_image_tokens,
            }
        self._tasks: list[asyncio.Task] = []
        self._lora_served: list = []
        self._served = None
        self._clear_served = None
        self._pull_served = None
        self._scale_served = None
        self._kvq_served = None
        self._drain_served = None
        # Graceful drain plane (engine/drain.py; docs/fault-tolerance.md
        # departure ladder): set by the coordinator; LoadMetrics carries
        # it so routers stop selecting this worker and planners count it
        # as departing capacity.
        self.draining = False
        self._drain_coordinator = None
        self._publisher = None
        self._pull_clients: dict = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._step_channel = step_channel
        if step_channel is not None:
            if ici_bridge is not None:
                raise ValueError("co-meshed ICI disagg and --multihost are "
                                 "mutually exclusive (cross-host pools use "
                                 "the host-relay transfer path)")
            if self.runner_config.max_loras > 0:
                raise ValueError("multi-LoRA is not yet supported on "
                                 "multi-host workers (adapter slot writes "
                                 "are not mirrored)")
        self._weight_service = weight_service
        self._weights_from_peer = weights_from_peer
        self._weights_served = None
        self._publish_task: Optional[asyncio.Task] = None
        # Arrival ladder resolution (docs/elasticity.md): init | service |
        # peer_striped | peer | object_store | checkpoint
        self.weights_source = "init"
        # Donor-side chunk tree for striped serving: (weights_key,
        # WeightManifest, per-param raw bytes), built lazily on the first
        # manifest/chunk request and invalidated on elastic reshard. The
        # lock serializes concurrent pullers so the paced device gather
        # runs once, not once per puller.
        self._donor_cache: Optional[tuple] = None
        self._donor_task: Optional[asyncio.Task] = None
        self._donor_task_key: Optional[str] = None
        self._donor_lock = asyncio.Lock()
        # Cold-start ladder (engine/coldstart.py): created in prepare(),
        # closed by the first non-canary token generate() serves.
        self.coldstart = None
        # Live roofline gauges (perf/steptrace.py LiveRoofline) + the
        # interval baseline (prefill/decode tokens, decode steps,
        # device-ms total) behind dynamo_mfu/dynamo_roofline_fraction.
        self._roofline: Optional[LiveRoofline] = None
        self._roof_prev: Optional[tuple] = None
        # Set by the scheduler thread when an exception escapes a step;
        # main() exits non-zero once teardown has run.
        self.engine_failure: Optional[BaseException] = None
        self.outbox = Outbox()

    async def start(self) -> None:
        """prepare + serve in one go (normal startup). Snapshot-gated
        startup calls prepare() and serve() separately around the dump
        point (runtime/snapshot.py)."""
        await self.prepare()
        await self.serve()

    def _weights_key(self) -> str:
        """Arena key: model name + a digest of the FULL config, so any
        architecture change (heads, mlp width, vocab, ...) misses the old
        arena instead of loading wrong-shaped weights."""
        import xxhash

        cfg = self.model_config
        digest = xxhash.xxh64_intdigest(repr(cfg).encode())
        key = f"{cfg.name}:{digest:016x}"
        if self.model_path:
            # Updated weights on disk must miss a stale arena even when
            # the architecture (and so the config digest) is unchanged.
            from ..models.checkpoint import checkpoint_digest

            key += f":{checkpoint_digest(self.model_path)}"
        return key

    def _params_template(self):
        import jax

        from ..models import init_params as _ip

        return jax.eval_shape(
            lambda: _ip(jax.random.PRNGKey(0), self.model_config))

    def _params_from_flat(self, flat, source: str):
        """Validate + rebuild a fetched flat param dict; None on mismatch
        (caller falls back to the next source)."""
        from ..weights.client import unflatten_like

        try:
            params = unflatten_like(self._params_template(), flat)
        except KeyError as exc:
            log.warning("%s weights mismatch (%s); ignoring", source, exc)
            return None
        self.weights_source = source
        return params

    async def _resolve_params(self):
        """Fast-start weight resolution — the arrival ladder
        (docs/elasticity.md): weight service (crash survival) -> striped
        peer pull (parallel across donors) -> single-peer stream -> G4
        object store -> checkpoint -> init. Publishes to the service and
        store whenever enabled so the NEXT arrival is fast."""
        from ..runtime.config import env as _cfg_env

        host_params = None
        client = None
        if self._step_channel is not None:
            # Multi-host: every process resolves weights from its own disk
            # copy (checkpoint or deterministic init) — shm arenas and peer
            # streams hold host-local arrays that cannot represent a
            # cross-host sharded model.
            if self.model_path:
                from ..models.checkpoint import load_params

                log.info("loading checkpoint from %s ...", self.model_path)
                host_params = await asyncio.to_thread(
                    load_params, self.model_path, self.model_config)
                self.weights_source = "checkpoint"
            return host_params, None
        if self._weight_service:
            from ..weights import WeightClient

            client = WeightClient(self._weight_service)
            flat = await asyncio.to_thread(client.fetch, self._weights_key())
            if flat is not None:
                host_params = self._params_from_flat(flat, "service")
        if (host_params is None and self._weights_from_peer
                and self.runtime is not None):
            if _cfg_env("DYNT_WEIGHT_STRIPE"):
                from ..weights.striped import pull_weights_striped

                flat = await pull_weights_striped(
                    self.runtime, self.card.namespace, self.card.component,
                    expected_key=self._weights_key(),
                    max_donors=int(_cfg_env("DYNT_WEIGHT_STRIPE_DONORS")))
                if flat is not None:
                    host_params = self._params_from_flat(
                        flat, "peer_striped")
            if host_params is None:
                from ..weights.streaming import pull_weights

                flat = await pull_weights(self.runtime, self.card.namespace,
                                          self.card.component,
                                          expected_key=self._weights_key())
                if flat is not None:
                    host_params = self._params_from_flat(flat, "peer")
        if host_params is None and _cfg_env("DYNT_WEIGHT_STORE"):
            # No live peer serves this model (scale-up from zero / whole-
            # fleet eviction): the object store is the last fast rung
            # before the slow checkpoint read.
            from ..weights.objstore import (
                fetch_weights_from_store,
                make_store_client,
            )

            flat = await asyncio.to_thread(
                fetch_weights_from_store,
                make_store_client(_cfg_env("DYNT_WEIGHT_STORE")),
                self._weights_key())
            if flat is not None:
                host_params = self._params_from_flat(flat, "object_store")
        if host_params is None and self.model_path:
            # Disk checkpoint: the slow-but-real path. Errors are FATAL —
            # a worker given a model path must never silently fall back
            # to random-init weights.
            from ..models.checkpoint import load_params

            log.info("loading checkpoint from %s ...", self.model_path)
            host_params = await asyncio.to_thread(
                load_params, self.model_path, self.model_config)
            self.weights_source = "checkpoint"
        return host_params, client

    def rederive_identity(self) -> None:
        """Fresh instance identity after a snapshot restore: clones of a
        dumped process must NOT share instance ids — cards would clobber
        and KV event streams would interleave under one worker id (ref:
        snapshot.py worker protocol 're-derives namespace/discovery
        identity'). Call before serve(); safe because nothing has been
        published yet at the dump point."""
        self.instance_id = new_instance_id()
        self.events.worker_id = self.instance_id
        self.events.local_index.worker_id = self.instance_id

    async def prepare(self) -> None:
        """Build the engine: weights on device, steps compiled, scheduler
        running. No runtime connections are made here (snapshot protocol:
        the dump point must have no open sockets)."""
        from ..runtime.config import env as _cfg_env
        from .coldstart import ColdStartLadder

        self.coldstart = ColdStartLadder(f"{self.instance_id:x}")
        log.info("building model runner (%s, pages=%d, batch=%d)...",
                 self.model_config.name, self.runner_config.num_pages,
                 self.runner_config.max_batch)
        with self.coldstart.phase("fetch"):
            host_params, weight_client = await self._resolve_params()
        self.coldstart.source = self.weights_source
        if _cfg_env("DYNT_COMPILE_CACHE_STORE"):
            # Warm the persistent compile cache BEFORE anything traces:
            # with the shared store's entries on disk the warmup/prewarm
            # pass below compiles nothing (engine/compile_cache.py).
            from .compile_cache import sync_down

            t0 = time.monotonic()
            await asyncio.to_thread(sync_down)
            self.coldstart.mark("compile", time.monotonic() - t0)
        with self.coldstart.phase("load"):
            self.runner = await asyncio.to_thread(
                ModelRunner, self.model_config, self.runner_config,
                self.mesh, host_params,
            )
        if self._step_channel is not None:
            # Driver rank of a multi-host worker: every device-program
            # launch from here on is mirrored to the follower processes
            # (parallel/multihost.py) so the SPMD programs stay in lockstep.
            from ..parallel.multihost import MirroredRunner

            self.runner = MirroredRunner(self.runner, self._step_channel)
        log.info("weights source: %s", self.weights_source)
        _store_root = (_cfg_env("DYNT_WEIGHT_STORE")
                       if self._step_channel is None else "")
        _publish_service = (weight_client is not None
                            and self.weights_source != "service")
        # Snapshot on the loop: the _publish thread below must not read
        # loop-domain worker state (weights_source is loop-only).
        _publish_store = bool(_store_root
                              and self.weights_source != "object_store")
        if _publish_service or _publish_store:
            # Publish for the next arrival — best-effort AND off the
            # startup critical path (it only benefits a future restart;
            # the host gather of every param must not delay first serve).
            def _publish() -> None:
                try:
                    if _publish_service:
                        weight_client.store(self._weights_key(),
                                            self.runner.params)
                except Exception:  # noqa: BLE001 — crash survival is
                    # best-effort; serving continues without it
                    log.exception("weight publish failed")
                if not _publish_store:
                    return
                try:
                    from ..weights.client import flatten_params
                    from ..weights.objstore import (
                        make_store_client,
                        publish_weights_to_store,
                        weights_prefix,
                    )

                    store = make_store_client(_store_root)
                    key = self._weights_key()
                    if not store.exists(
                            f"{weights_prefix(key)}/manifest.json"):
                        publish_weights_to_store(
                            store, key, flatten_params(self.runner.params))
                except Exception:  # noqa: BLE001 — store convergence is
                    # best-effort; peers still serve the striped pull
                    log.exception("object-store weight publish failed")

            self._publish_task = asyncio.create_task(
                asyncio.to_thread(_publish))
        if self._warmup:
            with self.coldstart.phase("compile"):
                prewarm = self._prewarm or (
                    "buckets" if _cfg_env("DYNT_PREWARM") else "off")
                if prewarm == "off":
                    await asyncio.to_thread(self.runner.warmup)
                else:
                    # Pre-warm the FULL predicted jit-key space (decode +
                    # every prefill bucket + spec combos; `full`: batched
                    # prefill and the fused decode block as well) so
                    # steady state compiles zero keys — with a warm
                    # persistent cache this is a disk replay, not a
                    # compile.
                    await asyncio.to_thread(
                        self.runner.prewarm, None, prewarm == "full",
                        max(1, int(_cfg_env("DYNT_DECODE_BLOCK") or 1)))
            if _cfg_env("DYNT_COMPILE_CACHE_STORE"):
                # Seed the shared cache with whatever this arrival DID
                # compile — best-effort, off the critical path.
                from .compile_cache import sync_up

                self._tasks.append(asyncio.create_task(
                    asyncio.to_thread(sync_up)))
        if self.kvbm_config is not None and self.kvbm_config.enabled:
            if self._step_channel is not None:
                # Multihost: the paged pool is sharded across hosts —
                # use the leader/worker split (each rank stores its own
                # shards; ref: block_manager/distributed/{leader,worker}.rs)
                from ..block_manager.distributed import (
                    DistributedKvbm,
                    KvbmShardWorker,
                )

                if (self.kvbm_config.disk_blocks
                        or self.kvbm_config.object_store_root):
                    log.warning(
                        "distributed KVBM (multihost) supports the host "
                        "tier only in v1 — ignoring disk_blocks=%s / "
                        "object_store_root=%s",
                        self.kvbm_config.disk_blocks,
                        self.kvbm_config.object_store_root)
                self.runner.kvbm_worker = KvbmShardWorker(
                    self.kvbm_config.host_blocks)
                self.kvbm = DistributedKvbm(self.kvbm_config, self.runner)
            else:
                from ..block_manager import BlockLayoutSpec, KvBlockManager

                self.kvbm = KvBlockManager(
                    self.kvbm_config,
                    BlockLayoutSpec.from_runner_layout(
                        self.runner.kv_layout()),
                )
        self.scheduler = InferenceScheduler(
            self.runner,
            on_stored=self.events.on_stored,
            on_removed=self.events.on_removed,
            kvbm=self.kvbm,
        )
        # Logits-processor factories that declare a `tokenizer` parameter
        # get this model's tokenizer (ref: logits_processing examples —
        # HelloWorldLogitsProcessor takes the tokenizer).
        try:
            from ..llm.tokenizer import load_tokenizer

            self.scheduler.logits_tokenizer = load_tokenizer(
                self.card.tokenizer)
        except Exception:  # noqa: BLE001 — processors are optional;
            # a tokenizer-less deployment still serves
            self.scheduler.logits_tokenizer = None
        self.scheduler.on_fatal = self._on_engine_fatal
        self.scheduler.on_emit_end = self.outbox.flush
        self._report_engine()
        self.scheduler.start()

    def _report_engine(self) -> None:
        """State at start-up what this engine runs on and which path
        each hot-path slot took (log line + dynamo_engine_info)."""
        from ..native import get_native
        from ..runtime.metrics import ENGINE_INFO

        paths = self.runner.kernel_paths()
        native = get_native() is not None
        # Built here, not at the first metrics tick: a device nobody has
        # peaks for (perf/steptrace.py detect_chip) fails the start, once.
        self._roofline = LiveRoofline(
            self.model_config,
            num_chips=int(self.mesh.devices.size),
            weight_bytes_per_param={"int8": 1.0, "int4": 0.53125}.get(
                self.runner_config.weight_dtype, 2.0),
            kv_dtype_bytes=1 if self.runner_config.kv_dtype == "int8" else 2,
        )
        log.info("engine on %s %r devices=%s: decode_attention=%s "
                 "spec_attention=%s prefill_attention=%s weight_matmul=%s%s "
                 "native=%s",
                 paths["platform"], paths["device_kind"],
                 paths["device_ids"], paths["decode_attention"],
                 paths["spec_attention"], paths["prefill_attention"],
                 paths["weight_matmul"],
                 "".join(f" {slot}={paths[slot]}"
                         for slot in ("ssm_update", "ssm_scan", "expert_gmm")
                         if slot in paths), native)
        if self.runner.cache_plan.state:
            from ..models.hybrid import state_slot_bytes

            # what --max-batch costs a model whose cache is mostly state
            per_slot = state_slot_bytes(self.model_config)
            slots = self.runner_config.max_batch
            log.info("recurrent state: %d slots x %.1f MB = %.2f GB", slots,
                     per_slot / 1e6, slots * per_slot / 1e9)
        ENGINE_INFO.labels(
            worker=f"{self.instance_id:x}", platform=paths["platform"],
            device_kind=paths["device_kind"],
            devices=",".join(str(d) for d in paths["device_ids"]),
            decode_attention=paths["decode_attention"],
            spec_attention=paths["spec_attention"],
            prefill_attention=paths["prefill_attention"],
            weight_matmul=paths["weight_matmul"],
            block=("parallel" if self.model_config.parallel_block
                   else "sequential"),
            norm=self.model_config.norm_kind,
            native=str(native).lower()).set(1)

    def _on_engine_fatal(self, exc: BaseException) -> None:
        """Scheduler-thread callback: the engine loop has ended and
        failed its requests. Resolve the shutdown event so main() tears
        down and exits non-zero instead of idling registered."""
        from ..runtime.signals import request_shutdown

        self.engine_failure = exc
        if self._loop is not None:
            self._loop.call_soon_threadsafe(
                request_shutdown, f"engine thread died: {exc!r}")

    async def serve(self) -> None:
        """Connect endpoints + publish the card (requires self.runtime;
        set after restore in snapshot mode)."""
        _t_register = time.monotonic()
        self._loop = asyncio.get_running_loop()
        endpoint = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint("generate")
        )
        canary = PreprocessedRequest(
            request_id="_canary",
            token_ids=[0],
            sampling=SamplingOptions(max_tokens=1, temperature=0.0),
            stop=StopConditions(),
            annotations={"canary": True},
        ).to_wire()
        self._served = await endpoint.serve_endpoint(
            self.generate, instance_id=self.instance_id,
            health_check_payload=canary,
        )
        # clear_kv_blocks endpoint (ref: vllm worker clear_kv_blocks)
        clear_ep = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint("clear_kv_blocks")
        )
        self._clear_served = await clear_ep.serve_endpoint(
            self._clear_kv, instance_id=self.instance_id
        )
        # Local-indexer query endpoint: routers bootstrap / gap-resync from
        # here (ref: kv_router/worker_query.rs).
        kvq_ep = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint("kv_blocks")
        )
        self._kvq_served = await kvq_ep.serve_endpoint(
            self._kv_blocks, instance_id=self.instance_id
        )
        # Peer weight streaming source (ModelExpress analog): cold replicas
        # pull parameters from here instead of re-initializing.
        weights_ep = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint("weights")
        )
        self._weights_served = await weights_ep.serve_endpoint(
            self._stream_weights, instance_id=self.instance_id
        )
        # kv_pull is served in EVERY mode, not just prefill: graceful
        # drains park live decode sequences' pages with the transfer
        # table, and the handoff destination pulls them from here
        # (engine/drain.py; docs/fault-tolerance.md departure ladder).
        pull_ep = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint("kv_pull")
        )
        self._pull_served = await pull_ep.serve_endpoint(
            self._kv_pull, instance_id=self.instance_id
        )
        # Drain control verb (request plane); the status server's
        # POST /drain routes to the same coordinator.
        drain_ep = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint("drain")
        )
        self._drain_served = await drain_ep.serve_endpoint(
            self._drain_endpoint, instance_id=self.instance_id
        )
        if getattr(self.runtime, "status_server", None) is not None:
            self.runtime.status_server.register_drain(self.drain)
        # Startup stamp: dynamo_drain_state=0 (serving). The coordinator
        # only exists once a drain starts, so this is the only place the
        # documented serving sample can come from.
        from .drain import SERVING, set_drain_state

        set_drain_state(self.instance_id, SERVING)
        # Elastic parallelism rescale (ref: vllm handlers scale_elastic_ep)
        ep_ep = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint("scale_elastic_ep")
        )
        self._scale_served = await ep_ep.serve_endpoint(
            self._scale_elastic, instance_id=self.instance_id
        )
        # LoRA endpoints (ref: vllm worker LoRA load/unload/list endpoints)
        if self.loras is not None:
            self.card.runtime_config["lora"] = {
                "max_loras": self.runner_config.max_loras,
                "rank": self.runner_config.lora_rank,
            }
            for ep_name, handler in (("lora_load", self._lora_load),
                                     ("lora_unload", self._lora_unload),
                                     ("lora_list", self._lora_list)):
                ep = (
                    self.runtime.namespace(self.card.namespace)
                    .component(self.card.component)
                    .endpoint(ep_name)
                )
                self._lora_served.append(await ep.serve_endpoint(
                    handler, instance_id=self.instance_id))
            for name, path in self._initial_loras.items():
                await self._do_lora_load(name, path)
        await publish_card(self.runtime, self.card, self.instance_id)
        publisher = self.runtime.event_publisher(self.card.namespace)
        self._publisher = publisher
        if hasattr(publisher, "set_snapshot_fn"):
            # Durable journal plane: rotations seed the new generation
            # with this worker's full index instead of the old history.
            from ..kv_router.protocols import KV_SNAPSHOT_TOPIC

            publisher.set_snapshot_fn(
                lambda: [(KV_SNAPSHOT_TOPIC,
                          self.events.local_index.dump())])
        self._tasks.append(asyncio.create_task(self._event_drain(publisher)))
        if self.coldstart is not None:
            self.coldstart.mark("register", time.monotonic() - _t_register)
        log.info("tpu worker serving %s as %s (instance=%x)",
                 self.model_config.name, self.card.name, self.instance_id)

    async def _clear_kv(self, body, ctx) -> AsyncIterator[dict]:
        cleared = self.scheduler.pool.clear()
        self.events.on_cleared()
        yield {"cleared_blocks": len(cleared)}

    async def _kv_blocks(self, body, ctx=None) -> AsyncIterator[dict]:
        yield self.events.local_index.dump()

    async def _donor_tree(self):
        """Donor-side chunk tree for striped serving: gather every param
        to host ONCE (paced — see _build_donor_tree), chunk it, cache the
        result for every concurrent/subsequent puller until a reshard
        invalidates it. Single-flight: the lock only guards the cache
        check and build-task claim — the slow gather itself runs
        unlocked, and concurrent pullers await the same task."""
        key = self._weights_key()
        async with self._donor_lock:
            cache = self._donor_cache
            if cache is not None and cache[0] == key:
                return cache[1], cache[2]
            task = self._donor_task
            if (task is None or self._donor_task_key != key
                    or (task.done() and task.exception() is not None)):
                task = asyncio.create_task(
                    self._build_donor_tree(key))  # dynaflow: disable=DF201 -- create_task only SCHEDULES the build; the slow gather runs after the lock is released, awaited below outside the lock
                self._donor_task = task
                self._donor_task_key = key
        # Shielded: one puller disconnecting must not cancel the build
        # the other pullers are waiting on.
        return await asyncio.shield(task)

    async def _build_donor_tree(self, key: str):
        """The slow half of _donor_tree. The device->host gathers ride
        the scheduler's dispatch/drain gap and are duty-cycle paced by
        DYNT_WEIGHT_STREAM_BW_FRAC (the PR-8 KVBM offload formula: a
        gather costing g seconds defers the next by g*(1/frac-1)), so
        seeding a newcomer does not regress this donor's decode ITL."""
        import jax
        import numpy as np

        from ..runtime.config import env as _cfg_env
        from ..runtime.metrics import WEIGHT_STREAM_DEFERRED
        from ..weights.striped import BandwidthBudget, WeightManifest

        budget = BandwidthBudget(_cfg_env("DYNT_WEIGHT_STREAM_BW_FRAC"))
        leaves = jax.tree_util.tree_flatten_with_path(
            self.runner.params)[0]
        flat: list[tuple[str, np.ndarray]] = []
        for path, leaf in leaves:
            pkey = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path)
            t0 = time.monotonic()
            if self.scheduler is not None:
                q = self.scheduler.run_in_gap(
                    lambda a=leaf: np.asarray(a))
                arr, exc = await asyncio.to_thread(q.get, True, 60.0)
                if exc is not None:
                    raise exc
            else:
                arr = await asyncio.to_thread(np.asarray, leaf)
            flat.append((pkey, arr))
            defer = budget.defer_after(time.monotonic() - t0)
            if defer > 0:
                WEIGHT_STREAM_DEFERRED.inc(defer)
                await asyncio.sleep(defer)

        def _chunk():
            manifest = WeightManifest.build(flat, key)
            bufs = [np.ascontiguousarray(a).tobytes() for _, a in flat]
            return manifest, bufs

        manifest, bufs = await asyncio.to_thread(_chunk)
        self._donor_cache = (key, manifest, bufs)
        return manifest, bufs

    async def _stream_weights(self, body, ctx=None) -> AsyncIterator[dict]:
        """Serve this replica's parameters to a cold peer. The body
        multiplexes three shapes (weights/striped.py wire protocol):

          {}                           legacy full stream (back-compat)
          {"weights_manifest": true}   striped: one manifest frame
          {"weights_chunks": [cid..]}  striped: digest-stamped chunk frames

        All serialization (device->host gather + tobytes copies) runs
        off the event loop so multi-GB copies never stall it
        mid-token-stream."""
        from ..weights.client import flatten_params
        from ..weights.streaming import encode_param_chunks, manifest_frame

        if self._step_channel is not None:
            yield {"error": "multi-host workers do not stream weights "
                            "(parameters are sharded across hosts); cold "
                            "peers load from the shared checkpoint"}
            return
        body = body or {}
        if body.get("weights_manifest") or "weights_chunks" in body:
            from ..weights.striped import encode_chunk_frames

            try:
                manifest, bufs = await self._donor_tree()
            except Exception as exc:  # noqa: BLE001 — report to the
                # puller (it falls down the arrival ladder), keep serving
                log.exception("donor chunk tree build failed")
                yield {"error": f"donor tree build failed: {exc!r}"}
                return
            if body.get("weights_manifest"):
                yield manifest.to_wire()
                return
            for frame in encode_chunk_frames(
                    manifest, bufs, [int(c) for c in body["weights_chunks"]]):
                yield frame
            return
        flat = await asyncio.to_thread(flatten_params, self.runner.params)
        yield manifest_frame(self._weights_key(), len(flat))
        for index, (key, arr) in enumerate(flat):
            frames = await asyncio.to_thread(
                lambda k=key, a=arr: list(encode_param_chunks([(k, a)])))
            for frame in frames:
                frame["total_params"] = len(flat)
                frame["index"] = index
                yield frame

    async def _scale_elastic(self, body, ctx=None) -> AsyncIterator[dict]:
        """Re-place params on a new dp/tp/sp/ep mesh split at runtime.
        Body: {"dp": n, "tp": n, "sp": n, "ep": n} (missing axes default 1).
        In-flight requests are finished with 'migrate' (the frontend
        Migration operator replays them, tokens preserved) before the KV
        pool resets."""
        if self._step_channel is not None:
            yield {"ok": False,
                   "error": "elastic reshard is not supported on a "
                            "multi-host worker (mesh changes are not "
                            "mirrored); redeploy with the new topology"}
            return
        cfg = MeshConfig(
            dp=int(body.get("dp", 1)), tp=int(body.get("tp", 1)),
            sp=int(body.get("sp", 1)), ep=int(body.get("ep", 1)),
        )
        mesh = make_mesh(cfg)

        def _do() -> None:
            self.scheduler.abort_all("elastic reshard")
            self.scheduler.pool.clear()
            self.runner.reshard(mesh)

        q = self.scheduler.run_in_step(_do)
        await asyncio.get_running_loop().run_in_executor(None, q.get)
        self.events.on_cleared()
        # Resharded params live on a new mesh split: the cached donor
        # chunk tree (stale host gathers) must be rebuilt on next pull.
        self._donor_cache = None
        self._donor_task = None
        yield {"ok": True, "mesh": dict(mesh.shape)}

    # -- multi-LoRA --------------------------------------------------------

    async def _do_lora_load(self, name: str, path: str) -> None:
        adapter = self.loras.load(name, path)
        # Pack writes are serialized with stepping (one step must never see
        # a half-written slot).
        q = self.scheduler.run_in_step(
            lambda: self.runner.set_lora_slot(adapter.slot, adapter))
        _, exc = await asyncio.get_running_loop().run_in_executor(None, q.get)
        if exc is not None:
            self.loras.unload(name)
            raise exc
        await self._republish_loras()

    async def _republish_loras(self) -> None:
        """Advertise loaded adapters on the card so frontends route
        model=<adapter> here (ref: lora.rs routing via discovery)."""
        self.card.runtime_config["loras"] = self.loras.names()
        await publish_card(self.runtime, self.card, self.instance_id)

    async def _lora_load(self, body: dict, ctx=None) -> AsyncIterator[dict]:
        try:
            name = body["name"]
            await self._do_lora_load(name, body["path"])
        except Exception as exc:  # noqa: BLE001 — report, don't kill endpoint
            yield {"error": str(exc)}
            return
        yield {"ok": True, "name": name, "slot": self.loras.slot_of(name)}

    async def _lora_unload(self, body: dict, ctx=None) -> AsyncIterator[dict]:
        """Two-phase unload: unmap the name (new requests fail fast, slot
        stays reserved), then on the scheduler thread refuse if any
        in-flight sequence still uses the slot — zeroing (or a later load
        reusing it) would silently switch weights mid-generation. Busy ->
        the unload is aborted and the caller retries after draining."""
        try:
            name = body["name"]
            adapter = self.loras.begin_unload(name)
        except Exception as exc:  # noqa: BLE001
            yield {"error": str(exc)}
            return

        def _clear() -> None:
            busy = self.scheduler.lora_in_flight(adapter.slot)
            if busy:
                raise RuntimeError(
                    f"adapter {name!r} busy: {busy} in-flight sequence(s); "
                    "retry after they finish")
            self.runner.clear_lora_slot(adapter.slot)

        q = self.scheduler.run_in_step(_clear)
        _, exc = await asyncio.get_running_loop().run_in_executor(None, q.get)
        if exc is not None:
            self.loras.abort_unload(adapter)
            yield {"error": str(exc)}
            return
        self.loras.commit_unload(adapter)
        await self._republish_loras()
        yield {"ok": True, "name": name}

    async def _lora_list(self, body, ctx=None) -> AsyncIterator[dict]:
        yield {"adapters": self.loras.list()}

    # -- disaggregation: prefill-side export -------------------------------

    def _transfer_params(self, transfer_id: str, layout: KvLayoutDescriptor,
                         prompt_len: int, streaming: bool = False) -> dict:
        params = {
            "transfer_id": transfer_id,
            "namespace": self.card.namespace,
            "component": self.card.component,
            "instance_id": self.instance_id,
            "layout": layout.to_wire(),
            "prompt_len": prompt_len,
        }
        if streaming:
            # No first_token yet: the pull stream's terminal frame
            # carries it once the prompt pass finishes.
            params["streaming"] = True
        if self.ici_bridge is not None:
            # Decode workers in THIS process (co-meshed pools) pull over
            # ICI through the bridge; remote ones fall back to the wire.
            params["bridge_token"] = self.ici_bridge.token
        return params

    def _register_transfer(self, seq, first_token: int,
                           page_ids: list[int]) -> dict:
        """Runs on the scheduler thread when a prefill-only sequence
        finishes its prompt pass: park the pages with the transfer table
        and describe the pull route (ref §3.4 disaggregated_params). A
        sequence whose chunks were streamed (on_prefill_chunk) finishes
        its EXISTING StreamingTransfer instead of opening a new one."""
        import uuid as _uuid

        layout = KvLayoutDescriptor.from_wire(self.runner.kv_layout())
        stream = self._stream_transfers.pop(seq.request.request_id, None)
        if stream is not None:
            stream.finish(first_token, page_ids)
            return {**self._transfer_params(stream.transfer_id, layout,
                                            seq.prompt_len, streaming=True),
                    "first_token": first_token}
        transfer_id = _uuid.uuid4().hex
        self.transfers.add(PendingTransfer(
            transfer_id=transfer_id,
            page_ids=page_ids,
            release=lambda: self.scheduler.release_transfer_pages(seq),
            layout=layout,
            prompt_len=seq.prompt_len,
        ))
        return self._transfer_params(transfer_id, layout, seq.prompt_len)

    def _stream_transfer_chunk(self, seq, new_page_ids):
        """Scheduler-thread hook for each NON-final prefill chunk of a
        prefill-only sequence (InferenceScheduler._stream_prefill_chunk):
        park the newly completed pages with a StreamingTransfer so the
        decode worker pulls chunk i while chunk i+1 computes. First call
        registers the transfer and returns the params the scheduler
        emits mid-stream; `new_page_ids=None` is the abort signal
        (cancel/error before the prompt finished)."""
        import uuid as _uuid

        from ..runtime.metrics import DISAGG_STREAMED_PAGES

        rid = seq.request.request_id
        if new_page_ids is None:
            stream = self._stream_transfers.pop(rid, None)
            if stream is not None:
                stream.fail()
            return None
        stream = self._stream_transfers.get(rid)
        if stream is not None:
            stream.append_pages(new_page_ids)
            DISAGG_STREAMED_PAGES.labels(
                worker=f"{self.instance_id:x}").inc(len(new_page_ids))
            return None
        layout = KvLayoutDescriptor.from_wire(self.runner.kv_layout())
        stream = StreamingTransfer(
            transfer_id=_uuid.uuid4().hex,
            page_ids=[int(p) for p in new_page_ids],
            release=lambda: self.scheduler.release_transfer_pages(seq),
            layout=layout,
            prompt_len=seq.prompt_len,
            table=self.transfers,
        )
        self._stream_transfers[rid] = stream
        self.transfers.add(stream)
        DISAGG_STREAMED_PAGES.labels(
            worker=f"{self.instance_id:x}").inc(len(new_page_ids))
        return self._transfer_params(stream.transfer_id, layout,
                                     seq.prompt_len, streaming=True)

    async def _kv_pull(self, body: dict, ctx=None) -> AsyncIterator[dict]:
        """Decode workers pull parked prefill KV here: gather the pages on
        the scheduler thread (the cache buffer is donated through steps),
        then stream chunked binary frames."""
        from ..runtime.otel import get_tracer

        transfer_id = (body or {}).get("transfer_id", "")
        # Server half of the transfer trace: child of the decode side's
        # kv_transfer.pull via the wire traceparent.
        span = get_tracer().start_span(
            "kv_transfer.serve",
            parent=getattr(ctx, "traceparent", None), kind=2,
            **{"transfer.id": transfer_id})
        # Claim removes the entry atomically: TTL expiry can no longer
        # release (and let the pool reuse) these pages mid-gather.
        transfer = self.transfers.claim(transfer_id)
        if transfer is None:
            span.end(ok=False)
            yield {"error": f"unknown transfer {transfer_id}"}
            return
        if transfer.streaming:
            # Chunked handoff: stream pages as the (still running) prompt
            # pass parks them — the pipeline that overlaps the wire
            # transfer with prefill compute (docs/disaggregation.md).
            ok = False
            try:
                async for frame in self._stream_kv_pull(transfer, span,
                                                        ctx):
                    if frame.get("done"):
                        ok = True
                    yield frame
            finally:
                # Covers clean ends, error frames, and a decode-side
                # disconnect (GeneratorExit) alike; claimer owns the one
                # release.
                span.end(ok=ok)
                transfer.release()
            return
        try:
            page_ids = transfer.page_ids
            # Only the device gather holds the step thread; the D2H copy
            # of the bundle runs in a worker thread so decode keeps
            # stepping while the transfer drains (VERDICT: transfers must
            # not steal decode step time).
            resultq = self.scheduler.run_in_step(
                lambda: self.runner.gather_pages_device(page_ids)
            )
            try:
                # Bounded wait: if the scheduler is shutting down the final
                # control drain runs the gather, but never hang the handler.
                device_blocks, exc = await asyncio.to_thread(
                    resultq.get, True, 60.0)
            except Exception as exc_:  # noqa: BLE001 — queue.Empty on timeout
                yield {"error": f"gather timed out: {exc_!r}"}
                return
            if exc is not None:
                yield {"error": f"gather failed: {exc!r}"}
                return
            import numpy as _np

            try:
                # Async dispatch means a failed device gather can surface
                # only here, at materialization: keep the structured error
                # contract of the other failure paths.
                blocks = await asyncio.to_thread(_np.asarray, device_blocks)
            except Exception as exc_:  # noqa: BLE001
                yield {"error": f"gather readback failed: {exc_!r}"}
                return
            span.set_attribute("pages", len(page_ids))
            span.set_attribute("bytes",
                               len(page_ids) * transfer.layout.page_bytes())
            for frame in encode_block_chunks(blocks, transfer.layout):
                yield frame
            span.end(ok=True)
        finally:
            # Runs even when the decode side disconnects mid-stream (the
            # generator is aclose()d): close the span and return the
            # pages to the pool now, not after the TTL.
            span.end(ok=False)
            transfer.release()

    async def _stream_kv_pull(self, transfer: StreamingTransfer, span,
                              ctx) -> AsyncIterator[dict]:
        """Serve a streaming transfer: gather + send each chunk's pages
        as the scheduler parks them, then a terminal frame carrying the
        first sampled token. Gathers ride the prefill scheduler's
        dispatch/drain gap (run_in_gap) so they queue behind in-flight
        work instead of delaying the next prefill chunk."""
        import numpy as _np

        layout = transfer.layout
        total = transfer.total_pages
        deadline = getattr(ctx, "deadline", None) if ctx is not None else None
        budget = None
        if deadline is not None:
            budget = deadline.remaining()
            if budget <= 0:
                # Already expired (remaining() can be <= 0): fail fast
                # to the recompute fallback instead of gathering pages
                # for a request nobody can finish in time.
                yield {"error": "request deadline expired before "
                                "streaming kv pull"}
                return
        # Deadline-carrying requests get exactly their remaining budget
        # (the end-to-end contract). Deadlineless pulls get a 120s STALL
        # window re-armed on every chunk of progress — a long prompt may
        # legitimately prefill for many minutes; only a lull with no new
        # pages aborts to recompute.
        overall = time.monotonic() + max(1.0,
                                         budget if budget is not None
                                         else 120.0)
        gap_exec = getattr(self.scheduler, "run_in_gap",
                           self.scheduler.run_in_step)
        sent = 0
        while True:
            ids, done, failed = await asyncio.to_thread(
                transfer.wait_ready, sent, 1.0)
            if failed:
                yield {"error": f"transfer {transfer.transfer_id} aborted "
                                "(prefill cancelled)"}
                return
            new = ids[sent:]
            if not new and not done:
                if time.monotonic() > overall:
                    yield {"error": "streaming transfer timed out "
                                    "awaiting prefill chunks"}
                    return
                continue
            if new and budget is None:
                overall = time.monotonic() + 120.0  # progress re-arms
            if new:
                resultq = gap_exec(
                    lambda ids=new: self.runner.gather_pages_device(ids))
                try:
                    device_blocks, exc = await asyncio.to_thread(
                        resultq.get, True, 60.0)
                except Exception as exc_:  # noqa: BLE001 — queue.Empty
                    yield {"error": f"gather timed out: {exc_!r}"}
                    return
                if exc is not None:
                    yield {"error": f"gather failed: {exc!r}"}
                    return
                try:
                    blocks = await asyncio.to_thread(_np.asarray,
                                                     device_blocks)
                except Exception as exc_:  # noqa: BLE001
                    yield {"error": f"gather readback failed: {exc_!r}"}
                    return
                for frame in encode_block_chunks(blocks, layout, base=sent,
                                                 total_pages=total):
                    yield frame
                sent += len(new)
            if done and sent >= len(ids):
                span.set_attribute("pages", sent)
                span.set_attribute("bytes", sent * layout.page_bytes())
                yield {"done": True, "first_token": transfer.first_token,
                       "total_pages": total}
                return

    # -- disaggregation: decode-side onboard -------------------------------

    async def _pull_remote_kv(self, params: dict, deadline=None,
                              traceparent=None, record_id=None):
        """Pull prefill KV blocks from the prefill worker. Returns
        (bundle, first_token), or (None, None) for the recompute fallback
        (the aggregated fallback the reference also takes when transfer
        fails). Streaming handoffs (docs/disaggregation.md) carry the
        first token in the pull stream's terminal frame — the params dict
        has none when the prefill pass was still running at dispatch.
        `deadline` is the request's REMAINING end-to-end budget
        (ctx.deadline): the pull's frame waits are bounded by it instead
        of a fresh flat timeout. The pull leg is traced
        (kv_transfer.pull, with link/bytes/pages attributes) and recorded
        on the request's flight-recorder timeline."""
        from ..runtime.otel import get_tracer

        if params.get("mock") or "layout" not in params:
            return None, None  # mocker handoff carries no data; recompute
        link = ("ici" if self.ici_bridge is not None
                and params.get("bridge_token") == self.ici_bridge.token
                else "dcn")
        span = get_tracer().start_span(
            "kv_transfer.pull", parent=traceparent, kind=3,
            **{"transfer.id": params.get("transfer_id", ""), "link": link})
        try:
            blocks, first = await self._pull_remote_kv_inner(
                params, deadline, span, traceparent, record_id, link)
            if first is None:
                first = params.get("first_token")
            if blocks is not None:
                span.end(ok=True)
            return blocks, first
        finally:
            span.end(ok=False)  # fallback paths; success already ended

    async def _pull_remote_kv_inner(self, params: dict, deadline, span,
                                    traceparent, record_id, link):
        from ..runtime.flight_recorder import get_recorder
        from ..runtime.push_router import PushRouter

        if link == "ici":
            # Same process, co-meshed pools: direct chip-to-chip pull over
            # ICI (device bundle, no host relay). Any failure degrades to
            # the recompute fallback like the wire path.
            blocks, first = await self.ici_bridge.pull(
                params["transfer_id"], self.runner)
            if blocks is not None:
                get_recorder().event(record_id, "kv_pull", link="ici",
                                     transfer_id=params["transfer_id"])
            return blocks, first
        remote_layout = KvLayoutDescriptor.from_wire(params["layout"])
        local_layout = KvLayoutDescriptor.from_wire(self.runner.kv_layout())
        if not remote_layout.compatible(local_layout):
            log.warning("kv layout mismatch (remote=%s local=%s); "
                        "recomputing prefill", remote_layout, local_layout)
            return None, None
        subject = f"{params['namespace']}/{params['component']}/kv_pull"
        router = self._pull_clients.get(subject)
        if router is None:
            endpoint = (
                self.runtime.namespace(params["namespace"])
                .component(params["component"])
                .endpoint("kv_pull")
            )
            router = PushRouter(endpoint.client(), mode="round_robin")
            await router.client.start()
            self._pull_clients[subject] = router
        assembler = BlockAssembler()
        pulled_bytes = 0
        first_token = None
        start = time.monotonic()
        try:
            async for frame in router.generate(
                {"transfer_id": params["transfer_id"]},
                instance_id=params["instance_id"],
                deadline=deadline,
                traceparent=span.traceparent or traceparent,
            ):
                if frame.get("error"):
                    log.warning("kv pull failed: %s", frame["error"])
                    return None, None
                if frame.get("done"):
                    # Streaming handoff terminal frame: the first sampled
                    # token, produced after the last chunk we overlapped.
                    first_token = frame.get("first_token")
                    continue
                pulled_bytes += len(frame.get("data") or b"")
                assembler.add(frame)
        except Exception:  # noqa: BLE001 — any transfer failure -> recompute
            log.exception("kv pull transport failure; recomputing prefill")
            return None, None
        if not assembler.complete:
            log.warning("kv pull incomplete; recomputing prefill")
            return None, None
        blocks, _ = assembler.assemble()
        span.set_attribute("bytes", pulled_bytes)
        span.set_attribute("pages", int(blocks.shape[0]))
        get_recorder().event(
            record_id, "kv_pull", link="dcn", bytes=pulled_bytes,
            pages=int(blocks.shape[0]),
            duration_ms=round((time.monotonic() - start) * 1e3, 3))
        # Stage the H2D copy HERE (async context, off the step thread) so
        # admission's scatter only does the cheap fused write — the bulk
        # upload overlaps decode stepping. Failure falls back to the host
        # bundle (scatter_from_host does its own device_put in-step).
        try:
            import jax as _jax

            from .ici_transfer import bundle_sharding

            dev = _jax.device_put(
                blocks, bundle_sharding(
                    self.runner.mesh,
                    head_sharded=not self.runner.model_config.is_mla))
            await asyncio.to_thread(_jax.block_until_ready, dev)
            return dev, first_token
        except Exception:  # noqa: BLE001 — host bundle still works
            log.exception("onboard H2D staging failed; using host bundle")
            return blocks, first_token

    # -- graceful drain (engine/drain.py; docs/fault-tolerance.md) ---------

    def _load_metrics(self) -> LoadMetrics:
        active, waiting = self.scheduler.queue_depth()
        return LoadMetrics(
            worker_id=self.instance_id,
            active_blocks=(self.scheduler.pool.num_pages - 1
                           - self.scheduler.pool.free_count()),
            total_blocks=self.scheduler.pool.num_pages,
            active_requests=active,
            waiting_requests=waiting,
            kv_usage=self.scheduler.pool.usage(),
            step_wall_ms=self.scheduler.stats.last_step_wall_ms,
            prefill_tokens_in_step=self.scheduler.stats.prefill_tokens_last_step,
            decode_tokens_in_step=self.scheduler.stats.decode_tokens_last_step,
            device_ms_in_step=self.scheduler.stats.device_ms_last_step,
            host_ms_in_step=self.scheduler.stats.host_ms_last_step,
            draining=self.draining,
        )

    async def announce_draining(self) -> None:
        """Flip this worker to draining everywhere routers look: the
        discovery card (runtime_config) and an IMMEDIATE LoadMetrics
        publish — waiting for the next ~0.5s load tick would leave a
        window where routers keep selecting a vacating worker."""
        self.draining = True
        self.card.runtime_config["draining"] = True
        try:
            await publish_card(self.runtime, self.card, self.instance_id)
        except Exception:  # noqa: BLE001 — LoadMetrics still flips
            # routers; lease expiry is the backstop
            log.exception("draining card republish failed")
        if self._publisher is not None and self.scheduler is not None:
            try:
                await self._publisher.publish(
                    LOAD_TOPIC, self._load_metrics().to_wire())
            except Exception:  # noqa: BLE001
                log.exception("draining load publish failed")

    def register_drain_handoff(self, seq, page_ids: list[int],
                               computed_tokens: int) -> dict:
        """Scheduler-thread callback from InferenceScheduler.drain_sweep:
        park a live decode sequence's computed pages with the transfer
        table (served by our kv_pull endpoint while we drain) and
        describe the pull route plus the resume state the destination
        needs to continue the stream bit-identically."""
        import uuid as _uuid

        layout = KvLayoutDescriptor.from_wire(self.runner.kv_layout())
        transfer_id = _uuid.uuid4().hex
        self.transfers.add(PendingTransfer(
            transfer_id=transfer_id,
            page_ids=[int(p) for p in page_ids],
            release=lambda: self.scheduler.release_transfer_pages(seq),
            layout=layout,
            prompt_len=computed_tokens,
        ))
        params = self._transfer_params(transfer_id, layout,
                                       computed_tokens)
        # Never offer the ICI bridge for drain handoffs: the bridge
        # serves the comesh prefill pool's transfers, not ours, and the
        # whole process is departing anyway — the wire path is the one
        # that works from any peer.
        params.pop("bridge_token", None)
        params["handoff"] = {
            "seed": int(seq.seed),
            "generated": [int(t) for t in seq.generated],
            "prompt_len": int(seq.prompt_len),
        }
        return params

    async def drain(self, reason: str = "signal",
                    deadline_secs: Optional[float] = None) -> dict:
        """Run (or join) the departure ladder (engine/drain.py).
        Idempotent: double SIGTERM / a control verb racing the signal
        converge on one ladder run and one report. `deadline_secs`
        overrides DYNT_DRAIN_DEADLINE_SECS for THIS worker's ladder —
        a comesh main splits one eviction notice across its two
        workers' drains instead of granting the budget twice (only
        effective on the call that starts the ladder; joins keep the
        original budget)."""
        from .drain import DrainCoordinator

        if self.scheduler is None:
            return {"skipped": True, "reason": "no scheduler"}
        if self._drain_coordinator is None:
            self._drain_coordinator = DrainCoordinator(
                self, deadline_secs=deadline_secs)
        return await self._drain_coordinator.drain(reason)

    async def _drain_endpoint(self, body: dict, ctx=None
                              ) -> AsyncIterator[dict]:
        """Request-plane drain control verb: run the ladder, stream the
        report. body.shutdown=true also resolves the process's shutdown
        event so main() proceeds to deregister after the drain."""
        report = await self.drain(reason=(body or {}).get("reason",
                                                          "control"))
        try:
            yield report
        finally:
            # In a finally: a caller that closes the stream as soon as
            # the report frame lands (or a transport teardown racing the
            # long drain) raises GeneratorExit at the yield — the drain
            # already ran and the worker is terminally out of routing,
            # so dropping the requested shutdown here would strand a
            # vacated process waiting on an event nobody will set.
            if (body or {}).get("shutdown"):
                from ..runtime.signals import request_shutdown

                request_shutdown("drain control verb")

    def _publish_engine_gauges(self) -> None:
        """Tokens processed, programs launched and what they were
        launched over, page-time reserved and per-chip device memory
        (docs/metrics.md: dynamo_engine_tokens, dynamo_engine_launches,
        dynamo_engine_positions, dynamo_prefill_row_blocks_total,
        dynamo_prefill_attn_launches_total, dynamo_prefill_attn_blocks_total,
        dynamo_kv_reserved_page_ms, dynamo_kv_window_*, dynamo_latent_*,
        dynamo_kv_page_layer_reads_total,
        dynamo_prefill_cross_decoder_rows_total,
        dynamo_ssm_prefill_*, dynamo_ssm_scan_launches_total,
        dynamo_program_launches,
        dynamo_program_tokens,
        dynamo_device_hbm_bytes)."""
        from ..runtime.metrics import (
            DEVICE_HBM_BYTES,
            ENGINE_LAUNCHES,
            ENGINE_POSITIONS,
            ENGINE_TOKENS,
            EMIT_FRAMES,
            EMIT_HANDOVERS,
            KV_PAGE_LAYER_READS,
            KV_RESERVED_PAGE_MS,
            KV_WINDOW_ALLOC_FAIL,
            KV_WINDOW_EDGE_TOKENS,
            KV_WINDOW_PAGES_FREED,
            KV_WINDOW_RESERVED_PAGE_MS,
            LATENT_DECODE_TOKENS,
            LATENT_PREFILL_EXPAND_TOKENS,
            MOE_DROPPED_SLOTS,
            MOE_EXPERT_CALLS,
            MOE_EXPERT_TOKENS,
            MOE_EXPERTS_TOUCHED,
            PREFILL_ATTN_BLOCKS,
            PREFILL_ATTN_LAUNCHES,
            PREFILL_CROSS_DECODER_ROWS,
            PREFILL_ROW_BLOCKS,
            PROGRAM_LAUNCHES,
            PROGRAM_TOKENS,
            SSM_PREFILL_LAUNCH_ROWS,
            SSM_PREFILL_POSITIONS,
            SSM_SCAN_LAUNCHES,
            SSM_STATE_SLOT_MS,
        )

        worker = f"{self.instance_id:x}"
        stats = self.scheduler.stats
        ENGINE_TOKENS.labels(worker=worker, kind="prefill").set(
            stats.prefill_tokens)
        ENGINE_TOKENS.labels(worker=worker, kind="decode").set(
            stats.decode_tokens)
        for kind, count in (
                ("prefill", stats.prefill_launches),
                ("decode_block", stats.decode_block_launches),
                ("decode_step", getattr(self.runner, "decode_steps", 0))):
            ENGINE_LAUNCHES.labels(worker=worker, kind=kind).set(count)
        ENGINE_POSITIONS.labels(worker=worker, kind="prefill").set(
            getattr(self.runner, "prefill_positions", 0))
        EMIT_FRAMES.labels(worker=worker).set(stats.emit_frames)
        EMIT_HANDOVERS.labels(worker=worker).set(self.outbox.handovers)
        blocks = getattr(self.runner, "prefill_row_blocks", {})
        if any(blocks.values()):  # counted with int4 weights only
            for state, count in blocks.items():
                PREFILL_ROW_BLOCKS.labels(worker=worker, state=state).set(
                    count)
        for path, count in getattr(
                self.runner, "prefill_attn_launches", {}).items():
            PREFILL_ATTN_LAUNCHES.labels(worker=worker, path=path).set(count)
        stack = getattr(self.runner, "prefill_attn_group", "full")
        for group, attr in ((stack, "prefill_attn_blocks"),
                            ("window", "prefill_attn_window_blocks")):
            blocks = getattr(self.runner, attr, {})
            if group == stack or any(blocks.values()):
                for state, count in blocks.items():
                    PREFILL_ATTN_BLOCKS.labels(
                        worker=worker, state=state, group=group).set(count)
        # a snapshot: the scheduler thread adds keys as it launches
        for (fn, key), (launches, tokens) in list(getattr(
                self.runner, "program_launches", {}).items()):
            PROGRAM_LAUNCHES.labels(worker=worker, fn=fn, key=key).set(
                launches)
            if fn.startswith("prefill"):
                PROGRAM_TOKENS.labels(worker=worker, fn=fn, key=key).set(
                    tokens)
        KV_RESERVED_PAGE_MS.labels(worker=worker).set(
            stats.reserved_page_ms)
        win_pool = self.scheduler.win_pool
        if win_pool is not None:  # only a model with window layers
            KV_WINDOW_RESERVED_PAGE_MS.labels(worker=worker).set(
                stats.window_reserved_page_ms)
            KV_WINDOW_ALLOC_FAIL.labels(worker=worker).set(
                win_pool.alloc_fail)
            for phase, pages in win_pool.freed_behind.items():
                KV_WINDOW_PAGES_FREED.labels(
                    worker=worker, phase=phase).set(pages)
                KV_WINDOW_EDGE_TOKENS.labels(
                    worker=worker, phase=phase).set(
                        win_pool.edge_tokens[phase])
        if stats.state_slot_ms:  # only a model with recurrent state
            SSM_STATE_SLOT_MS.labels(worker=worker).set(stats.state_slot_ms)
            for carry, count in getattr(
                    self.runner, "ssm_prefill_positions", {}).items():
                SSM_PREFILL_POSITIONS.labels(
                    worker=worker, carry=carry).set(count)
                SSM_PREFILL_LAUNCH_ROWS.labels(
                    worker=worker, carry=carry).set(
                        self.runner.ssm_prefill_rows[carry])
            if self.model_config.ssm_layers:  # only Mamba layers scan
                for path, count in getattr(
                        self.runner, "ssm_scan_launches", {}).items():
                    SSM_SCAN_LAUNCHES.labels(
                        worker=worker, path=path).set(count)
        reads = getattr(self.runner, "page_layer_reads", {})
        if any(reads.values()):  # only a model with pages read, not owned
            for by, count in reads.items():
                KV_PAGE_LAYER_READS.labels(worker=worker, by=by).set(count)
        if getattr(self.runner, "runs_cross_decoder", False):
            for chunk, count in (("last", stats.prefill_rows_last),
                                 ("earlier", stats.prefill_rows_earlier)):
                PREFILL_CROSS_DECODER_ROWS.labels(
                    worker=worker, chunk=chunk).set(count)
        if stack == "latent":  # only a model with latent attention
            LATENT_DECODE_TOKENS.labels(worker=worker).set(
                self.runner.latent_decode_tokens)
            LATENT_PREFILL_EXPAND_TOKENS.labels(worker=worker).set(
                self.runner.latent_prefill_expand_tokens)
        if stats.moe_counts is not None:
            from .model_runner import MOE_PHASES

            lo, hi = self.model_config.held_experts
            tokens = stats.moe_counts[:, :hi - lo].sum(axis=0)
            for i, count in enumerate(tokens):
                MOE_EXPERT_TOKENS.labels(
                    worker=worker, expert=str(lo + i)).set(int(count))
            MOE_DROPPED_SLOTS.labels(worker=worker).set(
                int(stats.moe_counts[:, hi - lo].sum()))
            for phase, row in zip(MOE_PHASES, stats.moe_counts):
                MOE_EXPERTS_TOUCHED.labels(worker=worker, phase=phase).set(
                    int(row[hi - lo + 1]))
                MOE_EXPERT_CALLS.labels(worker=worker, phase=phase).set(
                    int(row[hi - lo + 2]))
        for device in self.mesh.local_devices:
            mem = device.memory_stats() or {}
            for kind, key in (("in_use", "bytes_in_use"),
                              ("peak", "peak_bytes_in_use"),
                              ("limit", "bytes_limit")):
                if key in mem:
                    DEVICE_HBM_BYTES.labels(
                        worker=worker, device=str(device.id),
                        kind=kind).set(mem[key])

    def _publish_spec_metrics(self) -> None:
        """Mirror the scheduler's speculative-decoding totals onto the
        dynamo_spec_* families (docs/metrics.md): counters advance by the
        delta since the last publish; gauges snapshot the EMA and the
        current per-step k."""
        from ..runtime.metrics import (
            SPEC_ACCEPTANCE,
            SPEC_ACCEPTED,
            SPEC_K,
            SPEC_PROPOSED,
        )

        stats = self.scheduler.stats
        worker = f"{self.instance_id:x}"
        prev_p, prev_a = self._spec_published
        if stats.spec_proposed > prev_p:
            SPEC_PROPOSED.labels(worker=worker).inc(
                stats.spec_proposed - prev_p)
        if stats.spec_accepted > prev_a:
            SPEC_ACCEPTED.labels(worker=worker).inc(
                stats.spec_accepted - prev_a)
        self._spec_published = (stats.spec_proposed, stats.spec_accepted)
        SPEC_ACCEPTANCE.labels(worker=worker).set(stats.spec_ema)
        SPEC_K.labels(worker=worker).set(stats.spec_last_k)

    def _publish_steptrace_metrics(self) -> None:
        """Publish the device-time attribution plane (perf/steptrace.py):
        per-step device/host histograms, the steps' wall and its
        measured parts (prep, dispatch, drain_wait, emit) from the samples
        buffered since the last drain, the host-bound verdict, and the live MFU /
        roofline-fraction gauges computed from this interval's work via
        the analytical TimingModel."""
        from ..runtime.metrics import (
            HOST_BOUND,
            MFU_GAUGE,
            ROOFLINE_FRACTION,
            STEP_DEVICE_MS,
            STEP_HOST_MS,
            STEP_PART_MS,
        )

        trace = self.scheduler.steptrace
        worker = f"{self.instance_id:x}"
        parts = {"wall": 0.0, "prep": 0.0, "dispatch": 0.0,
                 "drain_wait": 0.0, "emit": 0.0}
        for sample in trace.drain_samples():
            for phase, ms in sample.device_by_phase.items():
                STEP_DEVICE_MS.labels(phase=phase).observe(ms)
            STEP_HOST_MS.labels(phase=sample.kind).observe(sample.host_ms)
            parts["wall"] += sample.wall_ms
            parts["prep"] += sample.prep_ms
            parts["dispatch"] += sample.dispatch_ms
            parts["drain_wait"] += sample.drain_ms
            parts["emit"] += sample.emit_ms
        for part, ms in parts.items():
            STEP_PART_MS.labels(part=part).inc(ms)
        HOST_BOUND.labels(worker=worker).set(1.0 if trace.host_bound
                                             else 0.0)
        stats = self.scheduler.stats
        cur = (stats.prefill_tokens, stats.decode_tokens,
               getattr(self.runner, "decode_steps", 0),
               trace.device_ms_total)
        prev = self._roof_prev
        self._roof_prev = cur
        if prev is None:
            return
        device_s = (cur[3] - prev[3]) / 1e3
        if device_s <= 0:
            return
        mfu, fraction = self._roofline.observe(
            prefill_tokens=cur[0] - prev[0],
            decode_tokens=cur[1] - prev[1],
            decode_steps=cur[2] - prev[2],
            active_kv_tokens=self.scheduler.active_kv_tokens(),
            device_s=device_s,
        )
        MFU_GAUGE.labels(worker=worker).set(mfu)
        ROOFLINE_FRACTION.labels(worker=worker).set(fraction)

    async def _event_drain(self, publisher, interval: float = 0.05) -> None:
        self._drain_ticks = 0
        self._spec_published = (0, 0)
        while True:
            await asyncio.sleep(interval)
            for event in self.events.drain():
                try:
                    await publisher.publish(KV_EVENT_TOPIC, event.to_wire())
                except Exception:  # noqa: BLE001
                    log.exception("kv event publish failed")
            # load metrics on every 10th drain tick (~0.5s cadence)
            self._drain_ticks += 1
            if self._drain_ticks % 40 == 0:
                try:
                    self.transfers.expire_stale()
                except Exception:  # noqa: BLE001 — drain task must survive
                    log.exception("transfer expiry failed")
                if self.kvbm is not None \
                        and hasattr(self.kvbm, "sweep_pins"):
                    try:
                        # Session pin leases die at TTL even when no new
                        # pin traffic triggers the lazy sweep.
                        self.kvbm.sweep_pins()
                    except Exception:  # noqa: BLE001 — drain survives
                        log.exception("pin sweep failed")
            if self.scheduler is not None and self._drain_ticks % 10 == 0:
                metrics = self._load_metrics()
                KV_USAGE.labels(worker=f"{self.instance_id:x}").set(
                    metrics.kv_usage)
                self._publish_engine_gauges()
                if self.scheduler.spec_enabled:
                    self._publish_spec_metrics()
                try:
                    self._publish_steptrace_metrics()
                except Exception:  # noqa: BLE001 — gauges must not
                    # kill the drain task
                    log.exception("steptrace metrics publish failed")
                try:
                    await publisher.publish(LOAD_TOPIC, metrics.to_wire())
                except Exception:  # noqa: BLE001
                    pass

    # -- request handler ---------------------------------------------------

    async def generate(self, body: dict, ctx=None) -> AsyncIterator[dict]:
        request = PreprocessedRequest.from_wire(body)
        if request.annotations.get("embed"):
            # Embedding request: trunk-only pooled forward, serialized with
            # engine steps (shared device, no KV involvement).
            import numpy as np

            q = self.scheduler.run_in_step(
                lambda: self.runner.embed(
                    np.asarray(request.token_ids, np.int32)))
            vec, exc = await asyncio.get_running_loop().run_in_executor(
                None, q.get)
            if exc is not None:
                yield EngineOutput(finish_reason="error",
                                   error=str(exc)).to_wire()
                return
            yield EngineOutput(
                finish_reason="stop",
                prompt_tokens=len(request.token_ids),
                embedding=[float(x) for x in vec],
            ).to_wire()
            return
        if request.annotations.get("canary"):
            busy = compiling(self.scheduler.thread_ident)
            if busy is not None:
                # Found on the v5e (PR 21): a program outside the warm
                # set compiles for minutes at 7B, on the engine thread,
                # under the first request that needs it. Canaries queued
                # behind it timed out and the health manager
                # deregistered a healthy worker mid-request. A thread
                # inside a compile is slow, not wedged: answer for it.
                log.info("canary answered while the engine compiles "
                         "%s (%.0fs so far)", *busy)
                yield EngineOutput(finish_reason="stop").to_wire()
                return
        # W3C trace context: the wire header (first-class, ctx.traceparent)
        # wins; the annotation side-channel keeps legacy peers working.
        traceparent = None
        if ctx is not None:
            traceparent = getattr(ctx, "traceparent", None)
        traceparent = traceparent or request.annotations.get("traceparent")
        from ..runtime.flight_recorder import get_recorder
        from ..runtime.logging import current_request_id
        from ..runtime.otel import get_tracer, trace_id_of

        current_request_id.set(request.request_id)
        prefill_only = (self.mode == "prefill"
                        or bool(request.annotations.get("prefill_only")))
        tracer = get_tracer()
        # Worker span: child of the router's dispatch span via the carried
        # traceparent (ref: logging.rs propagation across the request plane).
        worker_span = tracer.start_span(
            "worker.generate", parent=traceparent, kind=2,
            **{"request.id": request.request_id, "worker.mode": self.mode,
               "instance.id": f"{self.instance_id:x}",
               "prefill.only": prefill_only})
        recorder = get_recorder()
        # Prefill legs reuse the decode request's id: qualify the record
        # key so both legs keep their own timeline when the pools share a
        # process (comesh). Canary probes never open a timeline.
        rec_id = (f"{request.request_id}#prefill" if prefill_only
                  else request.request_id)
        if not request.annotations.get("canary"):
            # Fall back to the wire traceparent's trace id when local
            # span export is disabled (_NoopSpan.trace_id is "") so
            # /debug/requests timelines still correlate to the client's
            # trace — same contract as the HTTP/kserve frontends.
            recorder.start(rec_id, model=request.model,
                           trace_id=worker_span.trace_id
                           or trace_id_of(traceparent))
        status = "error"
        try:
            loop = asyncio.get_running_loop()
            out_queue: asyncio.Queue = asyncio.Queue()

            post = self.outbox.post

            def emit(output: EngineOutput) -> None:
                post(loop, out_queue, output)

            if request.cache_anchors and self.kvbm is not None \
                    and hasattr(self.kvbm, "pin_blocks"):
                # Session tier: lease the anchored prefix blocks against
                # tier eviction (they always die at TTL) and stage any
                # G3/G4 residents up into G2 so the admission-time
                # onload hits host RAM (docs/prompt-caching.md).
                try:
                    from ..runtime.config import env as _env
                    from ..tokens import compute_block_hashes

                    page = self.scheduler.page_size
                    n = (max(request.cache_anchors) // page) * page
                    pin_hashes = compute_block_hashes(
                        request.token_ids[:n], page,
                        lora_id=request.kv_salt()) if n else []
                    if pin_hashes:
                        # Client-requested lease TTL when carried on the
                        # wire (pin_blocks clamps to the system ceiling).
                        ttl = (request.cache_ttl
                               or _env("DYNT_PIN_TTL_SECS"))
                        self.kvbm.pin_blocks(pin_hashes, ttl)
                        self.kvbm.prefetch(pin_hashes)
                        recorder.event(rec_id, "session_pin",
                                       blocks=len(pin_hashes))
                except Exception:  # noqa: BLE001 — pinning is a cache
                    # hint; a failure degrades to normal eviction order
                    log.exception("session pin failed for %s",
                                  request.request_id)

            submit_kwargs: dict = {}
            if prefill_only:
                submit_kwargs.update(
                    prefill_only=True,
                    on_prefill_done=self._register_transfer,
                )
                if self.disagg_pipeline > 0:
                    # Chunked handoff: stream transfer params + pages per
                    # chunk so the decode side pulls while we compute.
                    submit_kwargs.update(
                        on_prefill_chunk=self._stream_transfer_chunk)
            elif request.disaggregated_params:
                handoff = (request.disaggregated_params or {}).get(
                    "handoff")
                blocks, first_token = await self._pull_remote_kv(
                    request.disaggregated_params,
                    deadline=ctx.deadline if ctx is not None else None,
                    traceparent=worker_span.traceparent or traceparent,
                    record_id=rec_id)
                if handoff is not None:
                    # Drain handoff destination (engine/drain.py): the
                    # bundle covers prompt AND generated pages; resume
                    # state continues the stream bit-identically. A
                    # failed pull CANNOT fall through to plain submit —
                    # that would re-emit the whole stream from scratch
                    # on top of tokens the client already has. Bounce
                    # with a plain migrate instead: the Migration
                    # operator replays prompt+generated (the ladder's
                    # replay rung).
                    if blocks is not None:
                        submit_kwargs.update(
                            onboard_blocks=blocks,
                            resume_state=handoff,
                        )
                    else:
                        log.warning("drain handoff pull failed for %s; "
                                    "bouncing to replay",
                                    request.request_id)
                        yield EngineOutput(
                            finish_reason="migrate",
                            error="drain handoff pull failed; replay",
                        ).to_wire()
                        return
                elif blocks is not None and first_token is not None:
                    submit_kwargs.update(
                        onboard_blocks=blocks,
                        onboard_first_token=first_token,
                    )
                # else: fall through — plain submit recomputes the prefill

            if request.media_embeddings is not None:
                import numpy as np

                me = request.media_embeddings
                try:
                    rows = np.frombuffer(me["data"], np.float32).reshape(
                        tuple(me["shape"]))
                except (KeyError, TypeError, ValueError) as exc:
                    yield EngineOutput(
                        finish_reason="error",
                        error=f"malformed media embeddings: {exc}").to_wire()
                    return
                n_placeholders = sum(
                    1 for t in request.token_ids
                    if t == self.model_config.image_token_id)
                if (rows.ndim != 2
                        or rows.shape[-1] != self.model_config.hidden
                        or rows.shape[0] != n_placeholders):
                    # A row/placeholder mismatch (encoder n_image_tokens vs the
                    # card's) would silently misalign images; fail loudly.
                    yield EngineOutput(
                        finish_reason="error",
                        error=(f"media embeddings {rows.shape} do not match "
                               f"{n_placeholders} placeholder tokens x hidden "
                               f"{self.model_config.hidden} (encoder preset "
                               "mismatch?)")).to_wire()
                    return
                submit_kwargs["media_embeds"] = rows
            elif request.annotations.get("media_urls") or \
                    request.annotations.get("media"):
                yield EngineOutput(
                    finish_reason="error",
                    error="multimodal request reached the worker without "
                          "embeddings (no encoder pool?)").to_wire()
                return
            if request.lora_name:
                # Resolve the slot AFTER every await above: submit() runs in the
                # same event-loop step as this resolution, so lora_in_flight's
                # incoming-queue drain can never miss a resolved-but-unsubmitted
                # sequence (a suspend between resolve and submit would let a
                # concurrent unload free — and a load repurpose — the slot).
                slot = (self.loras.slot_of(request.lora_name)
                        if self.loras is not None else None)
                if slot is None:
                    yield EngineOutput(
                        finish_reason="error",
                        error=f"adapter {request.lora_name!r} not loaded here",
                    ).to_wire()
                    return
                submit_kwargs["lora_idx"] = slot
            recorder.stamp(rec_id, "queued")
            handle = self.scheduler.submit(
                request, emit, record_id=rec_id,
                traceparent=worker_span.traceparent or traceparent,
                **submit_kwargs)
            try:
                saw_error = False
                while True:
                    output: EngineOutput = await out_queue.get()
                    saw_error = saw_error or output.error is not None
                    if (self.coldstart is not None
                            and self.coldstart.total is None
                            and output.error is None
                            and not request.annotations.get("canary")):
                        # First served token closes the cold-start ladder
                        # (idempotent; canary probes don't count).
                        self.coldstart.first_token()
                    if output.finish_reason is not None:
                        status = "error" if saw_error else "ok"
                        yield output.to_wire()
                        return
                    yield output.to_wire()
            finally:
                handle.cancel()
        except asyncio.CancelledError:
            # Watchdog (deadline) cancel or the client went away: both
            # must close the span as not-ok instead of leaking an
            # open-looking success (satellite: span loss on abnormal ends).
            status = "cancelled"
            if ctx is not None and ctx.deadline is not None \
                    and ctx.deadline.expired():
                status = "deadline_exceeded"
            raise
        except GeneratorExit:
            # The request-plane server aclose()s the handler generator
            # when a cancel frame races its _send backpressure wait
            # (request_plane.py cancel handling): an ordinary client
            # cancel, not an error — don't WARNING-dump the timeline.
            # Keep "ok" when the close raced the FINAL yield (the finish
            # frame was already delivered and decided the status).
            if status == "error":
                status = "cancelled"
            raise
        finally:
            # One exit for every path (early error yields, exceptions,
            # cancellation, clean finish): close the timeline, synthesize
            # phase spans from it, then export the worker span. finish()
            # returns None when another component (shared-process
            # frontend) closed it first — fall back to a lookup.
            timeline = (recorder.finish(rec_id, status)
                        or recorder.get(rec_id))
            if (timeline is not None
                    and not request.annotations.get("canary")):
                # Device-time TTFT (docs/observability.md): the prefill
                # device-stream window behind this request's first
                # token, exemplar-linked to its trace.
                dev_ms = (timeline.device or {}).get("prefill_device_ms")
                if dev_ms:
                    from ..runtime.metrics import TTFT_DEVICE_MS

                    TTFT_DEVICE_MS.labels(model=request.model).observe(
                        dev_ms,
                        exemplar={"trace_id": timeline.trace_id}
                        if timeline.trace_id else None)
                observe_stages(timeline, request, prefill_only)
            self._record_phase_trace(tracer, worker_span, timeline,
                                     prefill_only)
            worker_span.end(ok=status == "ok")

    def _record_phase_trace(self, tracer, worker_span, timeline,
                            prefill_only: bool = False) -> None:
        """Attach the flight-recorder phases to the worker span as span
        events and synthesize explicit-timestamp child spans for the
        queue-wait / prefill / decode segments — the per-phase breakdown
        the trace needs without holding live spans across the scheduler
        thread."""
        if not tracer.enabled:
            return
        parent = worker_span.traceparent
        if timeline is None or not parent:
            return
        phases = timeline.phases
        for phase, ts in sorted(phases.items(), key=lambda kv: kv[1]):
            worker_span.add_event(phase, ts=ts)

        def _ns(key: str) -> int:
            return int(phases[key] * 1e9)

        if "queued" in phases and "scheduled" in phases:
            tracer.record_span("scheduler.queue", parent,
                               _ns("queued"), _ns("scheduled"))
        segments = []
        if "prefill_start" in phases and "first_token" in phases:
            segments.append(("worker.prefill", "prefill",
                             _ns("prefill_start"), _ns("first_token")))
        if "first_token" in phases and "finished" in phases \
                and not prefill_only:
            # Prefill-only legs never decode: first_token..finished there
            # is transfer-table handoff, not a decode segment.
            segments.append(("worker.decode", "decode",
                             _ns("first_token"), _ns("finished")))
        device = timeline.device or {}
        for span_name, phase, start_ns, end_ns in segments:
            seg_parent = tracer.record_span(span_name, parent,
                                            start_ns, end_ns)
            # Device slice of the phase (perf/steptrace.py attribution):
            # the device-stream window abuts the segment end (the drain
            # materialized the tokens that closed it), so the child span
            # is laid back from there; the host share is the remainder.
            dev_ms = device.get(f"{phase}_device_ms", 0.0)
            if not seg_parent or dev_ms <= 0:
                continue
            dev_ns = int(dev_ms * 1e6)
            seg_ns = max(0, end_ns - start_ns)
            dev_ns = min(dev_ns, seg_ns)
            tracer.record_span(
                "worker.device_execute", seg_parent,
                end_ns - dev_ns, end_ns,
                **{"phase": phase, "device_ms": round(dev_ms, 3),
                   "host_ms": round(max(0.0, seg_ns / 1e6 - dev_ms),
                                    3)})

    async def close(self) -> None:
        if self._publish_task is not None and not self._publish_task.done():
            # Let an in-flight weight publish finish (bounded) — cancelling
            # it would leave no arena for the next restart to attach.
            try:
                await asyncio.wait_for(
                    asyncio.shield(self._publish_task), 30.0)
            except Exception:  # noqa: BLE001 — only TimeoutError is
                # reachable; _publish logs its own failures
                pass
        for task in self._tasks:
            task.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
        # Endpoints drain BEFORE the scheduler stops — in-flight generate/
        # scale requests need a live scheduler loop to ever finish.
        for served in (self._served, self._clear_served, self._pull_served,
                       self._scale_served, self._kvq_served,
                       self._weights_served, self._drain_served,
                       *self._lora_served):
            if served is not None:
                await served.shutdown()
        if self.kvbm is not None:
            # Drain pending offload gathers while the scheduler thread can
            # still service run_in_step, then stop both.
            await asyncio.to_thread(self.kvbm.flush, 5.0)
        if self.scheduler is not None:
            self.scheduler.stop()
        if self.kvbm is not None:
            self.kvbm.close()
        for router in self._pull_clients.values():
            await router.client.close()
        if self._step_channel is not None:
            # Release the followers AFTER the scheduler stops (no more
            # mirrored launches can be in flight).
            self._step_channel.close()


def build_arg_parser():
    """Worker CLI (separate from main so tests can probe env-derived
    defaults like DYNT_KV_BLOCK_SIZE without starting a worker)."""
    import argparse

    from ..runtime.config import env

    parser = argparse.ArgumentParser("dynamo_tpu.worker")
    parser.add_argument("--model", default="tiny-test",
                        help="model preset (models/config.py PRESETS)")
    parser.add_argument("--model-path", default=None,
                        help="HF checkpoint directory (config.json + "
                             "*.safetensors [+ tokenizer.json]); overrides "
                             "--model — the architecture comes from the "
                             "checkpoint's config.json")
    parser.add_argument("--model-ref", default=None,
                        help="resolve the model from a registered "
                             "ModelRecord (deploy/registry.py, the "
                             "DynamoModel CRD analog) instead of "
                             "--model/--model-path; the record's source + "
                             "served name win")
    parser.add_argument("--served-model-name", default=None)
    parser.add_argument("--namespace", default="dynamo")
    parser.add_argument("--component", default="backend")
    parser.add_argument("--page-size", type=int,
                        default=env("DYNT_KV_BLOCK_SIZE"))
    parser.add_argument("--num-pages", type=int, default=2048)
    parser.add_argument("--max-batch", type=int, default=16)
    parser.add_argument("--max-pages-per-seq", type=int, default=128)
    parser.add_argument("--window-pages", type=int, default=0,
                        help="a model with window AND full attention "
                             "layers: pages of its second page group, "
                             "which holds the window layers only "
                             "(--num-pages stays the full group's); a "
                             "decoding row holds window/page-size + 2 of "
                             "them, a row in a prefill chunk the chunk's "
                             "more (docs/prompt-caching.md)")
    parser.add_argument("--prefill-buckets", default=None,
                        metavar="N,N,..",
                        help="prefill chunk lengths to compile for "
                             "(default 32..2048 by powers of two); the "
                             "largest is the token budget of a launch")
    parser.add_argument("--kv-dtype", default="model",
                        choices=["model", "int8"],
                        help="KV cache storage: model dtype (bf16) or "
                             "int8 (half the decode KV traffic, double "
                             "the KV capacity; composes with KVBM and "
                             "same-geometry disagg via packed uint8 "
                             "transfer blocks)")
    parser.add_argument("--weight-dtype", default="model",
                        choices=["model", "int8", "int4"],
                        help="Weight storage: model dtype (bf16), "
                             "weight-only int8 (W8A16 Pallas matmuls — "
                             "halves decode weight streaming), or packed "
                             "int4 (W4A16, per-group scale/zero — "
                             "quarters it; dense llama/mistral/qwen "
                             "family, tp=1)")
    parser.add_argument("--serve-layers", type=int, default=None,
                        help="serve only the leading N layers of the "
                             "preset: this worker is one stage of a "
                             "pipeline")
    parser.add_argument("--experts-held", default=None, metavar="LO:HI",
                        help="the routed experts this chip holds of the "
                             "published count (an expert-parallel share): "
                             "the router keeps its width and its top-k, a "
                             "token routed to an absent expert gets "
                             "nothing from it")
    parser.add_argument("--vocab-rows", type=int, default=None,
                        help="hold only the leading N rows of the "
                             "vocabulary: embedding, head, logits and "
                             "sampling are over the slice")
    parser.add_argument("--prewarm", default=None,
                        choices=("off", "buckets", "full"),
                        help="what to compile before serving (default: "
                             "DYNT_PREWARM decides between off and "
                             "buckets). off: decode and the smallest "
                             "prefill; buckets: every single-row prefill "
                             "bucket too; full: also every batched prefill "
                             "(rows x bucket) and fused decode block "
                             "(table width) the scheduler can launch, "
                             "from the runner's own buckets and budget")
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--dp", type=int, default=1)
    parser.add_argument("--sp", type=int, default=1)
    parser.add_argument("--replicas", type=int, default=1,
                        help="serve N independent replicas of the model "
                             "from this process, replica i on local chips "
                             "[i*k, (i+1)*k) with k = dp*tp*sp. A chip "
                             "belongs to the process that opened it, so "
                             "one process per host places a worker on "
                             "each chip; every replica registers as its "
                             "own instance and routers see N workers")
    parser.add_argument("--multihost", default=None, metavar="R/N@HOST:PORT",
                        help="span this worker across N host processes via "
                             "jax.distributed (one global mesh). Rank 0 is "
                             "the driver (serves endpoints); ranks 1..N-1 "
                             "are engine-only followers replaying the "
                             "driver's steps (ref: vLLM headless multi-node "
                             "mode, components/src/dynamo/vllm/main.py:79)")
    parser.add_argument("--mode", default="aggregated",
                        choices=["aggregated", "prefill", "decode", "comesh"],
                        help="disaggregated role (prefill workers register "
                             "ModelType prefill under their own component); "
                             "comesh runs a prefill pool AND a decode pool "
                             "on disjoint sub-meshes of the local chips "
                             "with direct ICI KV handoff")
    parser.add_argument("--prefill-devices", type=int, default=1,
                        help="comesh: chips for the prefill sub-mesh")
    parser.add_argument("--decode-devices", type=int, default=1,
                        help="comesh: chips for the decode sub-mesh")
    parser.add_argument("--kvbm-host-blocks", type=int, default=0,
                        help="G2 host-RAM KV tier size in blocks (0=off)")
    parser.add_argument("--kvbm-disk-blocks", type=int, default=0,
                        help="G3 local-SSD KV tier size in blocks (0=off)")
    parser.add_argument("--kvbm-disk-path", default="/tmp/dynamo_tpu_kvbm.bin")
    parser.add_argument("--kvbm-object-store", default=None,
                        help="G4 blob-store root (e.g. a gcsfuse mountpoint)")
    parser.add_argument("--weight-service", default=None,
                        help="unix socket of the weight service (GMS "
                             "analog; default DYNT_WEIGHT_SERVICE)")
    parser.add_argument("--weights-from-peer", action="store_true",
                        help="pull weights from a live replica at startup "
                             "(ModelExpress analog)")
    parser.add_argument("--max-loras", type=int, default=0,
                        help="adapter slots for multi-LoRA serving (0=off)")
    parser.add_argument("--lora-rank", type=int, default=8,
                        help="shared slot rank (adapters with lower rank "
                             "are zero-padded)")
    parser.add_argument("--lora", action="append", default=[],
                        metavar="NAME=PATH",
                        help="adapter to load at startup (repeatable)")
    parser.add_argument("--tool-call-parser", default=None,
                        choices=["hermes", "qwen", "mistral", "llama3_json",
                                 "pythonic", "xml", "dsml", "harmony"])
    parser.add_argument("--reasoning-parser", default=None,
                        choices=["think", "deepseek-r1", "granite",
                                 "harmony", "gpt-oss"])
    return parser


def _exit_if_engine_died(workers: list) -> None:
    """After teardown: an engine loop that ended on an exception failed
    its requests in-band; the process must not read as a clean exit."""
    dead = [w for w in workers if w.engine_failure is not None]
    if dead:
        raise SystemExit(
            "engine thread died: "
            + "; ".join(repr(w.engine_failure) for w in dead))


async def main(argv: Optional[list[str]] = None) -> None:
    from ..runtime import RuntimeConfig
    from ..runtime.config import env
    from ..runtime.signals import wait_for_shutdown_signal

    args = build_arg_parser().parse_args(argv)

    component = args.component
    if args.mode == "prefill" and component == "backend":
        component = "prefill"
    if args.kv_dtype == "int8" and args.mode != "aggregated":
        # KVBM tiers compose with int8 KV (packed uint8 blocks, r5), but
        # the DISAGG transfer planes (ICI bridge + DCN wire descriptors)
        # still move model-dtype bundles; a quantized pool would fail or
        # recompute every handoff.
        raise SystemExit("--kv-dtype int8 supports aggregated serving "
                         "(incl. KVBM tiers); disaggregated prefill/"
                         "decode pools still require kv-dtype=model")
    model_config = None
    cut = (args.serve_layers, args.experts_held, args.vocab_rows)
    has_cut = any(c is not None for c in cut)
    if has_cut and (args.model_path or args.model_ref or args.multihost
                    or args.mode == "comesh"):
        raise SystemExit("--serve-layers/--experts-held/--vocab-rows cut a "
                         "preset (--model) on one aggregated worker; they "
                         "do not combine with --model-path, --model-ref, "
                         "--multihost or --mode comesh")
    if not (args.model_path or args.model_ref):
        from ..models.config import cut_config

        try:
            preset = cut_config(get_config(args.model), *cut)
            # before any process or connection is made: what this model
            # cannot be served with, by flag
            recurrent_state_refusals(
                preset, mode=args.mode,
                kvbm=args.kvbm_host_blocks > 0 or args.kvbm_disk_blocks > 0,
                spec=bool(env("DYNT_SPEC_ENABLE")),
                weight_dtype=args.weight_dtype, kv_dtype=args.kv_dtype,
                devices=args.tp * args.dp * args.sp)
        except (KeyError, ValueError) as exc:
            raise SystemExit(f"dynamo_tpu.worker: {exc}")
        if has_cut:
            model_config = preset
    kvbm_config = None
    if args.kvbm_host_blocks > 0:
        from ..block_manager import KvbmConfig

        kvbm_config = KvbmConfig(
            host_blocks=args.kvbm_host_blocks,
            disk_blocks=args.kvbm_disk_blocks,
            disk_path=args.kvbm_disk_path,
            object_store_root=args.kvbm_object_store,
        )
    from ..runtime.config import env as _env
    from ..runtime.snapshot import SnapshotController

    multihost_cfg = None
    step_channel = None
    if args.multihost:
        from ..parallel import multihost as mh

        if args.mode == "comesh":
            raise SystemExit("--multihost does not combine with --mode "
                             "comesh (cross-host disagg pools use separate "
                             "multihost workers + host-relay KV transfer)")
        multihost_cfg = mh.MultihostConfig.parse(args.multihost)
        mh.initialize(multihost_cfg)
        rc = _runner_config(args)
        if not multihost_cfg.is_driver:
            # Follower: engine only — no runtime, no endpoints. Build a
            # runner IDENTICAL to the driver's and replay its steps.
            if args.model_path:
                from ..models.checkpoint import (
                    config_from_checkpoint,
                    load_params,
                )

                model_config = config_from_checkpoint(args.model_path)
                host_params = load_params(args.model_path, model_config)
            else:
                model_config = get_config(args.model)
                host_params = None
            mesh = make_mesh(MeshConfig(dp=args.dp, tp=args.tp, sp=args.sp))
            runner = ModelRunner(model_config, rc, mesh, host_params,
                                 seed=0)
            if args.kvbm_host_blocks > 0:
                # Distributed KVBM worker half: this rank stores/loads
                # its local KV shards when the driver mirrors
                # kvbm_store_shards / kvbm_load_shards.
                from ..block_manager.distributed import KvbmShardWorker

                runner.kvbm_worker = KvbmShardWorker(args.kvbm_host_blocks)
            await asyncio.to_thread(mh.follower_serve, runner, multihost_cfg)
            return
        host, port = multihost_cfg.plan_host_port
        step_channel = mh.StepChannel(
            host if host in ("127.0.0.1", "localhost") else "0.0.0.0",
            port, multihost_cfg.num_processes - 1)
        log.info("waiting for %d followers on the step channel...",
                 multihost_cfg.num_processes - 1)
        await asyncio.to_thread(step_channel.wait_for_followers)

    snapshot = SnapshotController()
    if snapshot.enabled and multihost_cfg is not None:
        raise SystemExit("snapshot-gated startup does not combine with "
                         "--multihost")
    # Snapshot protocol: the engine is prepared BEFORE any runtime
    # connection (no open sockets at the dump point); normal mode connects
    # first so the worker registers as soon as it's ready.
    runtime = None
    if not snapshot.enabled:
        runtime = await DistributedRuntime(RuntimeConfig.from_env()).start()

    if args.model_ref:
        # DynamoModel-analog resolution: the registry record decides the
        # source and served name (ref: dynamomodel_types.go).
        if runtime is None:
            raise SystemExit("--model-ref needs the discovery plane; it "
                             "does not combine with snapshot-gated "
                             "startup (resolve before dumping instead)")
        import os

        from ..deploy.registry import resolve_model_ref

        record = await resolve_model_ref(runtime, args.model_ref,
                                         args.namespace)
        if os.path.isdir(record.source):
            args.model_path = record.source
        else:
            # The record's source WINS over any --model-path on the
            # command line (model_path would otherwise override --model
            # downstream and silently serve the wrong checkpoint).
            args.model = record.source
            args.model_path = None
        if args.served_model_name is None:
            args.served_model_name = record.served_model_name
        log.info("model ref %r -> source=%s served=%s", args.model_ref,
                 record.source, record.served_model_name)

    if args.replicas > 1 and (args.mode == "comesh" or args.multihost
                              or snapshot.enabled):
        raise SystemExit("--replicas places whole workers on this host's "
                         "chips; it does not combine with --mode comesh, "
                         "--multihost or snapshot-gated startup")
    if args.mode == "comesh":
        # Co-meshed disagg: one process, prefill + decode pools on disjoint
        # sub-meshes, KV handoff over ICI (engine/ici_transfer.py). The
        # frontend orchestrates exactly as with remote disagg — the bridge
        # token in kv_transfer_params selects the fast path.
        from ..runtime import HealthCheckManager
        from .ici_transfer import IciKvBridge, split_mesh

        if snapshot.enabled:
            raise SystemExit(
                "--mode comesh does not support snapshot-gated startup "
                "(two engines, one dump point); unset DYNT_SNAPSHOT_MODE")
        # --tp > 1 sets in-pool tensor parallelism for BOTH pools; the
        # default is full-tp within each pool's devices. --dp has no
        # meaning here (the pools ARE the device split).
        if args.dp != 1:
            raise SystemExit("--dp is not meaningful with --mode comesh; "
                             "size the pools with --prefill-devices/"
                             "--decode-devices")
        pre_mesh, dec_mesh = split_mesh(
            args.prefill_devices, args.decode_devices,
            prefill_tp=args.tp if args.tp > 1 else None,
            decode_tp=args.tp if args.tp > 1 else None)
        bridge = IciKvBridge()
        rc = _runner_config(args)
        common = dict(
            model_name=args.model, model_path=args.model_path,
            served_name=args.served_model_name,
            namespace=args.namespace, runner_config=rc,
            tool_parser=args.tool_call_parser,
            reasoning_parser=args.reasoning_parser,
            lora_adapters=dict(s.split("=", 1) for s in args.lora),
            weight_service=(args.weight_service
                            or _env("DYNT_WEIGHT_SERVICE") or None),
            weights_from_peer=args.weights_from_peer,
            ici_bridge=bridge,
        )
        prefill_worker = TpuWorker(runtime, mode="prefill",
                                   component="prefill", mesh=pre_mesh,
                                   **common)
        decode_worker = TpuWorker(runtime, mode="decode",
                                  component=args.component, mesh=dec_mesh,
                                  kvbm_config=kvbm_config, **common)
        await prefill_worker.start()
        await decode_worker.start()
        # POST /drain and SIGTERM both vacate BOTH workers through this
        # one ladder, in order: decode first (live client streams hand
        # off / replay), then prefill (its transfers are being pulled
        # by decode peers) — and ONE DYNT_DRAIN_DEADLINE_SECS budget
        # spans the pair: granting each worker the full deadline would
        # take 2x worst-case and overrun the ~30s eviction notice the
        # knob is sized to fit inside. Per-worker auto-registrations on
        # the shared status server are last-wins; this composed drainer
        # replaces them.
        async def _drain_both(reason: str = "control") -> dict:
            budget = float(env("DYNT_DRAIN_DEADLINE_SECS"))
            t0 = time.monotonic()
            report: dict = {}
            for label, w in (("decode", decode_worker),
                             ("prefill", prefill_worker)):
                try:
                    report[label] = await w.drain(
                        reason, deadline_secs=max(
                            1.0, budget - (time.monotonic() - t0)))
                except Exception:  # noqa: BLE001 — one worker's failed
                    # drain must not skip the other's (or teardown)
                    log.exception("graceful drain failed (%s)", label)
                    report[label] = {"error": "drain failed; see log"}
            return report

        if getattr(runtime, "status_server", None) is not None:
            runtime.status_server.register_drain(_drain_both)
        health = HealthCheckManager(
            runtime, canary_wait_time=_env("DYNT_CANARY_WAIT_SECS"))
        health.start()
        try:
            await wait_for_shutdown_signal()
        finally:
            # Departure ladder BEFORE teardown (docs/fault-tolerance.md):
            # the same composed drainer POST /drain uses — decode then
            # prefill under one shared deadline; it swallows per-worker
            # failures so teardown always proceeds.
            await _drain_both("shutdown-signal")
            await health.close()
            await decode_worker.close()
            await prefill_worker.close()
            await runtime.shutdown()
        _exit_if_engine_died([decode_worker, prefill_worker])
        return

    mesh_config = MeshConfig(dp=args.dp, tp=args.tp, sp=args.sp)
    def build_worker(mesh) -> TpuWorker:
        return TpuWorker(
            runtime,
            model_name=args.model,
            model_path=args.model_path,
            served_name=args.served_model_name,
            namespace=args.namespace,
            component=component,
            mode=args.mode,
            runner_config=_runner_config(args),
            mesh_config=mesh_config,
            mesh=mesh,
            kvbm_config=kvbm_config,
            step_channel=step_channel,
            tool_parser=args.tool_call_parser,
            reasoning_parser=args.reasoning_parser,
            lora_adapters=dict(s.split("=", 1) for s in args.lora),
            weight_service=(args.weight_service
                            or _env("DYNT_WEIGHT_SERVICE") or None),
            weights_from_peer=args.weights_from_peer,
            model_config=model_config,
            prewarm=args.prewarm,
        )

    # Replica i takes local chips [i*k, (i+1)*k); one replica is the
    # front of the list, as make_mesh always placed it.
    import jax

    k = mesh_config.num_devices
    devices = jax.devices()
    if len(devices) < args.replicas * k:
        raise SystemExit(
            f"--replicas {args.replicas} x {k} chip(s) needs "
            f"{args.replicas * k} devices, have {len(devices)}")
    workers = [build_worker(make_mesh(
        mesh_config, devices=devices[i * k:(i + 1) * k]))
        for i in range(args.replicas)]
    worker = workers[0]
    if snapshot.enabled:
        await worker.prepare()
        snapshot.engine_ready()
        await snapshot.wait_for_restore()
        worker.rederive_identity()  # clones must not share an instance id
        runtime = await DistributedRuntime(RuntimeConfig.from_env()).start()
        worker.runtime = runtime
        await worker.serve()
        # A restore proves the snapshot is viable: record it as a
        # DynamoCheckpoint analog so deploy tooling can prefer
        # snapshot-restore cold starts (ref: dynamocheckpoint_types.go).
        try:
            from ..deploy.registry import (
                CheckpointRecord,
                register_checkpoint,
            )

            digest = ""
            if args.model_path:
                from ..models.checkpoint import checkpoint_digest

                # Strided reads over every shard: off the event loop —
                # the worker is already serving at this point.
                digest = await asyncio.to_thread(checkpoint_digest,
                                                 args.model_path)
            # Identity: prefer the explicit ref, else the checkpoint
            # directory basename — plain args.model defaults to
            # "tiny-test" under --model-path and would collide every
            # model-path snapshot worker on one registry key.
            import os

            ident = (args.model_ref
                     or (os.path.basename(args.model_path.rstrip("/"))
                         if args.model_path else args.model))
            await register_checkpoint(runtime, CheckpointRecord(
                name=f"{ident}-snapshot",
                model=args.model_ref or args.model_path or args.model,
                snapshot_dir=snapshot.directory,
                namespace=args.namespace,
                weights_digest=digest,
            ))
        except Exception:  # noqa: BLE001 — registry is advisory; serving
            # must not depend on it
            log.exception("checkpoint record registration failed")
    else:
        # Replicas build side by side: XLA compiles off the GIL, so N
        # engines warm in about the time of one.
        await asyncio.gather(*(w.start() for w in workers))

    async def drain_all(reason: str = "control") -> dict:
        # Replicas are independent, so their ladders run side by side
        # under the one deadline; a failed drain must not skip the rest.
        reports = await asyncio.gather(
            *(w.drain(reason) for w in workers), return_exceptions=True)
        out = {}
        for w, report in zip(workers, reports):
            if isinstance(report, BaseException):
                log.exception("graceful drain failed (instance=%x)",
                              w.instance_id, exc_info=report)
                report = {"error": "drain failed; see log"}
            out[f"{w.instance_id:x}"] = report
        return out

    if len(workers) > 1 and getattr(runtime, "status_server",
                                    None) is not None:
        # Per-worker registrations are last-wins; POST /drain must
        # vacate every replica.
        runtime.status_server.register_drain(drain_all)
    from ..runtime import HealthCheckManager

    health = HealthCheckManager(runtime,
                                canary_wait_time=env("DYNT_CANARY_WAIT_SECS"))
    health.start()
    try:
        await wait_for_shutdown_signal()
    finally:
        # Departure ladder BEFORE teardown: in-flight streams hand off
        # their KV state to peers (or replay) instead of dying with the
        # endpoints (docs/fault-tolerance.md).
        await drain_all("shutdown-signal")
        await health.close()
        for w in workers:
            await w.close()
        await runtime.shutdown()
    _exit_if_engine_died(workers)
