"""Mocker worker: registers a simulated engine into the runtime.

Equivalent of `python -m dynamo.mocker` (ref: components/src/dynamo/mocker/
main.py wrapping lib/mocker create_engine): create runtime -> serve
`generate` -> publish ModelDeploymentCard -> stream KV events + load metrics.
"""

from __future__ import annotations

import asyncio
import time
from typing import Optional

from ..llm.model_card import CHAT, COMPLETIONS, PREFILL, ModelDeploymentCard, publish_card
from ..runtime import DistributedRuntime, RuntimeConfig, new_instance_id
from ..runtime.config import env
from ..runtime.logging import get_logger
from ..runtime.signals import wait_for_shutdown_signal
from .engine import MockerConfig, MockerEngine

log = get_logger("mocker.worker")


def _canary_request() -> dict:
    """Synthetic single-token request recognized by the engine as cheap
    (ref: health_check.rs HealthCheckTarget payload)."""
    from ..llm.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    return PreprocessedRequest(
        request_id="_canary",
        token_ids=[0],
        sampling=SamplingOptions(max_tokens=1, temperature=0.0),
        stop=StopConditions(),
        annotations={"canary": True},
    ).to_wire()


class MockerWorker:
    def __init__(
        self,
        runtime: DistributedRuntime,
        model_name: str = "mock-model",
        namespace: str = "dynamo",
        component: str = "mocker",
        config: Optional[MockerConfig] = None,
        load_publish_interval: float = 1.0,
        mode: str = "aggregated",  # aggregated | prefill
        tool_parser: Optional[str] = None,
        reasoning_parser: Optional[str] = None,
    ) -> None:
        self.runtime = runtime
        self.instance_id = new_instance_id()
        self.config = config or MockerConfig()
        model_types = [PREFILL] if mode == "prefill" else [CHAT, COMPLETIONS]
        self.card = ModelDeploymentCard(
            name=model_name,
            model_types=model_types,
            namespace=namespace,
            component=component,
            endpoint="generate",
            kv_block_size=self.config.block_size,
            total_kv_blocks=self.config.num_blocks,
            tokenizer={"kind": "byte"},
            tool_parser=tool_parser,
            reasoning_parser=reasoning_parser,
        )
        self.card.runtime_config["kv_blocks_endpoint"] = True
        self.engine: Optional[MockerEngine] = None
        self._load_task: Optional[asyncio.Task] = None
        self._load_interval = load_publish_interval
        self._served = None
        self._kvq_served = None
        self._clear_served = None
        # Graceful drain plane (engine/drain.py simulated chip-free):
        # one ladder run per process; repeats join it.
        self._drain_task: Optional[asyncio.Task] = None
        self._publisher = None
        # Cold-start ladder (engine/coldstart.py): walked with modeled
        # latencies when config.coldstart, closed by the first
        # non-canary token — the chip-free twin of TpuWorker's ladder.
        self.coldstart = None

    async def _walk_coldstart(self) -> None:
        from ..engine.coldstart import ColdStartLadder
        from .engine import coldstart_phases

        self.coldstart = ColdStartLadder(
            f"{self.instance_id:x}",
            source=("peer_striped" if self.config.fetch_striped
                    else "object_store"),
            started=time.monotonic())  # modeled: this process is no arrival
        phases = coldstart_phases(self.config)
        scale = max(self.config.speedup_ratio, 1e-9)
        for name in ("boot", "fetch", "load", "compile", "register"):
            secs = phases[name] / scale
            await asyncio.sleep(secs)
            self.coldstart.mark(name, secs)

    async def start(self) -> None:
        if self.config.coldstart:
            await self._walk_coldstart()
        publisher = self.runtime.event_publisher(self.card.namespace)
        self._publisher = publisher
        self.engine = MockerEngine(self.config, worker_id=self.instance_id,
                                   event_publisher=publisher)
        if getattr(self.runtime, "status_server", None) is not None:
            self.runtime.status_server.register_drain(self.drain)
        # Startup stamp: dynamo_drain_state=0 (serving) — same contract
        # as TpuWorker (docs/metrics.md; engine/drain.py).
        from ..engine.drain import SERVING, set_drain_state

        set_drain_state(self.instance_id, SERVING)
        if hasattr(publisher, "set_snapshot_fn"):
            # Durable journal plane: rotation snapshots (see engine worker)
            from ..kv_router.protocols import KV_SNAPSHOT_TOPIC

            publisher.set_snapshot_fn(
                lambda: [(KV_SNAPSHOT_TOPIC,
                          self.engine.local_index.dump())])
        endpoint = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint("generate")
        )
        engine_generate = self.engine.generate

        async def generate(body, ctx=None):
            async for frame in engine_generate(body, ctx):
                if (self.coldstart is not None
                        and self.coldstart.total is None
                        and not (body.get("annotations") or {}).get(
                            "canary")):
                    # First served token closes the cold-start ladder
                    # (same contract as TpuWorker.generate).
                    self.coldstart.first_token()
                yield frame

        self._served = await endpoint.serve_endpoint(
            generate, instance_id=self.instance_id,
            health_check_payload=_canary_request(),
        )

        async def kv_blocks(body, ctx=None):
            yield self.engine.local_index.dump()

        kvq_ep = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint("kv_blocks")
        )
        self._kvq_served = await kvq_ep.serve_endpoint(
            kv_blocks, instance_id=self.instance_id)

        async def clear_kv(body, ctx=None):
            yield {"cleared": await self.engine.clear_prefix_cache()}

        clear_ep = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint("clear_kv_blocks")
        )
        self._clear_served = await clear_ep.serve_endpoint(
            clear_kv, instance_id=self.instance_id)
        await publish_card(self.runtime, self.card, self.instance_id)
        self._load_task = asyncio.create_task(self._load_loop())
        log.info("mocker worker up: model=%s instance=%x blocks=%d",
                 self.card.name, self.instance_id, self.config.num_blocks)

    async def _load_loop(self) -> None:
        while True:
            await asyncio.sleep(self._load_interval)
            try:
                await self.engine.publish_load()
            except Exception:  # noqa: BLE001
                log.exception("load publish failed")

    # -- graceful drain (the chip-free departure ladder; mirrors
    # TpuWorker.drain / engine/drain.py) ----------------------------------

    async def drain(self, reason: str = "signal") -> dict:
        """Run (or join) the departure ladder: announce draining on
        discovery + the load plane, hand off / replay live streams,
        then (deadline rung) error whatever remains. Idempotent —
        double SIGTERM and a racing POST /drain share one run."""
        if not env("DYNT_DRAIN_ENABLE"):
            return {"skipped": True, "reason": "DYNT_DRAIN_ENABLE=0"}
        if self._drain_task is None:
            self._drain_task = asyncio.create_task(self._run_drain(reason))
        return await asyncio.shield(self._drain_task)

    async def _run_drain(self, reason: str) -> dict:
        from ..engine.drain import DRAINED, DRAINING, set_drain_state

        start = time.monotonic()
        deadline = start + max(0.1, float(env("DYNT_DRAIN_DEADLINE_SECS")))
        set_drain_state(self.instance_id, DRAINING)
        self.card.runtime_config["draining"] = True
        try:
            await publish_card(self.runtime, self.card, self.instance_id)
        except Exception:  # noqa: BLE001 — the load flip still lands
            log.exception("draining card republish failed")
        self.engine.draining = True
        try:
            # Immediate LoadMetrics flip (draining=True) — waiting for
            # the next load tick would leave routers selecting us.
            await self.engine.publish_load()
        except Exception:  # noqa: BLE001
            log.exception("draining load publish failed")
        # One event tick for routers to apply the flip before migrate
        # frames re-dispatch (same settle as engine/drain.py).
        settle = min(float(env("DYNT_DRAIN_ANNOUNCE_SETTLE_SECS")),
                     max(0.0, deadline - time.monotonic() - 0.05))
        if settle > 0:
            await asyncio.sleep(settle)
        report = self.engine.drain_sweep(
            handoff=bool(env("DYNT_DRAIN_HANDOFF")))
        errored = 0
        while time.monotonic() < deadline:
            if not (self.engine._running or self.engine._waiting
                    or self.engine._parked):
                break
            await asyncio.sleep(0.02)
        else:
            errored = self.engine.drain_expire(
                "worker drain deadline exceeded")
        duration_ms = (time.monotonic() - start) * 1e3
        report = {**report, "reason": reason, "errored": errored,
                  "bounced": self.engine.drain_bounced,
                  "completed": errored == 0,
                  "duration_ms": round(duration_ms, 3)}
        log.info("mocker drain complete in %.0fms: %d handoff, %d "
                 "replay, %d errored", duration_ms,
                 len(report["handoff"]), len(report["replay"]), errored)
        set_drain_state(self.instance_id, DRAINED)
        return report

    async def close(self) -> None:
        if self._load_task is not None:
            self._load_task.cancel()
            try:
                await self._load_task
            except asyncio.CancelledError:
                pass
        if self.engine is not None:
            await self.engine.close()
        for served in (self._served, self._kvq_served,
                       self._clear_served):
            if served is not None:
                await served.shutdown()


async def main(argv: Optional[list[str]] = None) -> None:
    import argparse

    parser = argparse.ArgumentParser("dynamo_tpu.mocker")
    parser.add_argument("--model-name", default="mock-model")
    parser.add_argument("--namespace", default="dynamo")
    parser.add_argument("--component", default="mocker")
    parser.add_argument("--block-size", type=int, default=16)
    parser.add_argument("--num-blocks", type=int, default=1024)
    parser.add_argument("--max-batch", type=int, default=32)
    parser.add_argument("--speedup-ratio", type=float, default=1.0)
    parser.add_argument("--timing-preset", default=None,
                        help="measured-silicon step-time coefficients "
                             "(engine.TIMING_PRESETS, e.g. "
                             "tpu-v5e-qwen3-0.6b); overrides the generic "
                             "defaults so planner/SLA validation runs "
                             "against real step-time physics")
    parser.add_argument("--mode", default="aggregated",
                        choices=["aggregated", "prefill"])
    parser.add_argument("--echo", action="store_true",
                        help="generated tokens replay the prompt (parser/"
                             "protocol E2E testing)")
    parser.add_argument("--coldstart", action="store_true",
                        help="walk the modeled arrival ladder (fetch/load/"
                             "compile/register sleeps + dynamo_coldstart_* "
                             "stamps) before serving — chip-free fast-start "
                             "scenarios (docs/elasticity.md)")
    parser.add_argument("--tool-call-parser", default=None)
    parser.add_argument("--reasoning-parser", default=None)
    args = parser.parse_args(argv)

    component = args.component
    if args.mode == "prefill" and component == "mocker":
        component = "prefill"
    common_cfg = dict(
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        max_batch=args.max_batch,
        speedup_ratio=args.speedup_ratio,
        echo=args.echo,
    )
    if args.coldstart:
        # Only override when asked: a bare flag default of False must
        # not mask a preset that enables the cold-start walk.
        common_cfg["coldstart"] = True
    runtime = await DistributedRuntime(RuntimeConfig.from_env()).start()
    worker = MockerWorker(
        runtime,
        model_name=args.model_name,
        namespace=args.namespace,
        component=component,
        mode=args.mode,
        config=(MockerConfig.from_timing_preset(args.timing_preset,
                                                **common_cfg)
                if args.timing_preset else MockerConfig(**common_cfg)),
        tool_parser=args.tool_call_parser,
        reasoning_parser=args.reasoning_parser,
    )
    await worker.start()
    from ..runtime import HealthCheckManager
    from ..runtime.config import env

    health = HealthCheckManager(runtime,
                                canary_wait_time=env("DYNT_CANARY_WAIT_SECS"))
    health.start()
    try:
        await wait_for_shutdown_signal()
    finally:
        # Departure ladder BEFORE teardown (docs/fault-tolerance.md):
        # live streams hand off / replay instead of dying with the
        # endpoints — what the faults service's `evict` notice drives.
        try:
            await worker.drain("shutdown-signal")
        except Exception:  # noqa: BLE001 — teardown proceeds regardless
            log.exception("graceful drain failed")
        await health.close()
        await worker.close()
        await runtime.shutdown()


if __name__ == "__main__":
    asyncio.run(main())
