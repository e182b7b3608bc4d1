"""Model/checkpoint registry records (DynamoModel / DynamoCheckpoint CRD
analogs — ref: deploy/operator/api/v1alpha1/{dynamomodel,
dynamocheckpoint}_types.go) in the discovery plane, and worker
--model-ref resolution."""

import pytest

from dynamo_tpu.deploy.registry import (
    CheckpointRecord,
    ModelRecord,
    delete_model,
    get_checkpoint,
    get_model,
    list_checkpoints,
    list_models,
    register_checkpoint,
    register_model,
    resolve_model_ref,
)
from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig


async def _runtime():
    cfg = RuntimeConfig()
    cfg.discovery_backend = "mem"
    cfg.discovery_path = "registry-test"
    cfg.system_enabled = False
    return await DistributedRuntime(cfg).start()


class TestModelRegistry:
    def test_register_get_list_delete(self, run):
        async def body():
            rt = await _runtime()
            try:
                await register_model(rt, ModelRecord(
                    name="q06", source="qwen3-0.6b"))
                await register_model(rt, ModelRecord(
                    name="l8b", source="/ckpts/llama8b",
                    served_model_name="llama-3-8b", revision="abc123"))
                rec = await get_model(rt, "q06")
                assert rec.source == "qwen3-0.6b"
                assert rec.served_model_name == "q06"  # defaulted
                assert rec.created_ts > 0
                names = [m.name for m in await list_models(rt)]
                assert names == ["l8b", "q06"]
                await delete_model(rt, "q06")
                assert await get_model(rt, "q06") is None
            finally:
                await rt.shutdown()
        run(body())

    def test_resolve_unknown_ref_is_explicit_error(self, run):
        async def body():
            rt = await _runtime()
            try:
                await register_model(rt, ModelRecord(
                    name="known", source="tiny-test"))
                with pytest.raises(KeyError, match="known"):
                    await resolve_model_ref(rt, "missing")
                rec = await resolve_model_ref(rt, "known")
                assert rec.source == "tiny-test"
            finally:
                await rt.shutdown()
        run(body())


class TestWorkerModelRef:
    def test_worker_serves_registered_model(self, run, tmp_path):
        """--model-ref resolves the registry record: the worker loads the
        record's source and registers under its served name (the
        DynamoModel flow end-to-end over file discovery)."""
        import asyncio
        import os
        import subprocess
        import sys

        async def body():
            disc = str(tmp_path / "disc")
            cfg = RuntimeConfig()
            cfg.discovery_backend = "file"
            cfg.discovery_path = disc
            cfg.system_enabled = False
            rt = await DistributedRuntime(cfg).start()
            proc = None
            try:
                await register_model(rt, ModelRecord(
                    name="reg-tiny", source="tiny-test",
                    served_model_name="tiny-served"))
                env = dict(os.environ)
                env.update({"DYNT_DISCOVERY_BACKEND": "file",
                            "DYNT_DISCOVERY_PATH": disc,
                            "JAX_PLATFORMS": "cpu",
                            "DYNT_SYSTEM_ENABLED": "0"})
                proc = subprocess.Popen(
                    [sys.executable, "-m", "dynamo_tpu.worker",
                     "--model-ref", "reg-tiny", "--page-size", "4",
                     "--num-pages", "32", "--max-batch", "2",
                     "--max-pages-per-seq", "8"],
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.STDOUT, env=env)
                served = None
                for _ in range(240):
                    cards = await rt.discovery.get_prefix("v1/mdc/")
                    names = [c.get("name") for c in cards.values()]
                    if "tiny-served" in names:
                        served = names
                        break
                    await asyncio.sleep(0.5)
                assert served and "tiny-served" in served
            finally:
                if proc is not None:
                    proc.terminate()
                    proc.wait(timeout=20)
                await rt.shutdown()

        run(body(), timeout=180)


class TestCheckpointRegistry:
    def test_register_list_filter(self, run):
        async def body():
            rt = await _runtime()
            try:
                await register_checkpoint(rt, CheckpointRecord(
                    name="s1", model="q06", snapshot_dir="/snap/s1",
                    weights_digest="d1"))
                await register_checkpoint(rt, CheckpointRecord(
                    name="s2", model="l8b", snapshot_dir="/snap/s2"))
                rec = await get_checkpoint(rt, "s1")
                assert rec.snapshot_dir == "/snap/s1"
                assert rec.weights_digest == "d1"
                only_q06 = await list_checkpoints(rt, model="q06")
                assert [c.name for c in only_q06] == ["s1"]
                assert len(await list_checkpoints(rt)) == 2
            finally:
                await rt.shutdown()
        run(body())
