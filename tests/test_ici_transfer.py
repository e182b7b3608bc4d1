"""ICI fast-path disagg (v2): co-meshed prefill/decode pools with direct
device-to-device KV handoff (ref: kvbm-design.md §Remote Memory Integration,
nixl_connect device descriptors; our engine/ici_transfer.py).

Runs on the virtual 8-device CPU mesh from conftest.
"""

import asyncio
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine import RunnerConfig, TpuWorker
from dynamo_tpu.engine.ici_transfer import (
    IciKvBridge,
    bundle_sharding,
    ppermute_kv_handoff,
    split_mesh,
)
from dynamo_tpu.llm.engine import RouterEngine
from dynamo_tpu.llm.prefill_router import PrefillPool, PrefillRouterEngine
from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.ops.block_copy import gather_kv_blocks
from dynamo_tpu.runtime import DistributedRuntime
from dynamo_tpu.runtime.push_router import PushRouter


def _request(tokens, max_tokens=6, temperature=0.0):
    return PreprocessedRequest(
        request_id=uuid.uuid4().hex,
        token_ids=list(tokens),
        sampling=SamplingOptions(max_tokens=max_tokens,
                                 temperature=temperature, seed=7),
        stop=StopConditions(ignore_eos=True),
    )


async def _collect(engine, request):
    toks = []
    async for out in engine.generate(request):
        assert out.error is None, out.error
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            break
    return toks


class TestSplitMesh:
    def test_disjoint_device_partition(self):
        pre, dec = split_mesh(2, 2, prefill_tp=2, decode_tp=2)
        pre_ids = {d.id for d in pre.devices.flatten()}
        dec_ids = {d.id for d in dec.devices.flatten()}
        assert len(pre_ids) == 2 and len(dec_ids) == 2
        assert not (pre_ids & dec_ids)

    def test_rejects_oversubscription(self):
        with pytest.raises(ValueError):
            split_mesh(8, 8)


class TestDeviceBundleMovement:
    def test_gather_reshard_scatter_roundtrip(self):
        """Pages written on the prefill mesh land bit-identical in the
        decode pool after the cross-mesh reshard."""
        from dynamo_tpu.engine import ModelRunner
        from dynamo_tpu.models import get_config

        pre_mesh, dec_mesh = split_mesh(2, 2, prefill_tp=2, decode_tp=2)
        cfg = get_config("tiny-test")
        rcfg = RunnerConfig(page_size=4, num_pages=32, max_batch=2,
                            max_pages_per_seq=8, prefill_buckets=(8, 16))
        pre = ModelRunner(cfg, rcfg, pre_mesh, seed=0)
        dec = ModelRunner(cfg, rcfg, dec_mesh, seed=0)

        table = np.zeros(8, np.int32)
        table[:4] = [1, 2, 3, 4]
        prompt = np.arange(10, 23).astype(np.int32)  # 13 tokens
        pre.prefill_chunk(prompt, 0, table, len(prompt), (0.0, 1.0, 0, 0))

        src_pages = jnp.asarray([1, 2, 3, 4], jnp.int32)
        bundle = gather_kv_blocks(*pre.cache[0], src_pages)
        moved = jax.device_put(bundle, bundle_sharding(dec_mesh))
        dec.scatter_pages(np.array([5, 6, 7, 8], np.int32), moved)

        got = np.asarray(jax.device_get(
            gather_kv_blocks(*dec.cache[0], jnp.asarray([5, 6, 7, 8],
                                                        jnp.int32))),
            np.float32)
        want = np.asarray(jax.device_get(bundle), np.float32)
        np.testing.assert_array_equal(got, want)
        assert want.any(), "prefill wrote nothing?"


class TestBridgeE2E:
    def test_comesh_disagg_matches_aggregated(self, run, mem_runtime_config):
        """Prefill pool and decode pool on disjoint sub-meshes of one
        process; the KV handoff rides the bridge (device path), never the
        wire, and greedy decode matches a pure-decode-worker run."""

        async def body():
            cfg = mem_runtime_config()
            rt = await DistributedRuntime(cfg).start()
            pre_mesh, dec_mesh = split_mesh(2, 2, prefill_tp=2,
                                            decode_tp=2)
            bridge = IciKvBridge()
            rcfg = RunnerConfig(page_size=4, num_pages=64, max_batch=2,
                                max_pages_per_seq=16,
                                prefill_buckets=(8, 16, 32))
            prefill_w = TpuWorker(rt, model_name="tiny-test",
                                  component="prefill", mode="prefill",
                                  runner_config=rcfg, warmup=False,
                                  mesh=pre_mesh, ici_bridge=bridge)
            decode_w = TpuWorker(rt, model_name="tiny-test",
                                 component="backend", mode="decode",
                                 runner_config=rcfg, warmup=False,
                                 mesh=dec_mesh, ici_bridge=bridge)
            await prefill_w.start()
            await decode_w.start()

            decode_ep = rt.namespace("dynamo").component("backend") \
                          .endpoint("generate")
            decode_router = PushRouter(decode_ep.client(),
                                       mode="round_robin")
            await decode_router.client.start()
            inner = RouterEngine(decode_router)

            prefill_ep = rt.namespace("dynamo").component("prefill") \
                           .endpoint("generate")
            prefill_router = PushRouter(prefill_ep.client(),
                                        mode="round_robin")
            await prefill_router.client.start()
            pool = PrefillPool(router=prefill_router,
                               instances={prefill_w.instance_id})
            disagg_engine = PrefillRouterEngine(inner, lambda: pool)

            prompt = list(range(30, 47))  # 17 tokens: partial last page
            agg = await _collect(inner, _request(prompt))
            dis = await _collect(disagg_engine, _request(prompt))
            assert agg == dis
            assert len(dis) == 6
            assert bridge.pulls == 1 and bridge.hits == 1, \
                "handoff did not ride the ICI bridge"

            # prefill pages released promptly after the bridge gather
            for _ in range(50):
                if len(prefill_w.transfers) == 0:
                    break
                await asyncio.sleep(0.05)
            assert len(prefill_w.transfers) == 0

            await decode_router.client.close()
            await prefill_router.client.close()
            await prefill_w.close()
            await decode_w.close()
            await rt.shutdown()

        run(body(), timeout=300)

    def test_decode_proceeds_during_transfer(self, run, mem_runtime_config):
        """A long decode stream on the decode pool keeps producing tokens
        while a bridge pull for a second request is in flight — the bulk
        movement never blocks the decode step thread."""

        async def body():
            cfg = mem_runtime_config()
            rt = await DistributedRuntime(cfg).start()
            pre_mesh, dec_mesh = split_mesh(2, 2, prefill_tp=2,
                                            decode_tp=2)
            bridge = IciKvBridge()
            rcfg = RunnerConfig(page_size=4, num_pages=64, max_batch=2,
                                max_pages_per_seq=16,
                                prefill_buckets=(8, 16, 32))
            prefill_w = TpuWorker(rt, model_name="tiny-test",
                                  component="prefill", mode="prefill",
                                  runner_config=rcfg, warmup=False,
                                  mesh=pre_mesh, ici_bridge=bridge)
            decode_w = TpuWorker(rt, model_name="tiny-test",
                                 component="backend", mode="decode",
                                 runner_config=rcfg, warmup=False,
                                 mesh=dec_mesh, ici_bridge=bridge)
            await prefill_w.start()
            await decode_w.start()

            decode_ep = rt.namespace("dynamo").component("backend") \
                          .endpoint("generate")
            decode_router = PushRouter(decode_ep.client(),
                                       mode="round_robin")
            await decode_router.client.start()
            inner = RouterEngine(decode_router)
            prefill_ep = rt.namespace("dynamo").component("prefill") \
                           .endpoint("generate")
            prefill_router = PushRouter(prefill_ep.client(),
                                        mode="round_robin")
            await prefill_router.client.start()
            pool = PrefillPool(router=prefill_router,
                               instances={prefill_w.instance_id})
            disagg_engine = PrefillRouterEngine(inner, lambda: pool)

            # long-running stream occupying the decode pool
            long_task = asyncio.create_task(_collect(
                inner, _request(list(range(40, 50)), max_tokens=24)))
            await asyncio.sleep(0.05)
            # disagg request whose KV rides the bridge mid-stream
            dis = await _collect(disagg_engine,
                                 _request(list(range(60, 75))))
            long_toks = await asyncio.wait_for(long_task, 60.0)
            assert len(long_toks) == 24
            assert len(dis) == 6
            assert bridge.hits == 1

            await decode_router.client.close()
            await prefill_router.client.close()
            await prefill_w.close()
            await decode_w.close()
            await rt.shutdown()

        run(body(), timeout=300)


# engine/ici_transfer.py's collective-permute form calls jax.shard_map
# directly (ici_transfer.py:232).
class TestPpermuteHandoff:
    def test_pages_move_rank0_to_rank1(self):
        """Union-mesh collective-permute form: rank 0's src pages land in
        rank 1's dst pages; rank 0's pool is untouched."""
        devs = jax.devices()[:4]
        mesh = Mesh(np.array(devs).reshape(2, 2), ("pool", "tp"))
        L, KV, PAGES, PS, KH, HD = 2, 2, 8, 4, 2, 8
        rng = np.random.default_rng(0)
        pools = rng.normal(size=(2, L, KV, PAGES, PS, KH, HD)) \
                   .astype(np.float32)
        spec = P("pool", None, None, None, None, "tp", None)
        pooled = jax.device_put(pools, NamedSharding(mesh, spec))
        src = jnp.asarray([1, 3, 5], jnp.int32)
        dst = jnp.asarray([2, 4, 6], jnp.int32)
        out = np.asarray(jax.device_get(
            ppermute_kv_handoff(pooled, src, dst, mesh)), np.float32)
        # rank 1 received rank 0's pages
        np.testing.assert_array_equal(out[1][:, :, [2, 4, 6]],
                                      pools[0][:, :, [1, 3, 5]])
        # rank 1's other pages untouched
        others = [i for i in range(PAGES) if i not in (2, 4, 6)]
        np.testing.assert_array_equal(out[1][:, :, others],
                                      pools[1][:, :, others])
        # rank 0 pool untouched
        np.testing.assert_array_equal(out[0], pools[0])
