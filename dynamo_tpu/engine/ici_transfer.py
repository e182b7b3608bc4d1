"""ICI fast-path KV handoff for co-meshed disaggregation (disagg v2).

The reference's NIXL layer moves prefill KV to the decode GPU by direct
accelerator-to-accelerator RDMA, off the critical decode path (ref:
docs/design-docs/kvbm-design.md §Remote Memory Integration;
lib/bindings/python/src/dynamo/nixl_connect/__init__.py:633 device-to-device
descriptors). The TPU equivalent of "RDMA between accelerators" is the ICI
fabric, and the idiomatic way to ride it is NOT verbs — it is device-to-
device array movement under XLA's runtime:

  * co-meshed pools (one process, one device set split into a prefill
    sub-mesh and a decode sub-mesh): a jitted gather on the prefill mesh
    produces a compact page bundle, `jax.device_put` reshards it onto the
    decode mesh (a direct chip-to-chip copy over ICI on TPU — no host
    round-trip), and a jitted scatter lands it in the decode pool. Only the
    two jitted endpoints must serialize with their pool's stepping (the KV
    buffers are donated through steps); the bulk movement overlaps decode.

  * union-meshed pools (both pools inside ONE SPMD program, a "pool" mesh
    axis): `ppermute_kv_handoff` moves pages rank-to-rank with
    `lax.ppermute` inside shard_map — the explicit collective-permute form,
    used by xPyD layouts that co-locate prefill and decode shards in one
    jit (and by the driver's multi-chip dryrun).

Host-relay transfer (llm/kv_transfer.py) remains the DCN fallback between
unconnected slices, exactly as the reference falls back from NIXL to host
bounce buffers.
"""

from __future__ import annotations

import asyncio
import functools
import time
import uuid
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.block_copy import gather_kv_blocks
from ..parallel.mesh import AXIS_TP, Mesh, MeshConfig, make_mesh
from ..runtime.logging import get_logger

log = get_logger("engine.ici")

# Universal bundle layout [n, L, kv, ps, kh, hd]: kv heads follow the
# cache's tp sharding; everything else is replicated within the pool.
BUNDLE_SPEC = P(None, None, None, None, AXIS_TP, None)


def split_mesh(
    prefill_devices: int,
    decode_devices: int,
    prefill_tp: Optional[int] = None,
    decode_tp: Optional[int] = None,
    devices=None,
) -> tuple[Mesh, Mesh]:
    """Partition the local device set into disjoint prefill/decode
    sub-meshes (the co-meshed xPyD layout: xP + yD chips of one slice)."""
    if devices is None:
        devices = jax.devices()
    need = prefill_devices + decode_devices
    if len(devices) < need:
        raise ValueError(
            f"co-meshed disagg needs {need} devices "
            f"({prefill_devices}P + {decode_devices}D); have {len(devices)}")
    pre = make_mesh(MeshConfig(tp=prefill_tp or prefill_devices),
                    devices=list(devices[:prefill_devices]))
    dec = make_mesh(MeshConfig(tp=decode_tp or decode_devices),
                    devices=list(devices[prefill_devices:need]))
    return pre, dec


def bundle_sharding(mesh: Mesh, head_sharded: bool = True) -> NamedSharding:
    return NamedSharding(mesh, BUNDLE_SPEC if head_sharded else P())


class IciKvBridge:
    """In-process broker for direct prefill→decode page movement.

    One bridge per co-meshed worker process. The prefill side advertises
    `bridge_token` in its kv_transfer_params; a decode worker holding the
    same token (same process) pulls through the bridge instead of the wire.

    Pull pipeline (each stage on the thread that owns the touched buffer):
      1. gather  — prefill scheduler thread (prefill pool is donated
                   through prefill steps); produces an independent bundle,
                   prefill stepping resumes immediately
      2. reshard — `jax.device_put` prefill-mesh → decode-mesh: the ICI
                   copy. Runs off-thread; neither pool's step blocks on it
      3. scatter — decode scheduler thread (decode pool donation), one
                   fused write at admission
    """

    def __init__(self) -> None:
        self.token = uuid.uuid4().hex
        self._prefill = None  # TpuWorker (prefill side)
        self.pulls = 0  # attempted
        self.hits = 0  # delivered device bundles

    def attach_prefill(self, worker) -> None:
        self._prefill = worker

    async def pull(self, transfer_id: str, decode_runner
                   ) -> tuple[Optional[jax.Array], Optional[int]]:
        """Claim a parked transfer and return (bundle, first_token) as a
        device array on the decode mesh ((None, None) -> caller recomputes
        prefill, the same fallback the host-relay path takes). Streaming
        transfers (chunked disagg handoff) are pulled chunk-by-chunk: the
        gather + ICI reshard of chunk i runs while the prefill pool is
        still computing chunk i+1, and the terminal chunk carries the
        first sampled token."""
        self.pulls += 1
        worker = self._prefill
        if worker is None:
            log.warning("ici pull with no prefill side attached")
            return None, None
        transfer = worker.transfers.claim(transfer_id)
        if transfer is None:
            log.warning("ici pull: unknown transfer %s", transfer_id)
            return None, None
        first_token = getattr(transfer, "first_token", None)
        gap_exec = getattr(worker.scheduler, "run_in_gap",
                           worker.scheduler.run_in_step)
        head_sharded = not worker.runner.model_config.is_mla
        target = bundle_sharding(decode_runner.mesh, head_sharded)
        parts: list[jax.Array] = []

        async def gather_reshard(ids: list[int]) -> bool:
            """Gather `ids` on the prefill scheduler (gap window), then
            launch the ICI reshard; False -> recompute fallback."""
            page_ids = jnp.asarray(ids, jnp.int32)
            resultq = gap_exec(
                lambda: gather_kv_blocks(*worker.runner.cache[0], page_ids))
            try:
                bundle, exc = await asyncio.to_thread(resultq.get, True, 60.0)
            except Exception as exc_:  # noqa: BLE001 — queue.Empty on timeout
                log.warning("ici gather timed out: %r", exc_)
                return False
            if exc is not None:
                log.warning("ici gather failed: %r", exc)
                return False
            try:
                parts.append(jax.device_put(bundle, target))  # ICI hop
            except Exception as exc_:  # noqa: BLE001 — degrade to recompute
                log.warning("ici reshard failed (%r); recomputing prefill",
                            exc_)
                return False
            return True

        try:
            if transfer.streaming:
                sent = 0
                # Stall window, re-armed on every chunk of progress: a
                # long prompt may legitimately prefill for many minutes
                # (other sequences share the chunk budget); only a
                # 120s lull with NO new pages aborts to recompute.
                deadline = time.monotonic() + 120.0
                while True:
                    ids, done, failed = await asyncio.to_thread(
                        transfer.wait_ready, sent, 1.0)
                    if failed:
                        log.warning("ici pull: transfer %s aborted",
                                    transfer_id[:8])
                        return None, None
                    new = ids[sent:]
                    if not new and not done:
                        if time.monotonic() > deadline:
                            log.warning("ici pull timed out awaiting "
                                        "prefill chunks")
                            return None, None
                        continue
                    if new:
                        if not await gather_reshard(new):
                            return None, None
                        sent += len(new)
                        deadline = time.monotonic() + 120.0
                    if done and sent >= len(ids):
                        first_token = transfer.first_token
                        break
            else:
                if not await gather_reshard(list(transfer.page_ids)):
                    return None, None
        finally:
            # Pages go back to the prefill pool as soon as the gathers
            # made independent copies (or failed) — not after decode
            # admission.
            transfer.release()
        try:
            dst = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            await asyncio.to_thread(jax.block_until_ready, dst)
        except Exception as exc:  # noqa: BLE001 — degrade like the wire path
            # Same contract as the host-relay pull: ANY transfer failure
            # (decode HBM full, sharding mismatch) means recompute, not a
            # failed user request.
            log.warning("ici concat failed (%r); recomputing prefill", exc)
            return None, None
        self.hits += 1
        log.info("ici bridge pull %s: %d pages moved prefill->decode "
                 "on-device (%d chunk(s))", transfer_id[:8],
                 int(dst.shape[0]), len(parts))
        return dst, first_token


# -- union-mesh (single SPMD program) collective-permute form ---------------


@functools.lru_cache(maxsize=16)
def _ppermute_fn(mesh: Mesh, pool_axis: str):
    """Compile the handoff program once per (mesh, pool_axis) — a fresh
    closure per call would miss jit's identity-keyed cache and retrace the
    whole SPMD program on every transfer."""

    def body(kv, src, dst):
        # kv arrives as the rank-local pool [1, L, kvd, P, ps, kh, hd].
        local = kv[0]
        moved = local[:, :, src].transpose(2, 0, 1, 3, 4, 5)
        moved = jax.lax.ppermute(moved, pool_axis, [(0, 1)])
        # Only rank 1 receives real data; rank 0 gets zeros from ppermute's
        # no-source hole, and its scatter is masked off by `is_decode`.
        is_decode = jax.lax.axis_index(pool_axis) == 1
        landed = jnp.where(
            is_decode,
            local.at[:, :, dst].set(moved.transpose(1, 2, 0, 3, 4, 5)),
            local,
        )
        return landed[None]

    specs = P(pool_axis, None, None, None, None, AXIS_TP, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs, P(), P()),
        out_specs=specs,
    )
    return jax.jit(fn, donate_argnums=(0,))


def ppermute_kv_handoff(
    pooled_kv: jax.Array,  # [2, L, kv, P, ps, kh, hd] — axis 0 over "pool"
    src_pages: jax.Array,  # [n] pages to read on pool rank 0
    dst_pages: jax.Array,  # [n] pages to write on pool rank 1
    mesh: Mesh,
    pool_axis: str = "pool",
) -> jax.Array:
    """Move pages between the prefill half (pool rank 0) and decode half
    (pool rank 1) of ONE union mesh with an explicit `lax.ppermute` — the
    collective-permute KV handoff. Everything happens in a single jitted
    SPMD program: gather on rank 0, one ICI permute, scatter on rank 1.

    `pooled_kv` leads with the pool axis so each rank owns its page pool;
    within a rank the cache keeps its usual [L, kv, P, ps, kh, hd] layout
    (kh may additionally be tp-sharded — the permute moves each tp shard
    to its peer with the same tp coordinate, n_tp parallel ICI hops).
    """
    return _ppermute_fn(mesh, pool_axis)(pooled_kv, src_pages, dst_pages)
