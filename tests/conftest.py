"""Test config: force a virtual 8-device CPU mesh so sharding tests run
anywhere (the chip itself is reached through `chip_smoke.py`), and
provide asyncio helpers since pytest-asyncio isn't available.

Mirrors the reference's chip-free test strategy (ref: tests/README.md — the
integration tier runs with the mocker, "no GPU required").
"""

import asyncio
import os

# Tests run on a virtual 8-device CPU mesh and must never touch an
# accelerator (a chip belongs to one process; a test run must not be it).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("DYNT_LOG_LEVEL", "WARNING")
# Each xdist worker keeps its own persistent compile cache: six workers
# reading and writing one directory lost a worker to a segmentation fault
# inside jax's cache read (ROADMAP C12 (1), PR 45's run).
if "PYTEST_XDIST_WORKER" in os.environ:
    import tempfile

    os.environ["DYNT_COMPILE_CACHE_DIR"] = os.path.join(
        tempfile.gettempdir(),
        f"dynamo_tpu_jax_cache-{os.environ['PYTEST_XDIST_WORKER']}")

import pytest


@pytest.fixture
def run():
    """Run a coroutine to completion on a fresh event loop."""

    def _run(coro, timeout=60.0):
        async def _with_timeout():
            return await asyncio.wait_for(coro, timeout)

        return asyncio.run(_with_timeout())

    return _run


@pytest.fixture
def tmp_discovery(tmp_path):
    """Isolated file-discovery root."""
    return str(tmp_path / "discovery")


@pytest.fixture
def mem_runtime_config():
    """In-process runtime config: mem discovery + mem request plane."""
    from dynamo_tpu.runtime.config import RuntimeConfig
    import uuid

    def _make(cluster=None):
        cfg = RuntimeConfig.from_env()
        cfg.discovery_backend = "mem"
        cfg.discovery_path = cluster or uuid.uuid4().hex
        cfg.request_plane = "mem"
        cfg.event_plane = "mem"
        cfg.system_enabled = False
        cfg.lease_ttl_secs = 2.0
        return cfg

    return _make
