"""JAX/TPU inference engine (the layer the reference delegates to vLLM)."""

from .model_runner import ModelRunner, PrefillRow, RunnerConfig
from .pages import PageAllocation, PagePool
from .scheduler import InferenceScheduler, SchedulerStats
from .worker import KvEventBuffer, TpuWorker

__all__ = [
    "InferenceScheduler",
    "KvEventBuffer",
    "ModelRunner",
    "PrefillRow",
    "PageAllocation",
    "PagePool",
    "RunnerConfig",
    "SchedulerStats",
    "TpuWorker",
]
