"""dynamo_tpu — a TPU-native datacenter-scale LLM inference framework.

A ground-up re-design of the capabilities of NVIDIA Dynamo (the reference,
surveyed in SURVEY.md) for TPU hardware: an asyncio distributed runtime
(discovery, request plane, event plane), OpenAI-compatible frontend,
KV-cache-aware routing, disaggregated prefill/decode serving, a multi-tier KV
block manager (HBM -> host DRAM -> SSD -> object store), an SLA planner, and —
unlike the reference, which orchestrates external GPU engines — a native
JAX/pjit/Pallas inference engine with paged attention and continuous batching.

Layer map (mirrors reference layers, see SURVEY.md section 1):
  runtime/    distributed runtime core (ref: lib/runtime)
  tokens/     token-block hashing      (ref: lib/tokens)
  kv_router/  routing data structures  (ref: lib/kv-router)
  llm/        serving layer            (ref: lib/llm)
  engine/     JAX inference engine     (ref: delegated to vLLM/SGLang upstream)
  models/     model families (flagship: Qwen3/Llama-style decoders)
  ops/        Pallas TPU kernels       (ref: CUDA kernels, section 2.4)
  parallel/   mesh/sharding/collectives
  kvbm/       KV block manager         (ref: lib/kvbm-*)
  mocker/     chip-free engine sim     (ref: lib/mocker)
  planner/    SLA autoscaler           (ref: components/planner)
"""

import time as _time

__version__ = "0.1.0"
# Where the kernel's own record of this process's start cannot be read,
# the cold-start ladder counts from here (engine/coldstart.py).
IMPORTED_AT = _time.monotonic()
