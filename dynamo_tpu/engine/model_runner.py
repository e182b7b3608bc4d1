"""ModelRunner: compiled prefill/decode steps over a device mesh.

Owns params + the paged KV cache on device and exposes exactly two host
entry points per step kind:

  * prefill(chunk)  — one sequence, bucketed chunk length, writes KV pages,
                      samples the first token on the final chunk
  * decode(batch)   — one token for every active slot

Everything (forward, KV scatter, sampling) is inside `jit` with the KV cache
donated, so steady-state decode moves only [B] int32 tokens host<->device.
Bucketed shapes keep XLA compilation finite; the persistent compilation
cache makes warmup a one-time cost (ref design concern: "Continuous batching
under XLA static shapes", SURVEY section 7).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import threading
import time
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import ModelConfig, make_kv_cache, make_steps, param_axes
from ..models.config import cache_plan
from ..models.transformer import KV_SCALE_LANES, forward_ring, write_kv_stack
from ..parallel import kv_cache_sharding, param_shardings
from ..parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP, Mesh
from ..runtime.config import env
from ..runtime.logging import get_logger
from .sampler import sample, sample_with_logprobs

log = get_logger("engine.runner")

DEFAULT_PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048)
# Rows of `ModelRunner.moe_counts`: the expert layers' statistics are kept
# apart for prefill launches (thousands of tokens: every expert is
# touched) and decode steps (a token a row: few are).
MOE_PHASES = ("prefill", "decode")

# -- compile observability ---------------------------------------------------
# Runtime cross-check for the dynajit DJ1xx static pass: every XLA
# backend compile increments dynamo_jit_compiles_total{fn=<entry>},
# where <entry> is the runner entry point in scope on the compiling
# thread. Steady-state decode must hold the counter flat; the
# retrace-canary tier-1 test asserts the observed set is bounded and
# matches what the checked-in jit-signature registry predicts.
#
# A build is a span, recorded where jax reports it: the three duration
# events of a compile (trace, jaxpr -> MLIR, backend) and the persistent
# cache's hit / miss fire on the compiling thread, inside the
# `compile_scope` the runner entry opened there, and close into one
# record of the ring `/debug/programs` serves. The scope's key is the
# entry and the static shape that selects the compiled program
# (`program_key`): the name a program is built under is the name it is
# launched under (`ModelRunner.program_launches`).

_COMPILE_SCOPE = threading.local()
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_LISTENER_LOCK = threading.Lock()
_LISTENER_INSTALLED = False

# The last builds of this process, oldest first, and how many ever
# closed. Builds land from the start-up thread and the scheduler
# threads (one a replica), `/debug/programs` reads on the loop.
BUILD_RING = 512
_BUILDS: collections.deque = collections.deque(maxlen=BUILD_RING)
_BUILDS_LOCK = threading.Lock()
_builds_total = 0


def program_key(fn: str, *parts) -> str:
    """`fn[part,part]`: a runner entry and the static shape that selects
    its compiled program (`prefill_batch[4x1024]`, `decode[w128]`)."""
    return f"{fn}[{','.join(str(p) for p in parts if p != '')}]"


class _Build:
    """What jax reported on one thread between a scope's opening and its
    close (or, outside any scope, up to one backend event)."""

    def __init__(self, fn: str, key: str, cause) -> None:
        self.fn, self.key, self.cause = fn, key, cause
        self.t_start = time.time()
        self.trace_s = self.lower_s = self.backend_s = 0.0
        self.backends = 0
        self.cache: Optional[str] = None
        self.hit = False  # a cache hit since the last backend event
        self._traces: list[tuple[float, float]] = []

    def trace(self, duration: float) -> float:
        """Seconds of this trace not yet counted: a jitted function
        traced inside another's trace reports first, and the outer
        one's duration holds it."""
        start = time.monotonic() - duration
        inner = 0.0
        while self._traces and self._traces[-1][0] >= start:
            inner += self._traces.pop()[1]
        self._traces.append((start, duration))
        return max(0.0, duration - inner)

    def record(self) -> dict:
        return {"fn": self.fn, "key": self.key, "cause": self.cause,
                "t_start": round(self.t_start, 3),
                "t_end": round(time.time(), 3),
                "trace_s": round(self.trace_s, 4),
                "lower_s": round(self.lower_s, 4),
                "backend_s": round(self.backend_s, 4),
                "backends": self.backends, "cache": self.cache or "off"}


def _append_build(build: _Build) -> dict:
    global _builds_total
    rec = build.record()
    with _BUILDS_LOCK:
        _BUILDS.append(rec)
        _builds_total += 1
    return rec


def _open_build() -> _Build:
    """The build events on this thread belong to: the open scope's, or
    one kept for what compiles outside any scope."""
    build = getattr(_COMPILE_SCOPE, "build", None)
    if build is None:
        build = getattr(_COMPILE_SCOPE, "loose", None)
        if build is None:
            build = _COMPILE_SCOPE.loose = _Build("unscoped", "", "unscoped")
    return build


def _on_compile_event(event: str, duration: float, **kw) -> None:
    if event not in (_TRACE_EVENT, _LOWER_EVENT, _COMPILE_EVENT):
        return
    from ..runtime.metrics import (
        JIT_COMPILE_SECONDS,
        JIT_COMPILES,
        JIT_STAGE_SECONDS,
    )

    build = _open_build()
    if event == _TRACE_EVENT:
        fresh = build.trace(duration)
        build.trace_s += fresh
        JIT_STAGE_SECONDS.labels(fn=build.fn, stage="trace").inc(fresh)
        return
    if event == _LOWER_EVENT:
        build.lower_s += duration
        JIT_STAGE_SECONDS.labels(fn=build.fn, stage="lower").inc(duration)
        return
    # The backend event wraps compile_or_get_cached: its whole duration
    # is a cache load if the cache reported a hit on this thread since
    # the last one, a compile otherwise, so the stages never overlap.
    stage = "cache_load" if build.hit else "compile"
    build.hit = False
    build._traces.clear()
    build.backend_s += duration
    build.backends += 1
    JIT_COMPILES.labels(fn=build.fn).inc()
    JIT_COMPILE_SECONDS.labels(fn=build.fn).inc(duration)
    JIT_STAGE_SECONDS.labels(fn=build.fn, stage=stage).inc(duration)
    if build is getattr(_COMPILE_SCOPE, "loose", None):
        # no scope will close it: one record a program, named by jax
        build.key = str(kw.get("fun_name", ""))
        _COMPILE_SCOPE.loose = None
        _append_build(build)


def _on_cache_event(event: str, **_kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is None:
        return
    from ..runtime.metrics import COMPILE_CACHE

    COMPILE_CACHE.labels(outcome=outcome).inc()
    build = _open_build()
    build.hit = outcome == "hit"
    if build.cache != "miss":
        build.cache = outcome


def _install_compile_listener() -> None:
    """Idempotent process-wide registration (jax.monitoring listeners
    cannot be unregistered individually; one of each kind is enough)."""
    global _LISTENER_INSTALLED
    with _LISTENER_LOCK:
        if _LISTENER_INSTALLED:
            return
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
        jax.monitoring.register_event_listener(_on_cache_event)
        _LISTENER_INSTALLED = True


# Thread ident -> (label, monotonic entry time) of the runner entry that
# thread is dispatching right now. Dispatch is asynchronous: a scope
# that has been open for seconds is tracing and compiling, not running.
_IN_DISPATCH: dict[int, tuple[str, float]] = {}

# A jit cache hit enqueues in milliseconds; past this, the scope is a
# compile (minutes at 7B: 32 unrolled layers of Mosaic kernels).
COMPILE_STALL_SECS = 2.0


@contextlib.contextmanager
def compile_scope(label: str, key: str, cause=None):
    """Attribute any XLA compile fired inside the block to `label`, and
    mark this thread as inside that entry's dispatch. If anything was
    built, the scope closes into one record of the build ring under
    `key`; `cause` names the warm-up pass that asked for it, and a
    launch's build (no cause) is kept for `take_builds`."""
    prev = getattr(_COMPILE_SCOPE, "build", None)
    build = _COMPILE_SCOPE.build = _Build(label, key, cause or "launch")
    ident = threading.get_ident()
    _IN_DISPATCH[ident] = (label, time.monotonic())
    try:
        yield
    finally:
        _COMPILE_SCOPE.build = prev
        _IN_DISPATCH.pop(ident, None)
        if build.backends or build.trace_s or build.lower_s:
            rec = _append_build(build)
            if cause is None:
                fresh = getattr(_COMPILE_SCOPE, "fresh", None)
                if fresh is None:
                    fresh = _COMPILE_SCOPE.fresh = collections.deque(
                        maxlen=16)
                fresh.append(rec)


def take_builds() -> Sequence[dict]:
    """Records of the launches' builds that closed on this thread since
    the last call (the scheduler names their requests as the cause)."""
    fresh = getattr(_COMPILE_SCOPE, "fresh", None)
    if not fresh:
        return ()
    _COMPILE_SCOPE.fresh = None
    return fresh


def programs_snapshot() -> dict:
    """The build ring, oldest first, and how many builds ever closed
    (more than the ring holds once the oldest have left it)."""
    with _BUILDS_LOCK:
        return {"builds": list(_BUILDS), "builds_total": _builds_total,
                "ring": BUILD_RING}


def compiling(thread_ident: Optional[int]) -> Optional[tuple[str, float]]:
    """(entry label, seconds so far) when `thread_ident` has sat inside
    one runner dispatch long enough that it can only be compiling;
    None otherwise. Liveness probes read it: an engine thread that is
    compiling is slow, not wedged."""
    entry = _IN_DISPATCH.get(thread_ident)
    if entry is None:
        return None
    label, since = entry
    secs = time.monotonic() - since
    return (label, secs) if secs >= COMPILE_STALL_SECS else None


def bucket_table_width(pages_needed: int, max_pages: int) -> int:
    """Power-of-two block-table width covering `pages_needed` (min 8,
    capped at max_pages). Shared by the scheduler and bench so both run
    the same jit specializations."""
    width = 8
    while width < pages_needed:
        width *= 2
    return min(width, max_pages)


@dataclasses.dataclass
class RunnerConfig:
    page_size: int = 16
    num_pages: int = 2048
    max_batch: int = 16
    max_pages_per_seq: int = 128  # => context cap = page_size * this
    prefill_buckets: tuple[int, ...] = DEFAULT_PREFILL_BUCKETS
    # Multi-LoRA slot pack (0 = LoRA disabled). All slots share one static
    # rank so any adapter mix batches into one compiled step.
    max_loras: int = 0
    lora_rank: int = 8
    # KV cache storage: "model" (the model dtype, bf16) | "int8"
    # (quantized pool + per-token head-shared scales). int8 gives ~1.6x
    # KV CAPACITY (more concurrent sequences / longer contexts per chip);
    # measured on v5e it currently costs ~25% decode step time (the q8
    # kernel's per-page DMA overheads outweigh the traffic saving — see
    # BASELINE.md), so it is a capacity lever, not a latency one, until
    # the kernel is tuned. r5: composes with KVBM/disagg transfers
    # (packed uint8 universal blocks, ops/block_copy.py).
    kv_dtype: str = "model"
    # Weight storage: "model" (bf16) | "int8" (weight-only W8A16: the
    # dense projection stack as int8 + per-output-channel scales through
    # the Pallas kernel in ops/q8_linear.py — halves decode weight
    # streaming, the 7B single-chip bandwidth lever; models/quantize.py
    # scope notes).
    weight_dtype: str = "model"
    # A model with window AND full attention layers: pages of its second
    # page group (engine/pages.py `WindowPool`; `num_pages` stays the
    # full group's).
    window_pages: int = 0

    @property
    def max_context(self) -> int:
        return self.page_size * self.max_pages_per_seq


class PrefillRow(NamedTuple):
    """One sequence's chunk in a batched prefill launch
    (`ModelRunner.prefill_chunk_batch`): the same fields for every model,
    as `prefill_chunk` takes them one by one."""

    tokens: np.ndarray  # [t] chunk token ids
    start: int  # absolute position of tokens[0]
    table: np.ndarray  # [max_pages_per_seq] int32: the full group's pages
    kv_len_after: int
    sampling: tuple  # (temp, top_p, top_k, seed)
    lora_idx: int = 0
    # the scheduler's slot, where per-slot state lives; None: past the
    # end, its write dropped
    slot: Optional[int] = None
    # a second page group's (pages from the row's first held block, that
    # block's first position); None where the cache has one group
    window: Optional[tuple] = None


# What a warm-up prefill row passes beside its scratch table: its state
# slot is past the end (the default), its window table empty.
IDLE_WINDOW = ((), 0)


def _enable_compile_cache() -> None:
    cache_dir = env("DYNT_COMPILE_CACHE_DIR")
    try:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        os.makedirs(cache_dir, exist_ok=True)
    except Exception:  # noqa: BLE001 — cache is best-effort
        pass


def _pallas_mode(mesh: Mesh) -> Optional[bool]:
    """DYNT_ATTENTION gating (ops.kernel_path): `interpret` (bool) when a
    Pallas kernel should be used, None for the XLA reference."""
    from ..ops import kernel_path

    path = kernel_path("DYNT_ATTENTION")
    return None if path == "xla" else path == "interpret"


def _mesh_kernels(mesh: Mesh) -> dict:
    """What this mesh and backend run in each attention slot of a step
    program (None: the XLA form). The model's adapter takes the ones its
    stack has a use for (`models.make_steps`).

    prefill: `ops.paged_attention.paged_attention`, the blocked prefill
    kernel over the paged pool wherever it admits the geometry;
    prefill_latent: `paged_attention_latent`, the same for a single
    stack of latent rows (a `layer_pattern` model with latent
    attention). spec:
    the whole-pool chunked-DMA kernel with the chunk dim folded into the
    GQA group dim, so one dispatch streams each owned page once for all
    k+1 candidate positions of a speculative verification. Both on a
    single device; a multi-device mesh keeps the XLA path, whose
    sharding pjit manages (its float32 score tensors cost there what
    they cost the flagship cell before the kernel: PERF.md, PR 39; a
    shard_map over kv heads as the decode kernel has is the mend)."""
    from ..ops.paged_attention import (
        paged_attention,
        paged_attention_latent,
        paged_attention_spec_pool,
    )

    interpret = _pallas_mode(mesh)
    one_device = interpret is not None and mesh.devices.size == 1
    return {
        "prefill": (partial(paged_attention, interpret=interpret)
                    if one_device else None),
        "prefill_latent": (partial(paged_attention_latent,
                                   interpret=interpret)
                           if one_device else None),
        "decode": _default_decode_attention_fn(mesh),
        "decode_latent": _default_decode_attention_fn(mesh, latent=True),
        "spec": (partial(paged_attention_spec_pool, interpret=interpret)
                 if one_device else None)}


def _default_decode_attention_fn(mesh: Mesh, latent: bool = False):
    """History-attention kernel for the DEFERRED-write decode path.

    On TPU the XLA page gather reads every table's full extent through
    scatter-shaped HLO; the whole-pool chunked-DMA Pallas kernel streams
    only the owned pages of active rows, with no per-layer slice copies
    (what it costs per step: PERF.md, `paged_attn_roofline_pct`).

    Mesh coverage: single device runs the kernel directly; a tp-only mesh
    runs it per-shard via shard_map over the kv-head axis (each shard
    streams its local pool slice — ops/paged_attention.py
    make_paged_attention_decode_pool_tp). Meshes with other multi-size
    axes (dp/sp/ep/pp) keep the XLA path, whose sharding pjit manages.

    `latent`: a `layer_pattern` model with latent attention, whose pool
    is a single stack of latent rows with a kernel of its own, on one
    device (the worker refuses more); else its XLA oracle
    (models/hybrid.py)."""
    interpret = _pallas_mode(mesh)
    if interpret is None:
        return None
    n = mesh.devices.size
    if latent:
        if n > 1:
            return None
        from ..ops.paged_attention import paged_attention_decode_latent

        return partial(paged_attention_decode_latent, interpret=interpret)
    if n == 1:
        from ..ops.paged_attention import paged_attention_decode_pool

        return partial(paged_attention_decode_pool, interpret=interpret)
    if mesh.shape.get(AXIS_TP, 1) == n:
        from ..ops.paged_attention import (
            make_paged_attention_decode_pool_tp,
        )

        return make_paged_attention_decode_pool_tp(mesh,
                                                   interpret=interpret)
    return None


class ModelRunner:
    def __init__(
        self,
        model_config: ModelConfig,
        runner_config: RunnerConfig,
        mesh: Mesh,
        params: Optional[dict] = None,
        seed: int = 0,
        attention_fn=None,
    ) -> None:
        _enable_compile_cache()
        _install_compile_listener()
        self.model_config = model_config
        self.config = runner_config
        self.mesh = mesh
        # What the cache is and cannot do, by the configuration
        # (`cache_plan`), and the model's half of every step program
        # (`models.make_steps`: the forwards, the kernels they take of
        # this mesh's, a caller's own `attention_fn` before any). Nothing
        # below asks which family the model is.
        self.cache_plan = cache_plan(model_config)
        self._user_attention_fn = attention_fn
        self._steps = make_steps(model_config, _mesh_kernels(mesh),
                                 attention_fn)
        self._check_plan(mesh)
        if model_config.ssm_layers:
            # No bucket under one chunk of the scan (published: 128): a
            # shorter launch reads the same weights, and every bucket
            # less is a row of programs less to compile (`prewarm`).
            self.config = runner_config = dataclasses.replace(
                runner_config, prefill_buckets=tuple(
                    b for b in runner_config.prefill_buckets
                    if b >= model_config.ssm_chunk
                    or b == runner_config.prefill_buckets[-1]))
        axes = param_axes(model_config)
        if runner_config.weight_dtype not in ("model", "int8", "int4"):
            raise ValueError(
                f"unknown weight_dtype {runner_config.weight_dtype!r} "
                "(expected 'model', 'int8', or 'int4')")
        self._weight_quantized = runner_config.weight_dtype in ("int8",
                                                                "int4")
        self._raw_param_sharding = None
        if self._weight_quantized:
            from ..models.quantize import check_quantizable

            check_quantizable(model_config,
                              tp=int(dict(mesh.shape).get("tp", 1)),
                              n_devices=mesh.devices.size,
                              dtype=runner_config.weight_dtype)
            # Raw tree places un-quantized inputs (checkpoints, random
            # init) before the device-side quantize transform.
            self._raw_param_sharding = param_shardings(mesh, axes)
            axes = self._quantize_axes(axes, model_config)
        self._param_sharding = param_shardings(mesh, axes)
        if runner_config.kv_dtype not in ("model", "int8"):
            raise ValueError(
                f"unknown kv_dtype {runner_config.kv_dtype!r} "
                "(expected 'model' or 'int8')")
        self._kv_quantized = runner_config.kv_dtype == "int8"
        if self._kv_quantized:
            from ..ops import kernel_path

            if model_config.head_dim != KV_SCALE_LANES:
                # The q8 kernel's elementwise dequant needs head_dim ==
                # the scale lane width; anything else would silently run
                # every decode step on the ~10x-slower XLA gather path.
                raise ValueError(
                    f"int8 KV requires head_dim == {KV_SCALE_LANES} "
                    f"(model has {model_config.head_dim}); the Pallas q8 "
                    "kernel cannot cover this geometry yet")
            shard_heads = model_config.n_kv_heads // mesh.shape.get(
                AXIS_TP, 1)
            if (shard_heads % 4 and mesh.devices.size > 1
                    and self._steps.decode_attention_fn is not None
                    and kernel_path("DYNT_ATTENTION") == "pallas"):
                # Found on the v5e (PR 21): Mosaic tiles an int8 pool's
                # (kv heads, head_dim) as (4, 128), and the kernel's
                # per-page DMA of a 2-head shard is refused ("Slice
                # shape along dimension 4 must be aligned to tiling").
                raise ValueError(
                    f"int8 KV under tp leaves {shard_heads} kv head(s) "
                    "per shard; the compiled q8 attention kernel needs a "
                    "multiple of 4 — use kv_dtype='model' or a smaller tp")
        self._rep = NamedSharding(mesh, P())  # replicated host inputs
        self._cache_sharding = self._kv_cache_sharding(mesh)

        def _already_quantized(p) -> bool:
            """True when the incoming pytree already carries THIS
            runner's quantized leaves; a tree quantized in the other
            dtype (e.g. an int8 weight-service stream re-attached by an
            int4 runner) is rejected up front — silently accepting it
            would die later on an opaque pytree-structure mismatch."""
            want = "q4" if runner_config.weight_dtype == "int4" else "q8"
            other = "q8" if want == "q4" else "q4"
            leaves = [leaf for leaf in p["layers"][0].values()
                      if isinstance(leaf, dict)]
            if any(other in leaf for leaf in leaves):
                raise ValueError(
                    f"params are already quantized as '{other}' but this "
                    f"runner wants weight_dtype="
                    f"{runner_config.weight_dtype!r}; re-publish the "
                    "weights unquantized or match the weight_dtype")
            return any(want in leaf for leaf in leaves)

        if params is None:
            params = self._init_random_params(seed)
        elif self._weight_quantized and not _already_quantized(params):
            # Host arrays (checkpoint / random): place raw, quantize on
            # device (one-time cost at load). Weight-service re-attach
            # streams the ALREADY-quantized pytree and skips this.
            quantize = self._quantize_params_fn()
            params = jax.tree.map(jax.device_put, params,
                                  self._raw_param_sharding)
            # donate: a 7B's bf16 params + quantized copy would exceed
            # HBM if both were live; donation lets XLA retire each bf16
            # leaf as its quantized form materializes.
            params = jax.jit(
                lambda p: quantize(p, model_config),
                out_shardings=self._param_sharding,
                donate_argnums=0,
            )(params)
        else:
            if runner_config.weight_dtype == "int4":
                from ..models.quantize import check_packed_int4

                check_packed_int4(params)
            # Host arrays (weight service / peer stream / checkpoint) or
            # device arrays: place each leaf under its sharding. For arrays
            # already placed correctly this is a no-op.
            params = jax.tree.map(jax.device_put, params,
                                  self._param_sharding)
        self.params = params
        # The one cache, donated whole to every step program and taken
        # back whole: (pools, state). `pools` has an entry a page group of
        # the plan (an int8 pool's (values, scales) inside its entry);
        # `state` is the per-slot pytree, None for a stack without.
        self.cache = self._kv_cache_init()()
        # expert statistics (models/hybrid.moe_mixer) of launches whose
        # readback nobody waits for, with the phase each belongs to;
        # `moe_stats` folds the ones that are ready into `moe_counts`
        # [phase (MOE_PHASES), tokens per held expert..., dropped,
        # touched, calls]
        self._moe_pending: list = []
        self.moe_counts = (np.zeros((len(MOE_PHASES), self._steps.stats_size),
                                    np.int64)
                           if self._steps.stats_size else None)
        self.lora_pack = None
        if runner_config.max_loras > 0:
            from ..models.transformer import init_lora_pack

            # Replicated (tiny vs the base weights); slot 0 stays zero.
            self.lora_pack = jax.device_put(
                init_lora_pack(model_config, runner_config.max_loras,
                               runner_config.lora_rank),
                NamedSharding(mesh, P()),
            )
        self._decode_fn = self._build_decode(False)
        self._decode_fn_lp = None  # built on first logprobs request
        self._decode_fn_logits = None  # built on first processor request
        self._decode_multi_fns: dict[int, callable] = {}
        self._decode_spec_fns: dict[tuple[int, bool], callable] = {}
        self._prefill_fns: dict[int, callable] = {}
        self._ring_prefill_fns: dict[int, callable] = {}
        self._embed_fns: dict[int, callable] = {}
        self._zero_embeds: dict[int, jax.Array] = {}  # per-bucket, mm only
        self.decode_steps = 0
        # What prefill launches were made of (dynamo_engine_positions,
        # dynamo_prefill_row_blocks_total): positions launched, padding
        # included, and with int4 weights the row blocks the matmul ran
        # (live) and was told to skip (padding only).
        self.prefill_positions = 0
        self.prefill_row_blocks = {"live": 0, "skipped": 0}
        # Which path the attention layers of prefill launches took
        # (dynamo_prefill_attn_launches_total) and, on the kernel's, the
        # (query block, key chunk) pairs a layer scored and skipped
        # (dynamo_prefill_attn_blocks_total), a page group apart: the
        # full group's (a model's only one, but for window layers; a
        # latent stack's goes out as group="latent") and the window
        # group's.
        self.prefill_attn_launches = {"kernel": 0, "xla": 0}
        self.prefill_attn_blocks = {"live": 0, "skipped": 0}
        self.prefill_attn_group = ("latent" if model_config.has_latent_layers
                                   else "full")
        self.prefill_attn_window_blocks = {"live": 0, "skipped": 0}
        # A model with latent attention (dynamo_latent_*): cached
        # positions its decode kernel was asked to read, and positions
        # whose keys and values prefill launches rebuilt from latents,
        # both x latent layers.
        self._latent_layers = (len(model_config.kv_layers)
                               if model_config.has_latent_layers else 0)
        self.latent_decode_tokens = 0
        self.latent_prefill_expand_tokens = 0
        # A model whose layers read pages they do not own
        # (dynamo_kv_page_layer_reads_total): active rows x layers that
        # read a page group's pages over decode steps, by whether the
        # layer wrote them (owner: `cache_plan.group_layers`) or reads
        # another layer's (shared: the rest of `group_readers`).
        plan = self.cache_plan
        self._page_readers = {
            "owner": sum(plan.group_layers),
            "shared": sum(plan.group_readers) - sum(plan.group_layers)}
        self.page_layer_reads = {"owner": 0, "shared": 0}
        # whether prefill runs a tail of layers on one position a row
        # (dynamo_prefill_cross_decoder_rows_total, the scheduler's count)
        self.runs_cross_decoder = (model_config.cross_decoder_start
                                   < model_config.n_layers)
        # A model with recurrent state (dynamo_ssm_prefill_*): valid
        # positions x state layers (Mamba-2 and short-conv alike) its
        # prefill launches carried a state over and the rows they held,
        # by whether a row began at position 0 (fresh: from zero state)
        # or took its slot's state up (continued).
        self._state_layers = len(model_config.state_layers)
        self._ssm_layers = len(model_config.ssm_layers)
        self.ssm_prefill_positions = {"fresh": 0, "continued": 0}
        self.ssm_prefill_rows = {"fresh": 0, "continued": 0}
        # and which path the Mamba layers' scan of those launches took
        # (dynamo_ssm_scan_launches_total; a model without them has none)
        self.ssm_scan_launches = {"kernel": 0, "xla": 0}
        # Launches by program (dynamo_program_launches, _tokens):
        # (entry, key) -> [launches, useful prompt tokens] of served
        # traffic; a warm-up pass lists the keys it walks at 0.
        self.program_launches: dict[tuple[str, str], list[int]] = {}
        self._warming: Optional[str] = None  # "warmup" | "prewarm"

    def _kv_cache_sharding(self, mesh: Mesh):
        """Sharding of `cache` as the step programs donate it: a pool a
        page group (K and V stacks sharded over their kv heads; a single
        latent stack has none to shard) and the replicated state. An
        int8 pool is (values, scales), whose per-token scales are
        head-shared and lane-broadcast, so replicated across tp shards."""
        pool = kv_cache_sharding(
            mesh, head_sharded=self.model_config.kv_cache_kv_dims == 2)
        if self._kv_quantized:
            pool = (pool, self._rep)
        return (tuple(pool for _ in self.cache_plan.groups), self._rep)

    def _kv_cache_init(self):
        """The program that makes a zeroed cache under `_cache_sharding`,
        run once at start and once a reshard."""
        cfg, rc, steps = self.model_config, self.config, self._steps
        pages = {"full": rc.num_pages, "window": rc.window_pages}
        if self._kv_quantized:
            from ..models.transformer import make_kv_cache_int8

            def pool(group):
                return make_kv_cache_int8(cfg, pages[group], rc.page_size)
        else:
            def pool(group):
                return make_kv_cache(cfg, pages[group], rc.page_size,
                                     group=group)

        def make():
            return (tuple(pool(group) for group in self.cache_plan.groups),
                    steps.make_state(rc.max_batch))

        return jax.jit(make, out_shardings=self._cache_sharding)

    def _count_latent_decode(self, kv_lens, active, steps: int) -> None:
        """Cached positions `steps` decode steps ask the latent kernel
        for: each active row's history, one longer a step, x layers.
        And, for a stack with layers that read pages they do not own,
        the rows x layers that read pages, owners and sharers apart."""
        if self._page_readers["shared"]:
            rows = int(np.count_nonzero(np.asarray(active, bool)))
            for by, layers in self._page_readers.items():
                self.page_layer_reads[by] += rows * steps * layers
        if self._latent_layers:
            hist = np.asarray(kv_lens, np.int64)[np.asarray(active, bool)] - 1
            self.latent_decode_tokens += int(
                steps * hist.sum() + len(hist) * steps * (steps - 1) // 2
            ) * self._latent_layers

    def _program(self, fn: str, *shape, tokens: int = 0, cause=None):
        """One launch of entry `fn`'s compiled program for the static
        `shape` (`program_key`): counted under that key unless a warm-up
        pass makes it, and whatever it builds recorded under the same
        key (`compile_scope`). `cause` names a launch no scheduler
        dispatch stands behind."""
        key = program_key(fn, *shape)
        row = self.program_launches.setdefault((fn, key), [0, 0])
        if self._warming is None:
            row[0] += 1
            row[1] += tokens
        return compile_scope(fn, key, cause=self._warming or cause)

    def prefill_attention_path(self) -> str:
        """kernel | xla | mixed: where the attention layers of this
        runner's prefill programs run, over its buckets
        (`prefill_attention_tiles`; the shapes decide, program by
        program), the full page group's and then `+` the window
        group's where the model has one; custom for a caller's own
        function, none for a stack without attention layers."""
        cfg = self.model_config
        if not (cfg.kv_layers or cfg.window_kv_layers):
            return "none"
        if self._user_attention_fn is not None:
            return "custom"

        def path(group: str) -> str:
            took = {bool(self.prefill_attention_tiles(bucket,
                                                      group == "window"))
                    for bucket in self.config.prefill_buckets}
            return ("mixed" if len(took) > 1
                    else "kernel" if took == {True} else "xla")

        return "+".join(path(group) for group in self.cache_plan.groups)

    def _table_widths(self, tables) -> tuple:
        """A decode program's key parts for its tables (`_table_args`):
        the width each page group's was traced at (`w64`; a second
        group's `ww72`)."""
        groups = len(self.cache_plan.groups)
        return tuple(f"{'w' * (i + 1)}{table.shape[-1]}"
                     for i, table in enumerate(tables[:groups]))

    @contextlib.contextmanager
    def _warm(self, cause: str):
        """Launches inside are a warm-up pass's: built, not counted."""
        prev, self._warming = self._warming, self._warming or cause
        try:
            yield
        finally:
            self._warming = prev

    def prefill_attention_tiles(self, bucket: int, window: bool = False):
        """(query positions a block, key tokens a chunk) where the
        attention layers of a `bucket`-position prefill launch run the
        blocked kernel, None where they run in XLA: `paged_attention`'s
        own rule on the shapes it will be handed (for latent layers
        `paged_attention_latent`'s), and only where the step program
        hands them to it (the default `attention_fn`). `window`: the
        window layers of a model that has them, over their own page
        group's table (`window_prefill_width`); else the full group's
        layers."""
        cfg, rc = self.model_config, self.config
        if (self._user_attention_fn is not None
                or self._steps.attention_fn is None
                or ("window" if window else "full")
                not in self._steps.attention_groups):
            return None
        from ..ops import kernel_path
        from ..ops.paged_attention import (
            latent_prefill_tiles,
            prefill_kernel_tiles,
        )

        if cfg.has_latent_layers:
            return latent_prefill_tiles(
                bucket, cfg.mla_nope_head_dim, cfg.mla_v_head_dim,
                cfg.mla_kv_lora_rank, cfg.kv_cache_head_dim, rc.page_size,
                rc.max_pages_per_seq, cfg.dtype,
                kernel_path("DYNT_ATTENTION") == "interpret")
        return prefill_kernel_tiles(
            bucket, *cfg.attn_geometry, rc.page_size,
            (self.window_prefill_width(bucket) if window
             else rc.max_pages_per_seq),
            jnp.int8 if self._kv_quantized else cfg.dtype,
            KV_SCALE_LANES if self._kv_quantized else None)

    def ssm_scan_tiles(self, bucket: int):
        """Heads a grid step where the Mamba layers of a `bucket`-position
        prefill launch run the chunked-scan kernel, None where they run
        the XLA form (or the model has none): `mamba_prefill`'s rule."""
        if not self._ssm_layers:
            return None
        from ..models.hybrid import scan_head_block

        return scan_head_block(self.model_config, bucket,
                               self._steps.ssm_path)

    def _count_prefill(self, starts: Sequence[int], lengths: Sequence[int],
                       rows: int, bucket: int, windows=()) -> None:
        """Host arithmetic on a launch's own positions (row i holds
        `lengths[i]` of them from `starts[i]`), no device sync. `windows`:
        each row's (window table, base) where the model has window
        layers."""
        self.prefill_positions += rows * bucket
        if self._state_layers:
            for start, length in zip(starts, lengths):
                carry = "continued" if start else "fresh"
                self.ssm_prefill_positions[carry] += (length
                                                      * self._state_layers)
                self.ssm_prefill_rows[carry] += 1
        if self._ssm_layers:
            self.ssm_scan_launches[
                "kernel" if self.ssm_scan_tiles(bucket) else "xla"] += 1
        if self.config.weight_dtype == "int4":
            from ..ops.q4_linear import count_row_blocks

            live, skipped = count_row_blocks(lengths, rows, bucket)
            self.prefill_row_blocks["live"] += live
            self.prefill_row_blocks["skipped"] += skipped
        from ..ops.paged_attention import count_prefill_blocks

        windowed = "window" in self.cache_plan.groups
        tiles = self.prefill_attention_tiles(bucket)
        win_tiles = (self.prefill_attention_tiles(bucket, window=True)
                     if windowed else tiles)
        # a launch is the kernel's where every page group's layers are
        self.prefill_attn_launches[
            "kernel" if tiles and win_tiles else "xla"] += 1
        ends = [s + n for s, n in zip(starts, lengths)]
        rebuilt = sum(ends)  # the XLA form: every row's context, once
        if tiles:
            live, skipped = count_prefill_blocks(
                starts, ends, rows, bucket, *tiles, self.config.max_context)
            self.prefill_attn_blocks["live"] += live
            self.prefill_attn_blocks["skipped"] += skipped
            # the kernel: a key chunk for every query block that sees it
            rebuilt = live * tiles[1]
        self.latent_prefill_expand_tokens += rebuilt * self._latent_layers
        if windowed and win_tiles:
            # the window group's frame: positions from each row's base
            frame = [s - w[1] for s, w in zip(starts, windows)]
            live, skipped = count_prefill_blocks(
                frame, [s + n for s, n in zip(frame, lengths)], rows,
                bucket, *win_tiles,
                self.window_prefill_width(bucket) * self.config.page_size,
                self.model_config.sliding_window)
            self.prefill_attn_window_blocks["live"] += live
            self.prefill_attn_window_blocks["skipped"] += skipped

    def _check_plan(self, mesh: Mesh) -> None:
        """What this model's cache and stack cannot run with, refused
        when the runner is built, by name: never a wrong answer later.
        (The worker refuses the same by flag before a process starts:
        `engine.worker.recurrent_state_refusals`.)"""
        cfg, rc, plan = self.model_config, self.config, self.cache_plan
        for asked, why in (
                (f"weight_dtype={rc.weight_dtype!r}",
                 rc.weight_dtype != "model" and plan.quantized_weights),
                (f"kv_dtype={rc.kv_dtype!r}",
                 rc.kv_dtype != "model" and plan.int8_pool),
                (f"a mesh of {mesh.devices.size} devices",
                 mesh.devices.size > 1 and plan.shard)):
            if why:
                raise ValueError(f"{asked}: {why}")
        if rc.max_loras and not self._steps.lora:
            raise ValueError(f"--max-loras: no adapter targets on {cfg.name} "
                             f"(layers {cfg.layer_pattern})")
        if "window" in plan.groups:
            need = -(-cfg.sliding_window // rc.page_size) + 2
            if rc.window_pages <= need:
                raise ValueError(
                    f"--window-pages {rc.window_pages}: {cfg.name} has "
                    f"window layers (window {cfg.sliding_window}) and "
                    f"needs a second page group of more than {need} pages "
                    "(one decoding row's)")
            if cfg.sliding_window % rc.page_size:
                raise ValueError(
                    f"--page-size {rc.page_size} does not divide the "
                    f"window {cfg.sliding_window} of {cfg.name}")

    # -- the window group's tables (a model with window layers) ------------

    @property
    def window_table_width(self) -> int:
        """Columns of the window group's table in a decode program: a
        decoding row's pages (engine/pages.py `bound`) and room to
        a multiple of 8."""
        blocks = self.model_config.sliding_window // self.config.page_size
        return -(-(blocks + 3) // 8) * 8

    def window_prefill_width(self, bucket: int) -> int:
        """The same for a prefill program of `bucket` positions a row:
        window + chunk keys, never the full layers' table, and room to
        whole key chunks of the prefill kernel, which admits no other
        width (`ops.paged_attention.prefill_table_pages`)."""
        from ..ops.paged_attention import prefill_table_pages

        blocks = -(-(self.model_config.sliding_window + bucket)
                   // self.config.page_size) + 1
        return prefill_table_pages(blocks, self.config.page_size)

    def _table_args(self, block_tables) -> tuple:
        """Block tables as a step program takes them, always a tuple of
        int32 arrays: `(full,)`, or for a model with a window group
        (full tables, window tables, window base [B]). A caller with one
        group may hand its one array in bare."""
        if not isinstance(block_tables, tuple):
            block_tables = (block_tables,)
        return tuple(jnp.asarray(t, jnp.int32) for t in block_tables)

    def _idle_tables(self, rows: int, width: int) -> tuple:
        """All-scratch tables of `rows` x `width` (warm-up launches): a
        second group's at its decode width, with its base."""
        tables = [np.zeros((rows, width), np.int32)]
        if "window" in self.cache_plan.groups:
            tables += [np.zeros((rows, self.window_table_width), np.int32),
                       np.zeros(rows, np.int32)]
        return tuple(tables)

    def _launch(self, fn, args, kwargs=None, phase: str = "decode"):
        """Run one compiled step, every one (params, cache, tokens,
        positions, tables, ...) -> (cache, <sampled>, stats). The cache
        goes in donated behind the params and comes back first; the last
        output is the experts' statistics (None where the stack has no
        dropless experts), kept on the device until ready."""
        self.cache, *rest, stats = fn(self.params, self.cache, *args,
                                      **(kwargs or {}))
        if stats is not None:
            self._moe_pending.append((MOE_PHASES.index(phase), stats))
        return rest

    def _outs(self, *rest):
        """out_shardings of a compiled step: the cache it took donated,
        `rest`, and the experts' statistics. Read off the runner at
        build time, since `reshard` replaces the shardings."""
        return (self._cache_sharding, *rest, self._rep)

    def moe_stats(self):
        """By phase (a row of MOE_PHASES each): tokens each held expert
        has computed, slots dropped (always 0: the layer is dropless),
        held experts touched and expert-layer calls, summed over launches
        whose results have landed. Never waits for the device. None for
        a model without routed dropless experts."""
        if self.moe_counts is None:
            return None
        while self._moe_pending and self._moe_pending[0][1].is_ready():
            phase, stats = self._moe_pending.pop(0)
            self.moe_counts[phase] += np.asarray(stats)  # dynalint: disable=DL201 -- is_ready() above: nothing to wait for; a few dozen ints per launch # dynajit: disable=DJ201 -- same: the launch has completed
        return self.moe_counts

    def _init_random_params(self, seed: int) -> dict:
        """`init_params` from the seed (same values), built and — for
        quantized weights — quantized one layer per dispatch. As one
        program a 7B spends minutes in XLA compiling 32 unrolled layers
        of init + quantize; per layer it compiles one layer-sized
        program per layer kind, and never more than one layer's bf16
        weights is live beside the quantized tree."""
        from ..models.transformer import init_layer_params, init_top_params

        cfg = self.model_config
        quantize = (self._quantize_params_fn() if self._weight_quantized
                    else lambda tree, _cfg: tree)
        shard = self._param_sharding

        def top_init_fn():
            def init_top(k_embed, k_head):
                tree = quantize({**init_top_params(k_embed, k_head, cfg),
                                 "layers": []}, cfg)
                return {k: v for k, v in tree.items() if k != "layers"}

            return jax.jit(init_top, out_shardings={
                k: v for k, v in shard.items() if k != "layers"})

        def layer_init_fn(i: int):
            return jax.jit(
                lambda k, *gain: quantize(
                    {"layers": [init_layer_params(k, cfg, i, *gain)]},
                    cfg)["layers"][0],
                out_shardings=shard["layers"][i])

        gains = [1.0] * cfg.n_layers
        if cfg.is_hybrid:
            from ..models.hybrid import branch_gain

            gains = [branch_gain(cfg, i) for i in range(cfg.n_layers)]
        keys = jax.random.split(jax.random.PRNGKey(seed), cfg.n_layers + 2)
        params = top_init_fn()(keys[0], keys[-1])
        if cfg.layer_sections:
            # a stack with rolled sections: an entry of the `layers` list
            # a launch, a rolled entry's mixers drawn stacked; ONE
            # program for the entries of a kind and a number of repeats
            # (the keys and gains of its mixers are operands)
            from ..models.hybrid import init_hybrid_entry

            def entry_init_fn(e: int, entry):
                return jax.jit(
                    lambda k, g: init_hybrid_entry(k, cfg, entry, g),
                    out_shardings=shard["layers"][e])

            gains = jnp.asarray(gains, jnp.float32)
            entry_fns: dict = {}
            params["layers"] = []
            for e, entry in enumerate(cfg.layer_entries):
                first, repeats, stride = entry
                at = first + stride * np.arange(repeats)
                kind = (cfg.layer_kind(first), repeats)
                if kind not in entry_fns:
                    entry_fns[kind] = entry_init_fn(e, entry)
                params["layers"].append(
                    entry_fns[kind](keys[at + 1], gains[at]))
            return params
        layer_fns: dict = {}  # one program per layer kind
        params["layers"] = []
        for i in range(cfg.n_layers):
            kind = cfg.layer_kind(i) if cfg.is_hybrid else cfg.layer_is_moe(i)
            if kind not in layer_fns:
                layer_fns[kind] = layer_init_fn(i)
            # a recipe whose residual writers grow with depth (a tied
            # hybrid head: `branch_gain`) hands the layer's gain in as
            # an operand; every other model's program takes the key alone
            gain = () if gains[i] == 1.0 else (jnp.float32(gains[i]),)
            params["layers"].append(layer_fns[kind](keys[i + 1], *gain))
        return params

    def kernel_paths(self) -> dict:
        """Which implementation each hot-path slot resolved to, in
        ops.kernel_path's vocabulary (pallas | interpret | xla), plus
        the devices it runs on — what the worker states at start-up and
        `chip_smoke.py` checks, so a reference kernel or the interpreter
        can never serve unnoticed. Unquantized weights have one
        implementation (`einsum`); `custom` is a caller-supplied
        attention_fn. The model's adapter adds its stack's own slots
        (`models.hybrid.HybridSteps.kernel_paths`)."""
        from ..ops import kernel_path

        def attention(fn) -> str:
            if self._user_attention_fn is not None:
                return "custom"
            return "xla" if fn is None else kernel_path("DYNT_ATTENTION")

        paths = {
            "decode_attention": attention(self._steps.decode_attention_fn),
            "spec_attention": attention(self._steps.spec_attention_fn),
            "prefill_attention": self.prefill_attention_path(),
            "weight_matmul": "einsum",
        }
        if self.config.weight_dtype == "int8":
            paths["weight_matmul"] = kernel_path("DYNT_Q8_MATMUL")
        elif self.config.weight_dtype == "int4":
            paths["weight_matmul"] = kernel_path("DYNT_Q4_MATMUL")
        paths.update(self._steps.kernel_paths())
        devices = list(self.mesh.devices.flat)
        paths["platform"] = devices[0].platform
        paths["device_kind"] = devices[0].device_kind
        paths["device_ids"] = [int(d.id) for d in devices]
        return paths

    # -- compiled step builders -------------------------------------------

    def _quantize_params_fn(self):
        """Device-side weight-quantize transform for the configured
        weight_dtype (models/quantize.py)."""
        if self.config.weight_dtype == "int4":
            from ..models.quantize import quantize_params_int4

            return quantize_params_int4
        from ..models.quantize import quantize_params_int8

        return quantize_params_int8

    def _quantize_axes(self, axes, model_config):
        if self.config.weight_dtype == "int4":
            from ..models.quantize import quantize_param_axes_q4

            return quantize_param_axes_q4(axes, model_config)
        from ..models.quantize import quantize_param_axes

        return quantize_param_axes(axes, model_config)

    def _build_decode(self, with_logprobs: bool = False,
                      with_logits: bool = False):
        one = self._steps.decode

        def step(params, cache, tokens, positions, tables, kv_lens,
                 active, temperature, top_p, top_k, seeds, step_idx,
                 lora=None, lora_idx=None):
            # step_idx: [B] per-slot generated-token index, so a fixed
            # request seed reproduces its stream independent of what other
            # requests the engine is running.
            cache, logits, stats = one(params, cache, tokens, positions,
                                       tables, kv_lens, active, lora,
                                       lora_idx)
            if with_logits:
                # Logits-processor escape hatch: ship the raw rows to
                # host alongside the device-sampled tokens; the scheduler
                # re-samples processor slots on host. Costs a [B, V] f32
                # readback — paid only by steps whose batch contains a
                # processor request.
                next_tokens = sample(
                    logits[:, 0, :], temperature, top_p, top_k, seeds,
                    step_idx)
                return (cache, next_tokens,
                        logits[:, 0, :].astype(jnp.float32), stats)
            if with_logprobs:
                next_tokens, lp, top_ids, top_lps = sample_with_logprobs(
                    logits[:, 0, :], temperature, top_p, top_k, seeds,
                    step_idx)
                return (cache, next_tokens, lp, top_ids, top_lps, stats)
            # Hot path: no full-vocab log_softmax/top_k and only [B] int32
            # crosses device->host (the per-token latency discipline,
            # SURVEY section 7).
            next_tokens = sample(
                logits[:, 0, :], temperature, top_p, top_k, seeds, step_idx)
            return (cache, next_tokens, stats)

        n_rest = 2 if with_logits else 4 if with_logprobs else 1
        return jax.jit(step, donate_argnums=(1,),
                       out_shardings=self._outs(*[self._rep] * n_rest))

    def _build_decode_multi(self, k: int):
        """K decode steps inside ONE jit call via lax.scan: a single
        host<->device round trip produces K tokens per slot. This is the
        TPU answer to per-token dispatch latency (multi-step scheduling in
        vLLM terms): it removes K-1 host syncs per block. The whole cache
        (a model's per-slot state with its pools) rides the carry."""
        one = self._steps.decode
        size = self._steps.stats_size

        def multi(params, cache, tokens, positions, tables, kv_lens,
                  active, temperature, top_p, top_k, seeds, step_idx,
                  lora=None, lora_idx=None):
            def body(carry, _):
                cache, toks, pos, lens, sidx, acc = carry
                cache, logits, stats = one(params, cache, toks, pos,
                                           tables, lens, active, lora,
                                           lora_idx)
                nxt = sample(logits[:, 0, :], temperature, top_p, top_k,
                             seeds, sidx)
                acc = None if stats is None else acc + stats
                return (cache, nxt, pos + 1, lens + 1, sidx + 1, acc), nxt

            acc0 = jnp.zeros(size, jnp.int32) if size else None
            (cache, *_, acc), toks_k = jax.lax.scan(
                body, (cache, tokens, positions, kv_lens, step_idx, acc0),
                None, length=k)
            return cache, toks_k, acc  # toks_k: [K, B]

        return jax.jit(multi, donate_argnums=(1,),
                       out_shardings=self._outs(self._rep))

    def _decode_args(self, tokens, positions, block_tables, kv_lens, active,
                     temperature, top_p, top_k, seeds, steps, lora_idx):
        """What every decode program takes behind the params and the
        cache: `tokens` and `positions` already on the device, the rest
        placed here; the adapter pack and each slot's adapter where the
        runner holds one."""
        rows = len(kv_lens)
        if steps is None:
            steps = np.zeros(rows, np.int32)
        args = [
            tokens, positions, self._table_args(block_tables),
            jnp.asarray(kv_lens, jnp.int32), jnp.asarray(active, bool),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32), jnp.asarray(top_k, jnp.int32),
            jnp.asarray(seeds, jnp.uint32), jnp.asarray(steps, jnp.int32),
        ]
        if self.lora_pack is not None:
            if lora_idx is None:
                lora_idx = np.zeros(rows, np.int32)
            args += [self.lora_pack, jnp.asarray(lora_idx, jnp.int32)]
        return args

    def decode_multi(
        self,
        tokens: np.ndarray,  # [B] last token per slot
        positions: np.ndarray,  # [B] position of that token
        block_tables: np.ndarray,
        kv_lens: np.ndarray,  # [B] kv length INCLUDING the current token
        active: np.ndarray,
        temperature: np.ndarray,
        top_p: np.ndarray,
        top_k: np.ndarray,
        seeds: np.ndarray,
        steps: Optional[np.ndarray] = None,
        k: int = 8,
        lora_idx: Optional[np.ndarray] = None,
        return_device: bool = False,
    ) -> np.ndarray:
        """K chained decode steps in one call; returns tokens [K, B].
        Callers must guarantee every active slot has >= k tokens of page
        budget left (the block table is written k rows forward).

        `return_device=True` skips the host readback and returns the
        device array — the scheduler's pipelined double-block dispatch
        feeds `toks[-1]` straight into the next block so the second
        dispatch never waits on the first readback (dispatch/readback
        latency hiding)."""
        self.decode_steps += k
        self._count_latent_decode(kv_lens, active, k)
        fn = self._decode_multi_fns.get(k)
        if fn is None:
            fn = self._build_decode_multi(k)
            self._decode_multi_fns[k] = fn  # dynajit: disable=DJ103 -- k is DYNT_DECODE_BLOCK, a deployment constant (one value per process; reshard resets the dict)
        args = self._decode_args(
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), block_tables, kv_lens,
            active, temperature, top_p, top_k, seeds, steps, lora_idx)
        # tokens from the host or from the block before, still on the
        # device (the pipelined second block): two jit keys a width
        fed = "chained" if isinstance(tokens, jax.Array) else "fed"
        with self._program("decode_multi", *self._table_widths(args[2]),
                           f"b{k}", fed):
            (toks_k,) = self._launch(fn, args)
        self.last_decode_sample = (None, None, None)
        if return_device:
            return toks_k
        return np.asarray(toks_k)  # dynajit: disable=DJ201 -- the fused block's one designed drain (callers pipeline via return_device)

    @property
    def supports_spec(self) -> bool:
        """Whether this runner can run speculative batched verification:
        whether the model's adapter has a step that scores several
        positions a slot (`DenseSteps.spec`: standard attention and no
        injected kernel)."""
        return self._steps.spec is not None

    def _build_decode_spec(self, t: int, with_logits: bool = False):
        """Speculative batched verification: ONE forward scores t chunk
        positions per slot (token 0 = the last committed token, tokens
        1..t-1 = the draftless proposals) against the paged KV, then
        `sampler.spec_verify` draws the per-position target tokens with
        the exact (seed, step) keys sequential decode would use and
        accepts the longest matching draft prefix. The weight stream —
        the memory-bound cost of a decode step — is paid once for up to
        t committed tokens. `with_logits` additionally ships the raw
        [B, t, V] rows to host for the logits-processor verification leg
        (scheduler._drain_spec applies processors per position there)."""
        from .sampler import spec_verify

        score = self._steps.spec
        assert score is not None, "this stack scores one position a step"

        def step(params, cache, tokens, positions, tables, kv_lens,
                 active, temperature, top_p, top_k, seeds, step_idx,
                 lora=None, lora_idx=None):
            cache, logits = score(params, cache, tokens, positions, tables,
                                  kv_lens, active, lora, lora_idx)
            targets, n_accept = spec_verify(
                logits, tokens[:, 1:], temperature, top_p, top_k, seeds,
                step_idx)
            if with_logits:
                return (cache, targets, n_accept,
                        logits.astype(jnp.float32), None)
            return cache, targets, n_accept, None

        return jax.jit(step, donate_argnums=(1,), out_shardings=self._outs(
            *[self._rep] * (3 if with_logits else 2)))

    def decode_spec(
        self,
        tokens: np.ndarray,  # [B] last committed token per slot
        drafts: np.ndarray,  # [B, K] proposed continuations (0-padded)
        positions: np.ndarray,  # [B] position of the committed token
        block_tables: np.ndarray,
        kv_lens: np.ndarray,  # [B] committed length INCLUDING the token
        active: np.ndarray,
        temperature: np.ndarray,
        top_p: np.ndarray,
        top_k: np.ndarray,
        seeds: np.ndarray,
        steps: Optional[np.ndarray] = None,
        lora_idx: Optional[np.ndarray] = None,
        want_logits: bool = False,
        return_device: bool = False,
    ):
        """One speculative verification step. Returns (targets [B, K+1],
        n_accept [B]); callers commit targets[b, : n_accept[b] + 1] —
        bit-identical to what K+1 sequential decode steps would emit for
        the accepted prefix. With `want_logits`, raw logits rows land in
        `last_spec_logits` [B, K+1, V] for host-side processor slots.
        `return_device=True` skips the readbacks (the scheduler drains
        them after overlapping prefill/admission work)."""
        b, k = drafts.shape
        t = k + 1
        self.decode_steps += 1
        fn = self._decode_spec_fns.get((t, want_logits))
        if fn is None:
            fn = self._build_decode_spec(t, want_logits)
            self._decode_spec_fns[(t, want_logits)] = fn
        chunk = np.concatenate(
            [np.asarray(tokens, np.int32)[:, None],
             np.asarray(drafts, np.int32)], axis=1)
        pos2 = (np.asarray(positions, np.int32)[:, None]
                + np.arange(t, dtype=np.int32)[None, :])
        args = self._decode_args(
            jnp.asarray(chunk), jnp.asarray(pos2), block_tables, kv_lens,
            active, temperature, top_p, top_k, seeds, steps, lora_idx)
        shape = (*self._table_widths(args[2]), f"k{k}",
                 "logits" if want_logits else "")
        with self._program("decode_spec", *shape):
            targets, n_accept, *logits = self._launch(fn, args)
        self.last_spec_logits = logits[0] if logits else None
        if return_device:
            return targets, n_accept
        if logits:
            self.last_spec_logits = np.asarray(logits[0])  # dynajit: disable=DJ201 -- processor-slot raw rows; paid only by want_logits steps
        return np.asarray(targets), np.asarray(n_accept)  # dynajit: disable=DJ201 -- the spec step's designed drain (scheduler defers via return_device)

    def _build_prefill(self, bucket: int):
        chunk = self._steps.prefill

        def step(params, cache, tokens, positions, tables, kv_lens, valid,
                 last_idx, temperature, top_p, top_k, seeds, slots=None,
                 lora=None, lora_idx=None, extra_embeds=None, window=()):
            # `window`: a second page group's (tables, base), handed in
            # behind every other argument and not beside `tables`, where
            # these programs have always taken them: the order of a
            # program's parameters is part of its compiled text.
            cache, last, stats = chunk(
                params, cache, tokens, positions, (*tables, *window),
                kv_lens, valid, last_idx, slots, lora, lora_idx,
                extra_embeds)
            # Unconditional here, unlike decode: one [1, V] log_softmax per
            # CHUNK is noise next to the chunk forward, and the extra host
            # transfer is a handful of floats. Decode pays this per token,
            # hence its gated _decode_fn/_decode_fn_lp split.
            token, lp, top_ids, top_lps = sample_with_logprobs(
                last, temperature, top_p, top_k, seeds, jnp.int32(0))
            return (cache, token, lp, top_ids, top_lps, stats)

        return jax.jit(step, donate_argnums=(1,),
                       out_shardings=self._outs(*[self._rep] * 4))

    @property
    def sp_size(self) -> int:
        return self.mesh.shape.get(AXIS_SP, 1)

    def _build_ring_prefill(self, bucket: int):
        """Sequence-parallel prefill: the whole prompt in ONE step with the
        sequence sharded over sp and ring attention across the ring
        (ops/ring_attention.py). Scales max prefill length by sp without
        ever materializing full attention on one chip."""
        cfg = self.model_config
        mesh = self.mesh
        from jax import shard_map

        from ..ops.ring_attention import ring_attention

        s_q = P(None, AXIS_SP, AXIS_TP, None)  # [B, T, heads, hd]
        s_p = P(None, AXIS_SP)  # [B, T]
        ring_fn = shard_map(
            lambda *a: ring_attention(*a, axis_name=AXIS_SP),
            mesh=mesh,
            in_specs=(s_q, s_q, s_q, s_p, s_p, s_p),
            out_specs=s_q,
        )

        def step(params, cache, tokens, positions, valid, block_table,
                 last_idx, temperature, top_p, top_k, seeds):
            (kv,), state = cache
            logits, ks, vs = forward_ring(params, cfg, tokens, positions,
                                          valid, ring_fn)
            kv = write_kv_stack(kv, ks, vs, block_table, positions, valid)
            last = jnp.take_along_axis(
                logits, last_idx[:, None, None], axis=1
            )[:, 0, :]
            token, lp, top_ids, top_lps = sample_with_logprobs(
                last, temperature, top_p, top_k, seeds, jnp.int32(0))
            return ((kv,), state), token, lp, top_ids, top_lps, None

        return jax.jit(step, donate_argnums=(1,),
                       out_shardings=self._outs(*[self._rep] * 4))

    def prefill_ring_batch(
        self,
        prompts: list,  # B arrays [t_i] — FULL prompts (start position 0)
        block_tables: np.ndarray,  # [B, max_pages_per_seq] int32
        samplings: list,  # B tuples (temp, top_p, top_k, seed)
    ) -> list[int]:
        """Sequence-parallel prefill of a BATCH of long prompts in one ring
        step: [B, bucket] with per-row validity masks, sequence axis
        sharded over sp (was one-sequence-per-call — VERDICT r2 weak #4,
        long-prompt pools couldn't batch). Returns the first sampled token
        per sequence; per-sequence logprob info lands in
        `last_prefill_samples` (list parallel to prompts). Requires an
        sp>1 mesh."""
        b = len(prompts)
        assert b >= 1 and len(samplings) == b
        sp = self.sp_size
        assert sp > 1, "prefill_ring needs an sp>1 mesh"
        t_max = max(len(p) for p in prompts)
        bucket = self._bucket_for(t_max)
        if bucket < t_max:
            # Ring prompts are longer than the largest chunk bucket by
            # definition (the scheduler routes here when prompt_len >
            # max_prefill_chunk); size to the prompt, power-of-two so jit
            # specializations stay finite.
            bucket = 1 << (t_max - 1).bit_length()
        # each sp shard needs an equal slice
        if bucket % sp:
            bucket += sp - bucket % sp
        fn = self._ring_prefill_fns.get(bucket)
        if fn is None:
            fn = self._build_ring_prefill(bucket)
            self._ring_prefill_fns[bucket] = fn
        self.prefill_positions += b * bucket  # the ring passes no block map
        tok = np.zeros((b, bucket), np.int32)
        pos = np.zeros((b, bucket), np.int32)
        valid = np.zeros((b, bucket), bool)
        last_idx = np.zeros(b, np.int32)
        for i, prompt in enumerate(prompts):
            t = len(prompt)
            tok[i, :t] = prompt
            # Padding positions run past the end so write_kv_stack drops
            # them onto the scratch page (their valid=False rows never
            # land in real slots).
            pos[i] = np.arange(bucket)
            valid[i, :t] = True
            last_idx[i] = t - 1
        temp = np.asarray([s[0] for s in samplings], np.float32)
        top_p = np.asarray([s[1] for s in samplings], np.float32)
        top_k = np.asarray([s[2] for s in samplings], np.int32)
        seeds = np.asarray([s[3] for s in samplings], np.uint32)
        with self._program("prefill_ring", f"{b}x{bucket}",
                           tokens=sum(len(p) for p in prompts)):
            token, lp, top_ids, top_lps = self._launch(fn, [
                jnp.asarray(tok), jnp.asarray(pos),
                jnp.asarray(valid), jnp.asarray(block_tables, jnp.int32),
                jnp.asarray(last_idx),
                jnp.asarray(temp), jnp.asarray(top_p),
                jnp.asarray(top_k), jnp.asarray(seeds),
            ], phase="prefill")
        lp_h = np.asarray(lp)  # dynajit: disable=DJ201 -- ring prefill ends the prompt pass; its sample drain is the step boundary
        ids_h = np.asarray(top_ids)  # dynajit: disable=DJ201 -- same ring-prefill drain
        lps_h = np.asarray(top_lps)  # dynajit: disable=DJ201 -- same ring-prefill drain
        self.last_prefill_samples = [
            (float(lp_h[i]), ids_h[i], lps_h[i]) for i in range(b)
        ]
        self.last_prefill_sample = self.last_prefill_samples[0]
        return [int(t) for t in np.asarray(token)]  # dynajit: disable=DJ201 -- same ring-prefill drain (first tokens)

    def prefill_ring(
        self,
        tokens: np.ndarray,  # [t] the FULL prompt (start position 0)
        block_table: np.ndarray,  # [max_pages_per_seq] int32
        sampling: tuple[float, float, int, int],
    ) -> int:
        """Single-sequence sequence-parallel prefill (B=1 wrapper around
        prefill_ring_batch)."""
        return self.prefill_ring_batch(
            [np.asarray(tokens, np.int32)],
            np.asarray(block_table, np.int32)[None, :],
            [sampling],
        )[0]

    def embed(self, tokens: np.ndarray) -> np.ndarray:
        """Pooled, L2-normalized embedding of a token sequence [H] float32
        (ref surface: /v1/embeddings). No KV cache involvement, so safe to
        serialize with engine steps via run_in_step."""
        from ..models import forward_embed

        t = len(tokens)
        if t > self.config.prefill_buckets[-1]:
            raise ValueError(
                f"embedding input of {t} tokens exceeds the engine's max "
                f"sequence bucket ({self.config.prefill_buckets[-1]})")
        bucket = self._bucket_for(t)
        fn = self._embed_fns.get(bucket)
        if fn is None:
            cfg = self.model_config
            fn = jax.jit(partial(forward_embed, config=cfg),
                         static_argnames=(), out_shardings=self._rep)
            self._embed_fns[bucket] = fn
        tok = np.zeros((1, bucket), np.int32)
        tok[0, :t] = tokens
        valid = np.zeros((1, bucket), bool)
        valid[0, :t] = True
        with self._program("embed", bucket, cause="embed"):
            out = fn(self.params, tokens=jnp.asarray(tok),
                     valid=jnp.asarray(valid))
        return np.asarray(out)[0]

    def _bucket_for(self, n: int) -> int:
        for b in self.config.prefill_buckets:
            if n <= b:
                return b
        return self.config.prefill_buckets[-1]

    @property
    def max_prefill_chunk(self) -> int:
        return self.config.prefill_buckets[-1]

    @property
    def max_prefill_rows(self) -> int:
        """Rows a prefill launch may hold: each is padded to the smallest
        bucket at least, so more of them make a tile past the token
        budget whatever their lengths (and a row of programs more)."""
        buckets = self.config.prefill_buckets
        return max(1, buckets[-1] // buckets[0])

    @property
    def bounds_prefill_launches(self) -> bool:
        """Whether a prefill launch's rows x bucket must stay inside the
        token budget (`prefill_launch_fits`), by the cache plan's
        `launch_bound`: always for latent layers, whose prefill
        attention scores a launch's positions against wide tables in
        float32, and window layers beside full ones, whose XLA form
        does; and for state carried from launch to launch, where a
        context runs past one launch (a model whose contexts fit one
        launch keeps its rows). On the chip the window groups and the
        scan run kernels that hold no such scores; the bound stays
        because a grid of rows x bucket past the budget is programs no
        launch needs, and what a launch holds in temporaries, the routed
        experts' float32 rows, grows with its positions (ROADMAP A7)."""
        bound = self.cache_plan.launch_bound
        return bound == "always" or (
            bound == "carried"
            and self.config.max_context > self.config.prefill_buckets[-1])

    def prefill_launch_fits(self, lengths: Sequence[int]) -> bool:
        """`bounds_prefill_launches`: whether rows of these chunk
        lengths make a launch (rows to a power of two x the longest's
        bucket) of no more positions than the token budget. Its tables
        are wide, so padding past the budget is paid in gathered keys,
        and each rows x bucket shape is a program `--prewarm full`
        compiles."""
        rows = 1 << max(0, len(lengths) - 1).bit_length()
        return (rows * self._bucket_for(max(lengths))
                <= self.config.prefill_buckets[-1])

    def _window_rows(self, bucket: int, rows: int, windows) -> tuple:
        """Each row's (window table, base) as the prefill program's
        `window` argument: tables padded to the bucket's width with the
        scratch page. Empty where the cache has one page group."""
        if "window" not in self.cache_plan.groups:
            return ()
        width = self.window_prefill_width(bucket)
        tables = np.zeros((rows, width), np.int32)
        base = np.zeros(rows, np.int32)
        for i, (table, first_token) in enumerate(windows):
            tables[i, :len(table)] = table
            base[i] = first_token
        return jnp.asarray(tables), jnp.asarray(base)

    # -- host API ----------------------------------------------------------

    def _prefill(self, entry: str, rows: Sequence[PrefillRow], b: int,
                 embeds: Optional[np.ndarray] = None):
        """Pack `rows` into one [b, bucket] launch of the bucket's
        prefill program and make it under `entry`'s program key. Rows
        past `len(rows)` write into the page-0 scratch sink with an
        all-False valid mask and a state slot past the end, the same
        padding contract a row's token tail has. `embeds` [t, H]: splice
        rows of a lone row's chunk (a multimodal engine)."""
        bucket = self._bucket_for(max(len(r.tokens) for r in rows))
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            fn = self._build_prefill(bucket)
            self._prefill_fns[bucket] = fn
        tok = np.zeros((b, bucket), np.int32)
        pos = np.zeros((b, bucket), np.int32)
        valid = np.zeros((b, bucket), bool)
        tables = np.zeros((b, len(rows[0].table)), np.int32)
        kv_lens = np.zeros(b, np.int32)
        last_idx = np.zeros(b, np.int32)
        temp = np.zeros(b, np.float32)
        top_p = np.ones(b, np.float32)
        top_k = np.zeros(b, np.int32)
        seeds = np.zeros(b, np.uint32)
        lora_rows = np.zeros(b, np.int32)
        slots = np.full(b, self.config.max_batch, np.int32)
        for i, row in enumerate(rows):
            t = len(row.tokens)
            tok[i, :t] = row.tokens
            pos[i, :t] = np.arange(row.start, row.start + t)
            valid[i, :t] = True
            tables[i] = row.table
            kv_lens[i] = row.kv_len_after
            last_idx[i] = t - 1
            temp[i], top_p[i], top_k[i], seeds[i] = row.sampling
            lora_rows[i] = row.lora_idx
            if row.slot is not None:
                slots[i] = row.slot
        windows = [r.window for r in rows]
        self._count_prefill([r.start for r in rows],
                            [len(r.tokens) for r in rows], b, bucket, windows)
        args = [
            jnp.asarray(tok), jnp.asarray(pos),
            (jnp.asarray(tables),), jnp.asarray(kv_lens), jnp.asarray(valid),
            jnp.asarray(last_idx), jnp.asarray(temp), jnp.asarray(top_p),
            jnp.asarray(top_k), jnp.asarray(seeds),
        ]
        # Optional features pass by KEYWORD: with lora disabled, a
        # positional embeds array would silently bind to the `lora`
        # parameter and the splice would never happen.
        kwargs: dict = {
            "slots": jnp.asarray(slots),
            "window": self._window_rows(bucket, b, windows)}
        if self.lora_pack is not None:
            kwargs["lora"] = self.lora_pack
            kwargs["lora_idx"] = jnp.asarray(lora_rows)
        if self.model_config.image_token_id >= 0:
            if embeds is not None:
                spliced = np.zeros((b, bucket, self.model_config.hidden),
                                   np.float32)
                spliced[0, :len(embeds)] = embeds
                kwargs["extra_embeds"] = jnp.asarray(spliced)
            else:
                # Text only on a multimodal engine: reuse a cached device
                # zero buffer (a fresh 10s-of-MB host alloc + transfer
                # per launch would tax every text request).
                zeros = self._zero_embeds.get((b, bucket))
                if zeros is None:
                    zeros = jnp.zeros(
                        (b, bucket, self.model_config.hidden), jnp.float32)
                    self._zero_embeds[(b, bucket)] = zeros
                kwargs["extra_embeds"] = zeros
        shape = bucket if entry == "prefill" else f"{b}x{bucket}"
        with self._program(entry, shape,
                           tokens=sum(len(r.tokens) for r in rows)):
            return self._launch(fn, args, kwargs, phase="prefill")

    def prefill_chunk(
        self,
        tokens: np.ndarray,  # [t] chunk token ids
        start_pos: int,  # absolute position of tokens[0]
        block_table: np.ndarray,  # [max_pages_per_seq] int32
        kv_len_after: int,
        sampling: tuple[float, float, int, int],  # (temp, top_p, top_k, seed)
        lora_idx: int = 0,
        chunk_embeds: Optional[np.ndarray] = None,  # [t, H] splice rows
        return_device: bool = False,
        slot: Optional[int] = None,  # the scheduler's slot, where per-slot
        #               state lives; None: past the end, its write dropped
        window=None,  # (window group's pages from its first held block,
        #               that block's first position): a second page group
    ) -> int:
        """Run one prefill chunk; returns the sampled token id (meaningful
        only on the final chunk). `chunk_embeds` rows replace the token
        embedding at image-placeholder positions within this chunk.
        `return_device=True` skips the host sync and returns the device
        token array — lets callers (bench pipelining, speculative
        schedulers) overlap successive chunks across the dispatch
        round trip the same way decode_multi does."""
        row = PrefillRow(tokens, start_pos, block_table, kv_len_after,
                         sampling, lora_idx, slot, window)
        token, lp, top_ids, top_lps = self._prefill("prefill", [row], 1,
                                                    chunk_embeds)
        if return_device:
            self.last_prefill_sample = None
            return token
        self.last_prefill_sample = (float(np.asarray(lp)[0]),  # dynajit: disable=DJ201 -- sync-needing rows only (logprobs/prefill_only); common path defers via return_device
                                    np.asarray(top_ids)[0],  # dynajit: disable=DJ201 -- same prefill sample drain
                                    np.asarray(top_lps)[0])  # dynajit: disable=DJ201 -- same prefill sample drain
        return int(np.asarray(token)[0])  # dynajit: disable=DJ201 -- same prefill drain (final-chunk token)

    def prefill_chunk_batch(
        self,
        rows: Sequence[PrefillRow],  # one a sequence
        want_samples: bool = False,
    ):
        """Run SEVERAL sequences' prefill chunks in one compiled dispatch
        — the cross-sequence shape fix for low-MFU small-model prefill
        (one [B, bucket] forward instead of B [1, bucket] forwards; the
        prefill step function is batch-general, jit specializes per
        (B, bucket)). Per-row results are bit-identical to equivalent
        prefill_chunk calls: the sampler keys on each row's (seed, step),
        never the row index. The batched path carries no embed splicing
        (the scheduler routes media sequences through single-row
        prefill).

        Returns the device token array [B_padded] (row i = rows[i]); with
        want_samples=True, `last_prefill_samples` holds per-row
        (logprob, top_ids, top_logprobs) — a host sync, so ask only when
        a row actually needs logprobs. B is padded to a power of two:
        bounded jit variants."""
        n = len(rows)
        token, lp, top_ids, top_lps = self._prefill(
            "prefill_batch", rows, 1 << max(0, n - 1).bit_length())
        if want_samples:
            lp_h = np.asarray(lp)  # dynajit: disable=DJ201 -- explicit want_samples contract: callers ask only when a row needs logprobs
            ids_h = np.asarray(top_ids)  # dynajit: disable=DJ201 -- same want_samples drain
            lps_h = np.asarray(top_lps)  # dynajit: disable=DJ201 -- same want_samples drain
            self.last_prefill_samples = [
                (float(lp_h[i]), ids_h[i], lps_h[i]) for i in range(n)]
        else:
            self.last_prefill_samples = [None] * n
        return token

    def decode(
        self,
        tokens: np.ndarray,  # [B] last token per slot
        positions: np.ndarray,  # [B]
        block_tables: np.ndarray,  # [B, max_pages_per_seq]
        kv_lens: np.ndarray,  # [B]
        active: np.ndarray,  # [B] bool
        temperature: np.ndarray,
        top_p: np.ndarray,
        top_k: np.ndarray,
        seeds: np.ndarray,
        steps: Optional[np.ndarray] = None,  # [B] per-slot token index
        lora_idx: Optional[np.ndarray] = None,  # [B] adapter slot per seq
        want_logprobs: bool = False,
        want_logits: bool = False,
    ) -> np.ndarray:
        """One decode step for all slots; returns sampled tokens [B].
        `want_logprobs` selects the variant that also returns logprob data
        (read via last_decode_sample) — the plain variant skips the
        full-vocab log_softmax/top_k and the extra host transfers.
        `want_logits` selects the logits-processor variant that also
        returns the raw [B, V] logits rows (read via last_decode_logits);
        it overrides want_logprobs (the scheduler derives logprob data on
        host from the raw rows in that mode)."""
        self.decode_steps += 1
        self._count_latent_decode(kv_lens, active, 1)
        args = self._decode_args(
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), block_tables, kv_lens,
            active, temperature, top_p, top_k, seeds, steps, lora_idx)
        variant = "logits" if want_logits else "lp" if want_logprobs else ""
        if variant == "logits" and self._decode_fn_logits is None:
            self._decode_fn_logits = self._build_decode(with_logits=True)
        if variant == "lp" and self._decode_fn_lp is None:
            self._decode_fn_lp = self._build_decode(True)
        fn = (self._decode_fn_logits if variant == "logits"
              else self._decode_fn_lp if variant else self._decode_fn)
        with self._program("decode", *self._table_widths(args[2]), variant):
            next_tokens, *more = self._launch(fn, args)
        self.last_decode_logits = None
        if variant == "logits":
            self.last_decode_logits = np.asarray(more[0])  # dynajit: disable=DJ201 -- logits-processor escape hatch: host sampling needs the raw rows now
        self.last_decode_sample = (None, None, None)
        if variant == "lp":
            lp, top_ids, top_lps = more
            self.last_decode_sample = (np.asarray(lp), np.asarray(top_ids),  # dynajit: disable=DJ201 -- logprobs path: per-step sample data is the request's contract
                                       np.asarray(top_lps))  # dynajit: disable=DJ201 -- same logprobs drain
        return np.asarray(next_tokens)  # dynajit: disable=DJ201 -- the per-token decode drain: [B] int32 is the step's designed readback

    # -- LoRA slot pack ----------------------------------------------------

    def set_lora_slot(self, slot: int, adapter) -> None:
        """Write an adapter's factors into pack slot `slot` (llm.lora
        LoraAdapter, factors already rank-padded + alpha-scaled). Targets
        the adapter does not provide are zeroed. Serialize with stepping
        (run on the scheduler thread) so one step never sees a half-written
        pack."""
        assert self.lora_pack is not None, "runner built with max_loras=0"
        assert 1 <= slot <= self.config.max_loras, f"bad lora slot {slot}"
        dtype = jnp.dtype(self.model_config.dtype)
        layers = self.lora_pack["layers"]
        for i, layer in enumerate(layers):
            provided = adapter.layers.get(i, {})
            for target, entry in layer.items():
                if target in provided:
                    a, b = provided[target]
                    layer[target] = {
                        "a": entry["a"].at[slot].set(
                            jnp.asarray(a, dtype)),
                        "b": entry["b"].at[slot].set(
                            jnp.asarray(b, dtype)),
                    }
                else:
                    layer[target] = {
                        "a": entry["a"].at[slot].set(0.0),
                        "b": entry["b"].at[slot].set(0.0),
                    }

    def clear_lora_slot(self, slot: int) -> None:
        assert self.lora_pack is not None, "runner built with max_loras=0"
        for layer in self.lora_pack["layers"]:
            for target, entry in layer.items():
                layer[target] = {
                    "a": entry["a"].at[slot].set(0.0),
                    "b": entry["b"].at[slot].set(0.0),
                }

    def reshard(self, mesh: Mesh) -> None:
        """Elastic parallelism rescale: re-place params on a NEW mesh
        (different ep/tp/dp split, possibly different device count) and
        rebuild the compiled steps. The paged KV pool is re-initialized —
        callers drain or re-prefill in-flight sequences first (the
        reference's scale_elastic_ep drains the same way,
        ref: components/src/dynamo/vllm/handlers.py:498 scale_elastic_ep).
        Must run on the scheduler thread (kv donation)."""
        self._check_plan(mesh)
        self.mesh = mesh
        # The kernel choice depends on the mesh (Pallas flash-decode is
        # single-device only): the adapter takes it anew.
        self._steps = make_steps(self.model_config, _mesh_kernels(mesh),
                                 self._user_attention_fn)
        axes = param_axes(self.model_config)
        if self._weight_quantized:
            from ..models.quantize import check_quantizable

            check_quantizable(self.model_config,
                              tp=int(dict(mesh.shape).get("tp", 1)),
                              n_devices=mesh.devices.size,
                              dtype=self.config.weight_dtype)
            axes = self._quantize_axes(axes, self.model_config)
        self._param_sharding = param_shardings(mesh, axes)
        self.params = jax.tree.map(
            jax.device_put, self.params, self._param_sharding
        )
        self._rep = NamedSharding(mesh, P())
        self._cache_sharding = self._kv_cache_sharding(mesh)
        self.cache = self._kv_cache_init()()
        if self.lora_pack is not None:
            self.lora_pack = jax.device_put(self.lora_pack, self._rep)
        self._decode_fn = self._build_decode(False)
        self._decode_fn_lp = None
        self._decode_multi_fns = {}
        self._decode_spec_fns = {}
        self._prefill_fns = {}
        self._ring_prefill_fns = {}
        self._embed_fns = {}
        self._zero_embeds = {}
        log.info("resharded onto mesh %s", dict(mesh.shape))

    def gather_pages_device(self, page_ids: np.ndarray,
                            replicated: bool = False):
        """Device-side page gather into a FRESH bundle [n, L, 2, ps, kh,
        hd]. Must run on the scheduler thread (the pool is donated through
        every step) — but it is the CHEAP half: the returned buffer is
        independent of the pool, so the caller does the slow D2H copy
        (np.asarray) off-thread and decode stepping overlaps the transfer
        (ref concern: SURVEY §7 host<->HBM bandwidth discipline; VERDICT
        'transfer steals decode step time').

        `replicated=True` all-gathers a head-sharded bundle onto every
        device first — REQUIRED on a multi-host mesh, where the sharded
        bundle is not addressable from one process (the MirroredRunner
        forces it so every host can read the full bundle locally)."""
        from ..ops.block_copy import gather_kv_blocks, gather_kv_blocks_q8

        if self.cache_plan.move_pages:
            raise RuntimeError(
                "pages cannot be transferred, offloaded or parked: "
                + self.cache_plan.move_pages)
        (pool,), _ = self.cache

        # Pad the id list to a power-of-two width (extra ids hit the
        # scratch page 0) so the gather jit compiles O(log n) shapes, not
        # one per transfer size; slice back on device.
        ids = np.asarray(page_ids, np.int32)
        n = len(ids)
        m = 1 << max(0, n - 1).bit_length()
        if m != n:
            ids = np.concatenate([ids, np.zeros(m - n, np.int32)])
        if self._kv_quantized:
            # Quantized pool: PACKED uint8 universal blocks (int8 values
            # + bf16 scale rows, ops/block_copy.py) — bit-exact through
            # every tier, no dequant/requant roundtrip.
            bundle = gather_kv_blocks_q8(*pool, jnp.asarray(ids))
        else:
            bundle = gather_kv_blocks(pool, jnp.asarray(ids))
        if m != n:
            bundle = bundle[:n]
        if replicated and not bundle.is_fully_addressable:
            bundle = jax.device_put(bundle, self._rep)
        return bundle

    def gather_pages(self, page_ids: np.ndarray) -> np.ndarray:
        """Pull pages to host in universal layout [n, L, 2, ps, kh, hd]
        (disagg prefill export / KVBM offload). Must run on the scheduler
        thread — the KV cache buffer is donated through every step.
        Prefer gather_pages_device + off-thread readback in transfer
        paths."""
        return np.asarray(jax.device_get(
            self.gather_pages_device(page_ids, replicated=True)))

    def scatter_pages(self, page_ids: np.ndarray, blocks) -> None:
        """Write a block bundle into pool pages (disagg decode onboard /
        KVBM onboard). Scheduler thread only (donation). `blocks` is either
        a host numpy bundle (DCN host-relay / KVBM tiers) or a jax.Array
        already resharded onto this runner's mesh by the ICI bridge — the
        device path skips the H2D copy entirely."""
        from ..ops.block_copy import (
            scatter_from_host,
            scatter_from_host_q8,
            scatter_kv_blocks,
            scatter_kv_blocks_q8,
        )

        (pool,), state = self.cache
        if self._kv_quantized:
            values, scales = pool
            if isinstance(blocks, jax.Array):
                pool = scatter_kv_blocks_q8(
                    values, scales, jnp.asarray(page_ids, jnp.int32),
                    blocks)
            else:
                pool = scatter_from_host_q8(
                    values, scales, np.asarray(page_ids, np.int32),
                    blocks)
        elif isinstance(blocks, jax.Array):
            pool = scatter_kv_blocks(
                pool, jnp.asarray(page_ids, jnp.int32), blocks)
        else:
            pool = scatter_from_host(
                pool, np.asarray(page_ids, np.int32), blocks)
        self.cache = ((pool,), state)

    # -- distributed KVBM worker half (block_manager/distributed.py) -------
    # Mirrored across multihost ranks via the step channel: each host
    # gathers/scatters only its addressable shards — no cross-host bytes.

    kvbm_worker = None  # set by the worker CLI on every rank

    def kvbm_store_shards(self, page_ids: np.ndarray,
                          hashes: list[int]) -> None:
        """Gather pages (pool-sharded bundle, NO replication) and store
        this host's shards in its local arena."""
        assert self.kvbm_worker is not None, "no KvbmShardWorker attached"
        bundle = self.gather_pages_device(np.asarray(page_ids, np.int32),
                                          replicated=False)
        self.kvbm_worker.store([int(h) for h in hashes], bundle)

    def kvbm_load_shards(self, hashes: list[int],
                         page_ids: np.ndarray) -> None:
        """Reassemble the sharded bundle from this host's arena rows and
        scatter it into the pool (every rank provides its shards of the
        same global array inside the same mirrored step)."""
        assert self.kvbm_worker is not None, "no KvbmShardWorker attached"
        per_device = self.kvbm_worker.load([int(h) for h in hashes])
        if per_device is None:
            # Arenas are deterministic replicas; a miss here on any rank
            # means the leader's index diverged — fail loudly rather than
            # scatter stale KV.
            raise RuntimeError("shard arena miss during onboard")
        bundle = self.kvbm_worker.make_bundle(per_device)
        self.scatter_pages(np.asarray(page_ids, np.int32), bundle)

    def kv_layout(self) -> dict:
        """Wire-layout descriptor of this runner's paged pool. Geometry comes
        from the *cache* dims, not the attention dims — MLA caches one latent
        stack per layer ([L, 1, ps, 1, rank+rope]), not per-head K/V."""
        cfg = self.model_config
        layout = {
            "n_layers": len(cfg.kv_layers),
            "kv_heads": cfg.kv_cache_heads,
            "head_dim": cfg.kv_cache_head_dim,
            "kv_dims": cfg.kv_cache_kv_dims,
            "page_size": self.config.page_size,
            "dtype": str(jnp.dtype(cfg.dtype).name),
        }
        if self._kv_quantized:
            # Tier blocks travel PACKED (uint8 values+scales bytes,
            # ops/block_copy.py gather_kv_blocks_q8); BlockLayoutSpec
            # derives the flat byte geometry from these fields.
            layout["kv_dtype"] = "int8"
            layout["scale_lanes"] = KV_SCALE_LANES
        return layout

    def warmup(self) -> None:
        """Compile decode + smallest prefill bucket ahead of traffic."""
        b = self.config.max_batch
        p = self.config.max_pages_per_seq
        with self._warm("warmup"):
            self.decode(
                np.zeros(b, np.int32), np.zeros(b, np.int32),
                self._idle_tables(b, p), np.zeros(b, np.int32),
                np.zeros(b, bool), np.ones(b, np.float32),
                np.ones(b, np.float32), np.zeros(b, np.int32),
                np.zeros(b, np.uint32),
            )
            self.prefill_chunk(
                np.zeros(1, np.int32), 0, np.zeros(p, np.int32), 1,
                (0.0, 1.0, 0, 0), window=IDLE_WINDOW,
            )

    def prewarm(self, spec_widths: Optional[Sequence[int]] = None,
                launches: bool = False, block: int = 1) -> None:
        """Compile the FULL predicted steady-state jit-key space before
        serving — exactly what the dynajit jit-surface registry (and the
        retrace canary) enumerate: decode (attr:_decode_fn, one key),
        EVERY prefill bucket (cached:_prefill_fns keyed by bucket), and
        the speculative verify combos the scheduler will drive
        (cached:_decode_spec_fns keyed (k+1, want_logits=False); the
        logits-processor variant stays lazy — it only exists when a
        request installs processor slots). A warm persistent compile
        cache (engine/compile_cache.py) turns every one of these into a
        disk replay, so a warm arrival compiles NOTHING — in either case
        steady state never traces (docs/elasticity.md).

        `spec_widths` defaults to the DYNT_SPEC_* configuration the
        scheduler will read: [DYNT_SPEC_MAX_K] when DYNT_SPEC_ENABLE.

        `launches` (the worker's `--prewarm full`) adds every other
        launch the scheduler can make, derived from this runner's own
        buckets, budget and table widths and from no list of shapes:
        batched prefill by (rows, bucket), rows a power of two up to
        `max_prefill_rows`, and the fused decode block of `block` steps
        at every block-table width, fed from the host and from the block
        before it (the pipelined second block takes its tokens on the
        device: another jit key). A bucket no context of this runner
        fills is then left out. Served traffic cannot be trusted to warm
        these (PERF.md, PR 25 fault 4 and PR 30): which shape a crafted
        group lands on depends on when its rows arrive. Nothing real is
        touched: every row's pages are the scratch page, its state slot
        is past the end, no decode row is active. The launches go
        through the served entries and so under the served program keys
        (`/debug/programs`, cause `prewarm`), and count as no launch."""
        with self._warm("prewarm"):
            self._prewarm(spec_widths, launches, block)

    def _prewarm(self, spec_widths, launches: bool, block: int) -> None:
        self.warmup()
        b = self.config.max_batch
        p = self.config.max_pages_per_seq
        greedy = (0.0, 1.0, 0, 0)
        buckets = self.config.prefill_buckets
        if launches:
            buckets = tuple(
                bucket for i, bucket in enumerate(buckets)
                if i == 0 or buckets[i - 1] < self.config.max_context)
        for bucket in buckets:
            self.prefill_chunk(
                np.zeros(bucket, np.int32), 0, np.zeros(p, np.int32),
                min(bucket, self.config.max_context), greedy,
                window=IDLE_WINDOW,
            )
        if launches:
            self._prewarm_launches(buckets, block)
        if spec_widths is None:
            spec_widths = ([max(1, int(env("DYNT_SPEC_MAX_K")))]
                           if env("DYNT_SPEC_ENABLE") else [])
        for k in spec_widths:
            self.decode_spec(
                np.zeros(b, np.int32), np.zeros((b, k), np.int32),
                np.zeros(b, np.int32), np.zeros((b, p), np.int32),
                np.ones(b, np.int32), np.zeros(b, bool),
                np.ones(b, np.float32), np.ones(b, np.float32),
                np.zeros(b, np.int32), np.zeros(b, np.uint32),
            )

    def _prewarm_launches(self, buckets: Sequence[int], block: int) -> None:
        """`prewarm(launches=True)`: batched prefill and the fused decode
        block, through the host entry points the scheduler calls, so the
        jit keys are the served ones."""
        b, p = self.config.max_batch, self.config.max_pages_per_seq
        greedy = (0.0, 1.0, 0, 0)
        rows = 2
        # rows are padded to a power of two: the limit's own ceiling
        limit = 1 << (min(self.max_prefill_rows, b) - 1).bit_length()
        while rows <= limit:
            for bucket in buckets:
                n = min(bucket, self.config.max_context - 1)
                if (self.bounds_prefill_launches
                        and not self.prefill_launch_fits([n] * rows)):
                    continue  # the scheduler never makes this launch
                row = PrefillRow(np.zeros(n, np.int32), 0,
                                 np.zeros(p, np.int32), n, greedy,
                                 window=IDLE_WINDOW)
                toks = self.prefill_chunk_batch([row] * rows)
                # the scheduler picks a row's token on the device
                # (`_prefill_batch`): one tiny program for each batch size
                toks[0]
            rows *= 2
        if block > 1:
            idle = (np.zeros(b, np.int32), np.zeros(b, np.int32))
            sampling = (np.zeros(b, np.float32), np.ones(b, np.float32),
                        np.zeros(b, np.int32), np.zeros(b, np.uint32))
            width = bucket_table_width(1, p)
            while True:
                args = (self._idle_tables(b, width), np.zeros(b, np.int32),
                        np.zeros(b, bool), *sampling)
                toks = self.decode_multi(*idle, *args, k=block,
                                         return_device=True)
                self.decode_multi(toks[-1], idle[1], *args, k=block,
                                  return_device=True)
                if width >= p:
                    break
                width = bucket_table_width(width + 1, p)
        jax.block_until_ready(self.cache)
