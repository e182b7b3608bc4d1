"""Analytical TPU roofline model for rapid (simulation-based) profiling.

Fills the role AIConfigurator plays in the reference's rapid profiler mode
(ref: components/src/dynamo/profiler/rapid.py — estimate perf without
touching hardware). The model is the standard two-roofline picture:

  prefill — compute-bound on the MXU: ttft = flops / (mfu * peak_flops),
            flops = 2 * params * isl + attention term 4 * isl^2 * d_model
            per layer pair; throughput/chip = isl / ttft.
  decode  — memory-bound on HBM: every step streams all weights plus the
            active KV working set; itl = bytes / (eff * bw);
            throughput/chip = batch / itl.

Both use the model geometry from models.config.ModelConfig and divide
weight/KV bytes by the chips-per-replica (TP shards weights and KV)."""

from __future__ import annotations

import dataclasses

import numpy as np

from ..models.config import ModelConfig, cache_plan
from .chips import ChipSpec


def param_count(cfg: ModelConfig) -> int:
    h = cfg.hidden
    per_layer = (
        h * cfg.n_q_heads * cfg.head_dim
        + 2 * h * cfg.n_kv_heads * cfg.head_dim
        + cfg.n_q_heads * cfg.head_dim * h
        + 3 * h * cfg.mlp_hidden
        + 2 * h
    )
    total = cfg.vocab_size * h + h + cfg.n_layers * per_layer
    if not cfg.tie_embeddings:
        total += h * cfg.vocab_size
    return total


def kv_bytes_per_token(cfg: ModelConfig, dtype_bytes: int = 2) -> int:
    """Bytes a cached token holds while every layer still sees it: the
    cache layers of the plan's page groups (the layers that WRITE pages:
    a `layer_pattern` stack's mixers are not all attention, and a layer
    that reads another's pages holds none), each a row of the pool."""
    layers = sum(cache_plan(cfg).group_layers) or cfg.n_layers
    return (layers * cfg.kv_cache_kv_dims
            * cfg.kv_cache_heads * cfg.kv_cache_head_dim * dtype_bytes)


@dataclasses.dataclass
class TimingModel:
    model: ModelConfig
    chip: ChipSpec
    num_chips: int = 1  # chips per replica (TP)
    mfu: float = 0.5  # achieved fraction of peak flops in prefill
    hbm_eff: float = 0.75  # achieved fraction of HBM bandwidth in decode
    dtype_bytes: int = 2

    def prefill_ttft_ms(self, isl: float) -> float:
        p = param_count(self.model)
        flops = 2.0 * p * isl + (
            4.0 * isl * isl * self.model.n_layers
            * self.model.n_q_heads * self.model.head_dim)
        peak = self.chip.bf16_tflops * 1e12 * self.mfu * self.num_chips
        return flops / peak * 1e3

    def prefill_thpt_per_chip(self, isl: float) -> float:
        ttft_s = self.prefill_ttft_ms(isl) / 1e3
        return isl / ttft_s / self.num_chips if ttft_s > 0 else 0.0

    def decode_itl_ms(self, batch: float, context: float) -> float:
        p_bytes = param_count(self.model) * self.dtype_bytes
        kv = batch * context * kv_bytes_per_token(self.model,
                                                  self.dtype_bytes)
        bw = self.chip.hbm_gbps * 1e9 * self.hbm_eff * self.num_chips
        return (p_bytes + kv) / bw * 1e3

    def decode_thpt_per_chip(self, batch: float, context: float) -> float:
        itl_s = self.decode_itl_ms(batch, context) / 1e3
        return batch / itl_s / self.num_chips if itl_s > 0 else 0.0

    def max_kv_tokens(self, weight_fraction_free: float = 0.9) -> int:
        hbm = self.chip.hbm_gib * (1 << 30) * self.num_chips
        p_bytes = param_count(self.model) * self.dtype_bytes
        free = max(0.0, hbm * weight_fraction_free - p_bytes)
        return int(free // kv_bytes_per_token(self.model, self.dtype_bytes))


def rapid_prefill_sweep(tm: TimingModel, isls) -> dict:
    isls = np.asarray(isls, float)
    return {
        "prefill_isl": isls,
        "prefill_ttft": np.array([tm.prefill_ttft_ms(i) for i in isls]),
        "prefill_thpt_per_chip": np.array(
            [tm.prefill_thpt_per_chip(i) for i in isls]),
    }


def rapid_decode_sweep(tm: TimingModel, kv_usages, contexts) -> dict:
    max_kv = tm.max_kv_tokens()
    xs, ys, itls, thpts = [], [], [], []
    for c in contexts:
        for x in kv_usages:
            b = max(1.0, x * max_kv / c)
            xs.append(x)
            ys.append(c)
            itls.append(tm.decode_itl_ms(b, c))
            thpts.append(tm.decode_thpt_per_chip(b, c))
    return {
        "x_kv_usage": np.asarray(xs), "y_context_length": np.asarray(ys),
        "z_itl": np.asarray(itls), "z_thpt_per_chip": np.asarray(thpts),
        "max_kv_tokens": np.asarray([max_kv]),
    }
