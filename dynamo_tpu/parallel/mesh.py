"""Device mesh construction.

Where the reference delegates intra-worker parallelism to engine flags
(`--tp-size 8`, ref: SURVEY section 2.5) and moves bytes with NCCL/NIXL, the
TPU build expresses all intra-worker parallelism as a `jax.sharding.Mesh`
over ICI and lets XLA insert collectives. Axes:

  dp — data parallel (replicated params, split batch). Router-visible:
       each dp rank is a distinct WorkerWithDpRank.
  tp — tensor parallel (attention heads / mlp hidden sharded); collectives
       ride ICI within a slice.
  sp — sequence/context parallel for long-context ring attention (ops/ring).
  ep - expert parallel for MoE layers (experts sharded over ep).
  pp — pipeline parallel: layer stages across slices/pods, activations
       moved rank-to-rank with collective permutes (ops/pipeline.py GPipe
       schedule); the outermost axis so stage hops ride DCN while tp
       all-reduces stay on ICI.

tp is the innermost axis so its all-reduces ride the fastest ICI links.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


AXIS_DP = "dp"
AXIS_TP = "tp"
AXIS_SP = "sp"
AXIS_EP = "ep"
AXIS_PP = "pp"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.sp * self.ep * self.pp

    def axis_names(self) -> tuple[str, ...]:
        return (AXIS_PP, AXIS_DP, AXIS_SP, AXIS_EP, AXIS_TP)

    def axis_sizes(self) -> tuple[int, ...]:
        return (self.pp, self.dp, self.sp, self.ep, self.tp)


def make_mesh(config: MeshConfig, devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < config.num_devices:
        raise ValueError(
            f"mesh {config} needs {config.num_devices} devices, "
            f"have {len(devices)}"
        )
    devices = devices[: config.num_devices]
    grid = np.array(devices).reshape(config.axis_sizes())
    return Mesh(grid, config.axis_names())


def local_mesh() -> Mesh:
    """Single-device mesh (1 chip): all axes size 1."""
    return make_mesh(MeshConfig())


def infer_mesh_config(n_devices: int, tp: Optional[int] = None) -> MeshConfig:
    """Default layout: as much tp as divides the device count (up to 8),
    rest dp — the common serving shape (tp within slice, dp across)."""
    if tp is None:
        tp = math.gcd(n_devices, 8)
    assert n_devices % tp == 0
    return MeshConfig(dp=n_devices // tp, tp=tp)
