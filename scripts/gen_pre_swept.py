"""Generate the shipped pre-swept planner profiles (VERDICT r4 item 10;
ref: components/src/dynamo/planner/utils/pre_swept_results/ — the
reference checks in per-GPU NPZ interpolation data so the planner boots
with zero profiling).

Method: the rapid analytic sweep (profiler/timing_model.py) generates
the grid SHAPE; chip anchors calibrate its absolute level — the grid
is scaled by measured/predicted at the anchor operating point. This
keeps the curves physically shaped (roofline over batch/context) while
pinning them to what a chip did. The anchors below are one v5e run of
2026-07-31 (the r5 tables of `git show 6b5a9d4:BASELINE.md`), taken
before PRs 1-21 and not rechecked: replace them from `PERF_LEDGER.jsonl`
once it holds the same operating points.

Usage: python scripts/gen_pre_swept.py   (writes into
dynamo_tpu/planner/pre_swept/<chip>/<model>/)
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.models import get_config  # noqa: E402
from dynamo_tpu.planner.interpolation import (  # noqa: E402
    save_decode_profile,
    save_prefill_profile,
)
from dynamo_tpu.profiler.chips import get_chip  # noqa: E402
from dynamo_tpu.profiler.timing_model import (  # noqa: E402
    TimingModel,
    rapid_decode_sweep,
    rapid_prefill_sweep,
)

ISLS = [128, 256, 512, 1024, 2048, 4096, 8192]
KV_USAGES = [0.1, 0.2, 0.35, 0.5, 0.65, 0.8, 0.95]
CONTEXTS = [256, 1024, 4096, 16384]

# Anchors, v5e single chip (2026-07-31; see the module docstring):
#   decode: (batch, context, measured tok/s/chip) from bench.py
#   prefill: (chunk_len, measured tok/s/chip) from bench.py's prefill
#            block (pipelined chunks)
ANCHORS = {
    "qwen3-0.6b": {"decode": (8, 256, 2350.2), "prefill": (1024, 6098.4)},
    "mistral-7b": {"decode": (8, 256, 247.2), "prefill": (1024, 7425.0)},
}


def gen(chip: str, model_name: str, out_root: str) -> None:
    cfg = get_config(model_name)
    tm = TimingModel(cfg, get_chip(chip), num_chips=1)
    anchors = ANCHORS[model_name]

    b, ctx, measured = anchors["decode"]
    predicted = tm.decode_thpt_per_chip(float(b), float(ctx))
    dscale = measured / predicted
    decode = rapid_decode_sweep(tm, KV_USAGES, CONTEXTS)
    decode["z_thpt_per_chip"] = decode["z_thpt_per_chip"] * dscale
    decode["z_itl"] = decode["z_itl"] / dscale

    chunk, pmeasured = anchors["prefill"]
    ppred = tm.prefill_thpt_per_chip(float(chunk))
    pscale = pmeasured / ppred
    prefill = rapid_prefill_sweep(tm, ISLS)
    prefill["prefill_thpt_per_chip"] = (
        prefill["prefill_thpt_per_chip"] * pscale)
    prefill["prefill_ttft"] = prefill["prefill_ttft"] / pscale

    out = os.path.join(out_root, chip, model_name)
    save_prefill_profile(out, prefill["prefill_isl"],
                         prefill["prefill_ttft"],
                         prefill["prefill_thpt_per_chip"])
    save_decode_profile(out, decode["x_kv_usage"],
                        decode["y_context_length"], decode["z_itl"],
                        decode["z_thpt_per_chip"],
                        int(decode["max_kv_tokens"][0]))
    with open(os.path.join(out, "PROVENANCE.json"), "w") as f:
        json.dump({
            "method": "rapid TimingModel sweep calibrated to real-chip "
                      "anchors (scripts/gen_pre_swept.py)",
            "chip": chip, "model": model_name,
            "anchors": anchors,
            "decode_scale": round(float(dscale), 4),
            "prefill_scale": round(float(pscale), 4),
            "measured": "one v5e chip, 2026-07-31 (r5 tables of git show "
                        "6b5a9d4:BASELINE.md); not rechecked since",
        }, f, indent=1)
    print(f"{chip}/{model_name}: decode_scale={dscale:.3f} "
          f"prefill_scale={pscale:.3f} -> {out}")


def main() -> None:
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "dynamo_tpu", "planner", "pre_swept")
    for model in ANCHORS:
        gen("v5e", model, root)


if __name__ == "__main__":
    main()
