"""Bench dry run: every wired bench.py block at toy sizes, on CPU.

`python bench.py` on silicon is a once-per-round capture; nothing in
CI exercised its block wiring between rounds, so a refactor could rot
a block (an import, a knob rename, a summary-key drift) and the
breakage would surface mid-capture on the chip. This smoke drives the
SAME functions bench.py's main() dispatches to — the model bench with
its spec and kvbm_offload blocks, plus every mocker-backed point —
with sizes shrunk to seconds-scale, and fails if any required block is
missing or errored.

Run: python scripts/bench_dry_run.py          (CI: bench-dry-run job)
Prints one JSON line mirroring bench.py's report shape; `--json PATH`
also writes it to a file — the input tools/dynawatch gates against its
blessed baselines (CI: obs-watch job).
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("DYNT_LOG_LEVEL", "WARNING")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REQUIRED_BLOCKS = ("spec", "kvbm_offload", "disagg", "session_cache",
                   "two_class_goodput", "drain", "cold_start")


def main() -> int:
    parser = argparse.ArgumentParser("bench_dry_run")
    parser.add_argument("--json", default="",
                        help="also write the report to this path")
    args = parser.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import bench

    # The model bench at toy sizes: one decode block, spec + kvbm
    # blocks on, prefill/ttft off (not capture blocks — pure runtime).
    result = bench.bench_one(
        "qwen3-0.6b", batch=2, prompt_len=64, decode_steps=64,
        num_pages=128, prefill_chunk=256, do_prefill=False,
        do_ttft=False, device_kind="cpu")

    # The mocker-backed points, exactly as bench.py main() wires them,
    # with every exposed size knob shrunk.
    result["disagg"] = bench.bench_disagg_point(requests=4)
    result["session_cache"] = bench.bench_session_point()
    result["two_class_goodput"] = bench.bench_two_class_point()
    result["drain"] = bench.bench_drain_point()
    result["cold_start"] = bench.bench_cold_start_point()

    print(json.dumps(result))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(result, fh)

    failures = []
    for key in REQUIRED_BLOCKS:
        block = result.get(key)
        if not isinstance(block, dict):
            failures.append(f"{key}: missing")
        elif "error" in block:
            failures.append(f"{key}: {block['error']}")
    # The chaos-backed points carry their own pass verdicts.
    if result["drain"].get("passed") is not True:
        failures.append("drain: scenario assertions failed")
    if result["cold_start"]["measured_spot"].get("passed") is not True:
        failures.append("cold_start: spot scenario assertions failed")
    if failures:
        print("bench dry run FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    print(f"bench dry run ok: {len(REQUIRED_BLOCKS)} blocks",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
