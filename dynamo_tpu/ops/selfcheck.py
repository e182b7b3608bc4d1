"""On-device self-check: every Pallas kernel the engine (or bench.py)
can reach, compiled by Mosaic at one model's shapes and compared with
its XLA oracle on the same device.

The unit tests run these kernels interpreted (`interpret=True`), which
proves the Python and says nothing about the compiler. This module is
the other half: `python -m dynamo_tpu.ops.selfcheck --model mistral-7b`
lowers each kernel with `interpret=False` and fails if Mosaic refuses
one or a result leaves its oracle's tolerance. `chip_smoke.py` runs it
as a child before serving; `--interpret` is the CPU rehearsal (it can
never pass as a chip result: the report names the platform and mode).

One case = one kernel entry point at one shape. Depth is cut (two pool
layers) because no kernel's tiling depends on it; widths are the
model's own.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import traceback

import numpy as np

# bf16 inputs with f32 accumulation against an f32 oracle: the error is
# a few output ULPs, far below this; a wrong tile, mask or scale is O(1).
REL_RMS_TOL = 2e-2

PAGE_SIZE = 16
BATCH = 8
SPEC_T = 5  # DYNT_SPEC_MAX_K (4) drafts + the committed token
TP_SHARDS = 4  # one host's chips: what one shard of `--tp 4` compiles


def _rel_rms(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    denom = max(float(np.sqrt(np.mean(want ** 2))), 1e-12)
    return float(np.sqrt(np.mean((got - want) ** 2)) / denom)


def _attention_inputs(rng, qh, kh, hd, width, t, quantized):
    """A paged pool with distinct owned pages per sequence and history
    lengths that cover empty, partial-page, chunk-boundary and full
    tables. Returns (q, kv_cache, layer, tables, kv_lens, k_cur, v_cur)."""
    import jax.numpy as jnp

    from ..models.transformer import quantize_kv

    n_pages = 1 + BATCH * width
    ctx = width * PAGE_SIZE
    pool = jnp.asarray(
        rng.normal(size=(2, 2, n_pages, PAGE_SIZE, kh, hd)), jnp.bfloat16)
    tables = jnp.asarray(
        1 + rng.permutation(n_pages - 1).reshape(BATCH, width), jnp.int32)
    lens = np.array([1, 2, PAGE_SIZE + 1, ctx // 2, ctx // 2 + 1,
                     ctx - PAGE_SIZE, ctx - 1, ctx], np.int32)
    q = jnp.asarray(rng.normal(size=(BATCH, t, qh, hd)), jnp.bfloat16)
    k_cur = jnp.asarray(rng.normal(size=(BATCH, t, kh, hd)), jnp.bfloat16)
    v_cur = jnp.asarray(rng.normal(size=(BATCH, t, kh, hd)), jnp.bfloat16)
    kv = quantize_kv(pool) if quantized else pool
    return q, kv, 1, tables, jnp.asarray(lens), k_cur, v_cur


def _attention_cases(qh, kh, hd, interpret, tag=""):
    """(name, thunk) pairs; each thunk returns (kernel, oracle), two
    zero-argument callables over the same inputs."""
    from ..models.transformer import (
        paged_attention_decode_xla,
        paged_attention_spec_xla,
        paged_attention_xla,
    )
    from .paged_attention import (
        paged_attention,
        paged_attention_decode_fused,
        paged_attention_decode_pool,
        paged_attention_spec_pool,
    )

    def pool_case(width, quantized, t):
        def run():
            rng = np.random.default_rng(width * 10 + t)
            args = _attention_inputs(rng, qh, kh, hd, width, t, quantized)
            if t == 1:
                return (lambda: paged_attention_decode_pool(
                            *args, interpret=interpret),
                        lambda: paged_attention_decode_xla(*args))
            return (lambda: paged_attention_spec_pool(
                        *args, interpret=interpret),
                    lambda: paged_attention_spec_xla(*args))
        return run

    def page_full():
        rng = np.random.default_rng(3)
        q, kv, layer, tables, lens, _, _ = _attention_inputs(
            rng, qh, kh, hd, 16, 1, False)
        positions = (lens - 1)[:, None]
        args = (q, kv, layer, tables, positions, lens)
        return (lambda: paged_attention(*args, interpret=interpret),
                lambda: paged_attention_xla(*args))

    def page_partial():
        rng = np.random.default_rng(4)
        args = _attention_inputs(rng, qh, kh, hd, 16, 1, False)
        return (lambda: paged_attention_decode_fused(
                    *args, interpret=interpret),
                lambda: paged_attention_decode_xla(*args))

    cases = []
    # Table widths 8 and 64 are two of the scheduler's power-of-two
    # buckets: one DMA chunk of the kernel, then two (the double-buffer
    # hand-off inside a row as well as between rows).
    # An int8 pool needs its kv heads in whole (4,128) tiles; the runner
    # refuses the geometry (ModelRunner: int8 KV under tp), so a shard
    # that thin is not reachable.
    kinds = (False, True) if kh % 4 == 0 or interpret else (False,)
    for width in (8, 64):
        for quantized in kinds:
            kind = "q8" if quantized else "bf16"
            cases.append((f"decode_pool{tag}/{kind}/w{width}",
                          pool_case(width, quantized, 1)))
    for quantized in kinds:
        kind = "q8" if quantized else "bf16"
        cases.append((f"spec_pool{tag}/{kind}/t{SPEC_T}",
                      pool_case(16, quantized, SPEC_T)))
    if not tag:
        cases.append(("decode_page/full", page_full))
        cases.append(("decode_page/partial", page_partial))
    return cases


def _projection_geoms(cfg):
    """Distinct [K, N] contractions of the dense projection stack."""
    qd = cfg.n_q_heads * cfg.head_dim
    kd = cfg.n_kv_heads * cfg.head_dim
    geoms = [
        ("wq", cfg.hidden, qd), ("wkv", cfg.hidden, kd),
        ("wo", qd, cfg.hidden), ("w_up", cfg.hidden, cfg.mlp_hidden),
        ("w_down", cfg.mlp_hidden, cfg.hidden),
    ]
    if not cfg.tie_embeddings:
        geoms.append(("lm_head", cfg.hidden, cfg.vocab_size))
    seen, out = set(), []
    for name, k, n in geoms:
        if (k, n) not in seen:
            seen.add((k, n))
            out.append((name, k, n))
    return out


def _matmul_cases(cfg, interpret, rows):
    import jax.numpy as jnp

    from .q4_linear import q4_matmul, q4_matmul_ref, quantize_weight_q4
    from .q8_linear import q8_matmul, q8_matmul_ref, quantize_weight

    @functools.lru_cache(maxsize=1)  # cases run geometry by geometry
    def weight(k, n):
        rng = np.random.default_rng(k + n)
        return jnp.asarray(
            rng.standard_normal((k, n), np.float32) / np.sqrt(k),
            jnp.bfloat16)

    def inputs(k, n, m):
        rng = np.random.default_rng(k + n + m)
        x = jnp.asarray(rng.standard_normal((m, k), np.float32),
                        jnp.bfloat16)
        return x, weight(k, n)

    def q8(k, n, m):
        def run():
            x, w = inputs(k, n, m)
            leaf = quantize_weight(w, 1)
            args = (x, leaf["q8"], leaf["qs"])
            return (lambda: q8_matmul(*args, interpret=interpret),
                    lambda: q8_matmul_ref(*args))
        return run

    def q4(k, n, m):
        def run():
            x, w = inputs(k, n, m)
            leaf = quantize_weight_q4(w, 1)
            args = (x, leaf["q4"], leaf["qs4"], leaf["qz4"])
            return (lambda: q4_matmul(*args, interpret=interpret),
                    lambda: q4_matmul_ref(*args))
        return run

    cases = []
    for name, k, n in _projection_geoms(cfg):
        for m in rows:
            cases.append((f"q8_matmul/{name}/m{m}", q8(k, n, m)))
            cases.append((f"q4_matmul/{name}/m{m}", q4(k, n, m)))
    return cases


def run_selfcheck(model: str, interpret: bool = False) -> dict:
    """Run every case; never raises for a failing kernel — the report
    carries the compiler's message so one call shows every refusal."""
    import jax

    from ..models import get_config

    cfg = get_config(model)
    device = jax.devices()[0]
    report = {
        "model": cfg.name,
        "mode": "interpret" if interpret else "compiled",
        "platform": device.platform,
        "device_kind": device.device_kind,
        "cases": [],
    }
    qh, kh, hd = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    cases = _attention_cases(qh, kh, hd, interpret)
    if kh % TP_SHARDS == 0:
        # The shard_map body sees kh/tp kv heads (sublane-thin tiles).
        cases += _attention_cases(qh // TP_SHARDS, kh // TP_SHARDS, hd,
                                  interpret, tag=f"_tp{TP_SHARDS}")
    # Decode rows (the batch) and one prefill bucket's rows.
    cases += _matmul_cases(cfg, interpret, rows=(BATCH, 512))
    for name, thunk in cases:
        entry = {"name": name, "ok": False}
        try:
            kernel, oracle = thunk()
            got = np.asarray(jax.block_until_ready(kernel()), np.float32)
            # Full-precision oracle only: inside the scope a kernel's
            # own bf16 dots would be traced at fp32 contract precision,
            # which Mosaic refuses ("Bad lhs type").
            with jax.default_matmul_precision("highest"):
                want = np.asarray(oracle(), np.float32)
            err = _rel_rms(got, want)
            entry["rel_rms_err"] = round(err, 6)
            entry["ok"] = bool(np.isfinite(got).all()
                               and err <= REL_RMS_TOL)
            if not entry["ok"]:
                entry["error"] = "result outside tolerance"
        except Exception as exc:  # noqa: BLE001 — the report is the
            # product: a Mosaic refusal is data, not a crash
            entry["error"] = f"{type(exc).__name__}: {exc}"[:2000]
            entry["trace"] = traceback.format_exc()[-1500:]
        report["cases"].append(entry)
    report["ok"] = all(c["ok"] for c in report["cases"])
    return report


def main(argv=None) -> int:
    parser = argparse.ArgumentParser("dynamo_tpu.ops.selfcheck")
    parser.add_argument("--model", default="mistral-7b")
    parser.add_argument("--interpret", action="store_true",
                        help="CPU rehearsal: run the kernels interpreted")
    parser.add_argument("--out", default=None,
                        help="also write the full report (with compiler "
                             "traces) to this JSON file")
    args = parser.parse_args(argv)
    report = run_selfcheck(args.model, interpret=args.interpret)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    for case in report["cases"]:
        case.pop("trace", None)
    print(json.dumps(report))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
