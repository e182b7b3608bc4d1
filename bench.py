"""Benchmark: steady-state decode + prefill throughput of the FLAGSHIP
model (mistral-7b, the honest single-chip 7-8B config — BASELINE.md) on
the available accelerator, with the 0.6B toy as a secondary datapoint.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N,
   "prefill": {...}, "ttft": {...}, "secondary": [{...}]}

`vs_baseline` is the fraction of this chip's HBM-bandwidth roofline for the
model (decode is memory-bound: every step streams all weights + the active
KV). The reference publishes only relative numbers (BASELINE.md), so roofline
fraction is the honest hardware-normalized comparison: 1.0 == perfect
bandwidth utilization, and the reference's vLLM-on-H100 recipes sit around
0.5-0.7 of their roofline on the same measure.

Model selection: with DYNT_BENCH_MODEL / DYNT_BENCH_MODEL_PATH set, bench
exactly that model (single-model mode, all DYNT_BENCH_* knobs honored).
Otherwise on TPU the headline is mistral-7b (int8 KV — required at 7B:
bf16 KV + 14.5 GB of weights exceed the 16 GB HBM) and qwen3-0.6b runs
as `secondary`; on CPU only the toy runs (a 7B random-init on the CPU
smoke path would add tens of minutes for no signal).
"""

from __future__ import annotations

import gc
import json
import os
import time

import numpy as np

PAGE_SIZE = 16
# HBM bandwidth by chip generation (GB/s) for the roofline denominator.
HBM_GBPS = {"v5 lite": 819.0, "v5e": 819.0, "v5p": 2765.0, "v6e": 1640.0,
            "cpu": 50.0}
PEAK_TFLOPS = {"v5 lite": 197.0, "v5e": 197.0, "v5p": 459.0,
               "v6e": 918.0, "cpu": 1.0}


def _param_bytes(config) -> int:
    h, v = config.hidden, config.vocab_size
    per_layer = (
        h * config.n_q_heads * config.head_dim
        + 2 * h * config.n_kv_heads * config.head_dim
        + config.n_q_heads * config.head_dim * h
        + 3 * h * config.mlp_hidden
        + 2 * h
    )
    total = v * h + h + config.n_layers * per_layer
    if not config.tie_embeddings:
        total += h * v
    return total * 2  # bf16


def bench_one(model: str, *, model_path: str | None = None,
              batch: int = 8, kv_dtype: str = "model",
              weight_dtype: str = "model",
              num_pages: int = 1024, prompt_len: int = 256,
              decode_steps: int = 256, prefill_chunk: int = 1024,
              do_prefill: bool = True, do_ttft: bool = True,
              do_spec: bool = True, do_kvbm: bool = True,
              device_kind: str = "cpu") -> dict:
    from dynamo_tpu.engine import ModelRunner, RunnerConfig
    from dynamo_tpu.models import get_config
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    host_params = None
    if model_path:
        from dynamo_tpu.models.checkpoint import (
            config_from_checkpoint,
            load_params,
        )

        config = config_from_checkpoint(model_path)
        host_params = load_params(model_path, config)
        model_label = config.name
    else:
        config = get_config(model)
        model_label = model

    max_pages_per_seq = max(64, prefill_chunk // PAGE_SIZE + 2)
    runner = ModelRunner(
        config,
        RunnerConfig(page_size=PAGE_SIZE, num_pages=num_pages,
                     max_batch=batch, max_pages_per_seq=max_pages_per_seq,
                     prefill_buckets=(256, prefill_chunk)
                     if prefill_chunk > 256 else (256,),
                     kv_dtype=kv_dtype, weight_dtype=weight_dtype),
        make_mesh(MeshConfig()),
        host_params,
        seed=0,
    )
    if kv_dtype != "model":
        model_label += f" kv={kv_dtype}"
    if weight_dtype != "model":
        model_label += f" w={weight_dtype}"

    # Prefill BATCH sequences of PROMPT_LEN so decode runs with real KV.
    # Capacity covers prompt + warmup block + timed blocks — undersizing
    # would scatter KV through zero table entries into the shared scratch
    # page and silently corrupt the measured state.
    block = 64
    # Capacity covers the warmup block + timed blocks, and (do_kvbm) the
    # G2-offload A/B window of another settle + n_blocks fused blocks —
    # undersizing would scatter KV through zero table entries into the
    # shared scratch page and corrupt the measured state (comment below).
    total_tokens = prompt_len + decode_steps + (2 if do_kvbm else 1) * block
    pages_per_seq = total_tokens // PAGE_SIZE + 1
    tables = np.zeros((batch, max_pages_per_seq), np.int32)
    rng = np.random.default_rng(0)
    next_page = 1
    for b in range(batch):
        tables[b, :pages_per_seq] = np.arange(next_page,
                                              next_page + pages_per_seq)
        next_page += pages_per_seq
        prompt = rng.integers(0, config.vocab_size, prompt_len).astype(np.int32)
        budget = runner.max_prefill_chunk
        start_tok = 0
        while start_tok < prompt_len:
            chunk = prompt[start_tok:start_tok + budget]
            runner.prefill_chunk(chunk, start_tok, tables[b],
                                 start_tok + len(chunk), (0.0, 1.0, 0, 0))
            start_tok += len(chunk)

    tokens = np.zeros(batch, np.int32)
    positions = np.full(batch, prompt_len, np.int32)
    kv_lens = np.full(batch, prompt_len + 1, np.int32)
    active = np.ones(batch, bool)
    temp = np.zeros(batch, np.float32)
    top_p = np.ones(batch, np.float32)
    top_k = np.zeros(batch, np.int32)
    seeds = np.zeros(batch, np.uint32)

    # Steady-state serving uses fused decode blocks (DYNT_DECODE_BLOCK;
    # lax.scan of K steps per compiled call) with PIPELINED dispatch
    # (DYNT_DECODE_PIPELINE): block d+1 consumes block d's tokens
    # ON-DEVICE, so the host readback of block d overlaps block d+1's
    # compute — exactly what the serving scheduler does
    # (engine/scheduler.py _dispatch_decode/_drain_decode).
    steps_np = np.zeros(batch, np.int32)

    # Table width bucketed to the live context (as the serving scheduler
    # does): the attention kernel streams the table extent's pages.
    from dynamo_tpu.engine.model_runner import bucket_table_width

    width = bucket_table_width(pages_per_seq, max_pages_per_seq)
    btables = np.ascontiguousarray(tables[:, :width])

    state = {"tokens": tokens, "pending": None}
    # Step decomposition accumulators (perf/steptrace.py definitions):
    # dispatch = host time inside submit calls, drain = blocked readback
    # waits. Recorded per timed trial so the
    # BENCH_r06 decode number ships with its host/device attribution.
    trace_acc = {"dispatch_s": 0.0, "drain_s": 0.0}

    def step_block():
        nonlocal positions, kv_lens, steps_np
        t0 = time.perf_counter()
        toks_dev = runner.decode_multi(
            state["tokens"], positions, btables, kv_lens, active, temp,
            top_p, top_k, seeds, steps_np, k=block, return_device=True)
        trace_acc["dispatch_s"] += time.perf_counter() - t0
        if state["pending"] is not None:
            t1 = time.perf_counter()
            np.asarray(state["pending"])  # stream block d while d+1 runs
            trace_acc["drain_s"] += time.perf_counter() - t1
        state["pending"] = toks_dev
        state["tokens"] = toks_dev[-1]  # device-side chain
        positions += block
        kv_lens += block
        steps_np += block

    def drain():
        if state["pending"] is not None:
            t1 = time.perf_counter()
            np.asarray(state["pending"])
            trace_acc["drain_s"] += time.perf_counter() - t1
            state["pending"] = None

    step_block()  # warmup (compile + first block)
    drain()

    # Median of three trials: a single window can catch a latency spike
    # that says nothing about the engine.
    n_blocks = decode_steps // block
    trials = []
    trial_traces = []
    for _ in range(3):
        trace_acc["dispatch_s"] = trace_acc["drain_s"] = 0.0
        start = time.perf_counter()
        for _ in range(n_blocks):
            step_block()
        drain()
        trials.append(time.perf_counter() - start)
        trial_traces.append(dict(trace_acc))
        # rewind positions so every trial measures the same context length
        positions -= n_blocks * block
        kv_lens -= n_blocks * block
        steps_np -= n_blocks * block
    median_i = sorted(range(3), key=lambda i: trials[i])[1]
    elapsed = trials[median_i]
    tok_per_sec = batch * n_blocks * block / elapsed
    # Decomposition of the median trial: host dispatch share vs device
    # window (= wall minus the host submit time).
    med_trace = trial_traces[median_i]
    steptrace_cols = {
        "dispatch_ms_per_block": round(
            med_trace["dispatch_s"] / n_blocks * 1e3, 4),
        "drain_wait_ms_per_block": round(
            med_trace["drain_s"] / n_blocks * 1e3, 4),
        "device_ms_per_block": round(
            max(0.0, elapsed - med_trace["dispatch_s"]) / n_blocks * 1e3,
            4),
        "host_dispatch_frac": round(
            med_trace["dispatch_s"] / elapsed, 4),
    }

    # Roofline: steps/sec ceiling = HBM_bw / (weights + active KV per step)
    hbm = 50.0
    for key, bw in HBM_GBPS.items():
        if key in device_kind:
            hbm = bw
            break
    kv_elem_bytes = 1 if kv_dtype == "int8" else 2
    kv_bytes_per_step = (
        config.n_layers * 2 * (prompt_len + decode_steps // 2) * batch
        * config.n_kv_heads * config.head_dim * kv_elem_bytes
    )
    param_bytes = _param_bytes(config)
    if weight_dtype == "int8":
        # W8A16 streams int8 projections (+ negligible scale rows);
        # embeddings/norms stay bf16 but the projections dominate.
        param_bytes //= 2
    elif weight_dtype == "int4":
        # W4A16: 0.5 B/weight packed + f32 scale+zero rows per group
        # (8 B / group weights) vs 2 B bf16. The group comes from the
        # same registered config the kernel reads (runtime/config.py).
        from dynamo_tpu.runtime.config import env as _cfg_env

        q4_group = int(_cfg_env("DYNT_Q4_GROUP"))
        param_bytes = int(param_bytes * (0.5 + 8.0 / q4_group) / 2.0)
    bytes_per_step = param_bytes + kv_bytes_per_step
    roofline_steps = hbm * 1e9 / bytes_per_step
    roofline_tok = roofline_steps * batch
    vs_baseline = tok_per_sec / roofline_tok

    result = {
        "metric": f"decode throughput {model_label} bs={batch} "
                  f"ctx={prompt_len} ({device_kind})",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(vs_baseline, 4),
        "steptrace": steptrace_cols,
    }
    # Speculative decode point (ROADMAP item 1 / ISSUE 7): the same
    # decode workload driven through the draftless speculation plane —
    # n-gram proposals mined from each sequence's own token stream,
    # verified k+1 positions per dispatch (engine/spec.py +
    # ModelRunner.decode_spec, exactly what the serving scheduler runs
    # with DYNT_SPEC_ENABLE=1). Greedy continuation of the SAME
    # random-prompt state as the plain decode number above, so
    # acceptance reflects what the model actually repeats — reported
    # alongside tok/s rather than assumed.
    # Gated on runner.supports_spec: MLA/gpt-oss configs have no
    # multi-token verification forward, and a single-model bench of one
    # must not crash away its decode/prefill numbers.
    if do_spec and os.environ.get("DYNT_BENCH_SPEC", "1") != "0" \
            and getattr(runner, "supports_spec", False):
        from dynamo_tpu.engine.spec import NGramProposer
        from dynamo_tpu.runtime.config import env as _spec_env

        # BENCH_r06 capture prep: the serving path speculates at
        # DYNT_SPEC_MAX_K when DYNT_SPEC_ENABLE is on (main() flips it
        # for the flagship run), so the bench's k defaults to the SAME
        # registered knob the scheduler reads — one `python bench.py`
        # on silicon records the number the fleet would serve, with the
        # knob state alongside the acceptance it produced.
        spec_k = int(os.environ.get("DYNT_BENCH_SPEC_K")
                     or _spec_env("DYNT_SPEC_MAX_K"))
        proposers = []
        sp_tokens = np.array(state["tokens"], np.int32).reshape(-1)
        sp_positions = np.full(batch, prompt_len + block, np.int32)
        sp_kv_lens = sp_positions + 1
        sp_steps = np.full(batch, block, np.int32)
        for b in range(batch):
            # History = this slot's committed stream (the bench has no
            # prompt text worth mining; serving seeds with the prompt).
            proposers.append(NGramProposer([int(sp_tokens[b])]))
        drafts = np.zeros((batch, spec_k), np.int32)
        # Committed tokens + the k-token verification overrun must stay
        # inside the per-sequence page allocation sized above.
        n_iter = max(1, (decode_steps - spec_k) // (spec_k + 1))
        proposed = accepted = emitted = 0

        def spec_iter():
            nonlocal proposed, accepted, emitted
            mined = np.zeros(batch, np.int32)
            for b in range(batch):
                drafts[b] = 0
                prop = proposers[b].propose(spec_k)
                drafts[b, : len(prop)] = prop
                mined[b] = len(prop)
                proposed += len(prop)
            targets, n_acc = runner.decode_spec(
                sp_tokens, drafts, sp_positions, btables, sp_kv_lens,
                active, temp, top_p, top_k, seeds, sp_steps)
            for b in range(batch):
                n = int(n_acc[b])
                toks = [int(t) for t in targets[b, : n + 1]]
                proposers[b].extend(toks)
                sp_tokens[b] = toks[-1]
                sp_positions[b] += len(toks)
                sp_kv_lens[b] += len(toks)
                sp_steps[b] += len(toks)
                emitted += len(toks)
                # Acceptance counts MINED drafts only (the scheduler's
                # cap): an accidental target match on a 0-padded row
                # commits a correct token but is not an acceptance.
                accepted += min(n, int(mined[b]))
            return targets

        spec_iter()  # warmup (compiles the spec variant)
        proposed = accepted = emitted = 0
        t0 = time.perf_counter()
        for _ in range(n_iter):
            spec_iter()
        spec_elapsed = time.perf_counter() - t0
        result["spec"] = {
            "tokens_per_sec_per_chip": round(emitted / spec_elapsed, 1),
            "spec_enable": bool(_spec_env("DYNT_SPEC_ENABLE")),
            "max_k": int(_spec_env("DYNT_SPEC_MAX_K")),
            "k": spec_k,
            "steps": n_iter,
            "proposed": proposed,
            "accepted": accepted,
            "acceptance_rate": round(accepted / proposed, 4)
                               if proposed else 0.0,
            "speedup_vs_decode": round(
                (emitted / spec_elapsed) / tok_per_sec, 3),
        }

    # G2-active vs G2-idle serving (ROADMAP item 2 / ISSUE 8): the same
    # fused-block decode loop while the REAL OffloadManager drains a
    # continuous store burst — gathers ride the bench loop's dispatch
    # gap exactly as the serving scheduler's run_in_gap window, with the
    # DYNT_OFFLOAD_* budget active. `active_vs_idle` is the acceptance
    # number (>= 0.8 target; the unbudgeted round-5 collapse was 42/170
    # = 0.25).
    if do_kvbm and os.environ.get("DYNT_BENCH_KVBM", "1") != "0":
        import queue as thread_queue
        import threading

        from dynamo_tpu.block_manager.offload import OffloadManager

        gap_q: thread_queue.Queue = thread_queue.Queue()

        def run_in_gap(fn):
            out: thread_queue.Queue = thread_queue.Queue(1)

            def wrapped():
                try:
                    out.put((fn(), None))
                except Exception as exc:  # noqa: BLE001
                    out.put((None, exc))

            gap_q.put(wrapped)
            return out

        def step_block_with_gap():
            step_block()
            while True:  # drain gathers into the dispatch gap
                try:
                    fn = gap_q.get_nowait()
                except thread_queue.Empty:
                    break
                fn()

        n_bench_pages = max(1, next_page - 1)
        sunk = {"blocks": 0, "bytes": 0}

        def sink(h, block_arr, parent):
            sunk["blocks"] += 1
            sunk["bytes"] += block_arr.nbytes

        mgr = OffloadManager(
            lookup_pages=lambda hs: [1 + (h % n_bench_pages) for h in hs],
            gather=runner.gather_pages_device,
            run_in_step=run_in_gap,
            sink=sink,
        )
        feeding = threading.Event()
        feeding.set()

        def feeder():
            seq = 0
            while feeding.is_set():
                mgr.notify_stored(list(range(seq, seq + 32)), parent=None)
                seq += 32
                time.sleep(0.02)

        feed_thread = threading.Thread(target=feeder, daemon=True)
        feed_thread.start()
        try:
            step_block_with_gap()  # settle
            t0 = time.perf_counter()
            for _ in range(n_blocks):
                step_block_with_gap()
            drain()
            active_elapsed = time.perf_counter() - t0
        finally:
            feeding.clear()
            feed_thread.join(timeout=5)
            mgr.close()
        positions -= (n_blocks + 1) * block
        kv_lens -= (n_blocks + 1) * block
        steps_np -= (n_blocks + 1) * block
        active_tok = batch * n_blocks * block / active_elapsed
        result["kvbm_offload"] = {
            "idle_tokens_per_sec": round(tok_per_sec, 1),
            "active_tokens_per_sec": round(active_tok, 1),
            "active_vs_idle": round(active_tok / tok_per_sec, 3),
            "offloaded_blocks": sunk["blocks"],
            "offloaded_mb": round(sunk["bytes"] / 2**20, 1),
        }

    # On-chip prefill throughput + MFU headline (VERDICT r3 item 2): time
    # PIPELINED prefill chunks exactly like the decode bench pipelines
    # decode blocks — return_device defers the host sync so the dispatch
    # round trip overlaps the next chunk's compute. MFU denominator: model forward FLOPs (2 * active params
    # per token) over the chip's peak bf16 FLOPs.
    if do_prefill:
        chunk_len = runner.max_prefill_chunk
        n_chunks = 8
        # All chunks write the SAME page range: they are independent
        # prefills whose KV content is irrelevant to timing, and reuse
        # keeps the bench inside small NUM_PAGES pools (a 14.5GB model
        # leaves little HBM for benchmark-only pages).
        pf_table = np.zeros(max_pages_per_seq, np.int32)
        pf_pages = chunk_len // PAGE_SIZE + 1
        avail = num_pages - next_page
        assert avail >= pf_pages, (
            f"prefill bench needs {pf_pages} free pages, pool has {avail}")
        pf_table[:pf_pages] = np.arange(next_page, next_page + pf_pages)
        pf_prompt = rng.integers(0, config.vocab_size,
                                 chunk_len).astype(np.int32)

        def prefill_pass():
            pending = []
            for _ in range(n_chunks):
                pending.append(runner.prefill_chunk(
                    pf_prompt, 0, pf_table, chunk_len,
                    (0.0, 1.0, 0, 0), return_device=True))
            for tok in pending:
                np.asarray(tok)

        prefill_pass()  # compile + settle
        pf_trials = []
        for _ in range(3):
            t0 = time.perf_counter()
            prefill_pass()
            pf_trials.append(time.perf_counter() - t0)
        pf_elapsed = sorted(pf_trials)[1]
        pf_tok_per_sec = n_chunks * chunk_len / pf_elapsed
        peak = 1.0
        for key, tf in PEAK_TFLOPS.items():
            if key in device_kind:
                peak = tf
                break
        # Forward FLOPs/token: 2 * ACTIVE matmul params (MoE counts only
        # the routed experts; the embedding gather does no matmul) +
        # attention score/value FLOPs over the mean context.
        h = config.hidden
        per_layer = (h * config.n_q_heads * config.head_dim
                     + 2 * h * config.n_kv_heads * config.head_dim
                     + config.n_q_heads * config.head_dim * h)
        if config.n_experts:
            em = config.expert_mlp_hidden or config.mlp_hidden
            per_layer += config.n_experts_active * 3 * h * em
            per_layer += h * config.n_experts  # router
            per_layer += 3 * h * (getattr(config, "n_shared_experts", 0)
                                  * em)
        else:
            per_layer += 3 * h * config.mlp_hidden
        matmul_params = (config.n_layers * per_layer
                         + config.vocab_size * h)  # the head matmul
        attn_flops = (2 * 2 * config.n_layers * config.n_q_heads
                      * config.head_dim * (chunk_len / 2))
        flops_per_tok = 2 * matmul_params + attn_flops
        mfu = pf_tok_per_sec * flops_per_tok / (peak * 1e12)
        result["prefill"] = {
            "tokens_per_sec_per_chip": round(pf_tok_per_sec, 1),
            "chunk_len": chunk_len,
            "mfu": round(mfu, 4),
        }

    # Prefill/TTFT tail: p50/p99 single-request prefill latency at a few
    # ISLs (the reference's aiperf sweeps report TTFT alongside decode —
    # BASELINE.md measurement method).
    if do_ttft:
        ttft = {}
        bt = np.zeros(max_pages_per_seq, np.int32)
        for isl in (128, 512, 1024):
            if isl > runner.config.max_context - 8:
                continue
            pages = isl // PAGE_SIZE + 1
            bt[:] = 0
            bt[:pages] = np.arange(1, pages + 1)
            prompt = rng.integers(0, config.vocab_size, isl).astype(np.int32)
            # TTFT = time to run the full prefill (chunked at the largest
            # bucket) + sample the first token, prompt cold in the engine.
            budget = runner.max_prefill_chunk
            samples = []
            for trial in range(12):
                t0 = time.perf_counter()
                start = 0
                tok = None
                dispatch_s = 0.0
                while start < isl:
                    chunk = prompt[start:start + budget]
                    # Deferred readback per chunk, as the serving
                    # scheduler dispatches (dispatch-submit cost is the
                    # host share; the final drain closes the
                    # device-stream window).
                    d0 = time.perf_counter()
                    tok = runner.prefill_chunk(chunk, start, bt,
                                               start + len(chunk),
                                               (0.0, 1.0, 0, 0),
                                               return_device=True)
                    dispatch_s += time.perf_counter() - d0
                    start += len(chunk)
                np.asarray(tok)
                total_ms = (time.perf_counter() - t0) * 1e3
                samples.append((total_ms, dispatch_s * 1e3))
            samples = sorted(samples[2:])  # drop compile-warmup trials
            p50 = samples[len(samples) // 2]
            p99 = samples[min(len(samples) - 1, int(len(samples) * 0.99))]
            ttft[str(isl)] = {
                "p50_ms": round(p50[0], 2),
                "p99_ms": round(p99[0], 2),
                # Decomposition of the p50 sample: host dispatch vs
                # device share of the TTFT
                "p50_host_dispatch_ms": round(p50[1], 2),
                "p50_device_ms": round(max(0.0, p50[0] - p50[1]), 2),
            }
        result["ttft"] = ttft
    return result


def bench_disagg_point(requests: int = 16) -> dict:
    """Pipelined vs serial disaggregated prefill on the mocker xPyD
    profile (measured v5e step physics + modeled per-block KV handoff,
    TIMING_PRESETS) — the chip-free overlap point BENCH_r06 records next
    to the silicon numbers. TTFT falls because chunk i's handoff
    overlaps chunk i+1's compute; ITL is untouched by construction
    (docs/disaggregation.md)."""
    import asyncio

    from dynamo_tpu.mocker.engine import MockerConfig
    from dynamo_tpu.mocker.loadgen import OfflineReplay, synthesize_trace

    # Long prompts + moderate speedup keep the modeled handoff delta an
    # order of magnitude above asyncio timer jitter (sub-ms sleeps at
    # high speedup ratios drown the signal), and the arrival rate sits
    # below the 2-engine prefill service rate so queueing noise doesn't
    # swamp the p50.
    records = synthesize_trace(requests, rate_rps=5.0, isl_mean=4096,
                               osl_mean=32, seed=11)
    cfg = MockerConfig.from_timing_preset(
        "tpu-v5e-qwen3-0.6b", speedup_ratio=10.0,
        max_prefill_tokens_per_step=512)

    async def both() -> tuple[dict, dict]:
        pipe = await OfflineReplay(mode="disagg", num_workers=2,
                                   num_prefill_workers=2, config=cfg,
                                   disagg_pipeline=True).run(records)
        serial = await OfflineReplay(mode="disagg", num_workers=2,
                                     num_prefill_workers=2, config=cfg,
                                     disagg_pipeline=False).run(records)
        return pipe.summary(), serial.summary()

    pipe, serial = asyncio.run(both())
    return {
        "profile": "tpu-v5e-qwen3-0.6b xPyD (2P/2D, mocker)",
        "pipelined_ttft_ms": pipe["ttft_ms"],
        "serial_ttft_ms": serial["ttft_ms"],
        "pipelined_itl_ms": pipe["itl_ms"],
        "serial_itl_ms": serial["itl_ms"],
        "ttft_p50_speedup": round(
            serial["ttft_ms"]["p50"] / max(pipe["ttft_ms"]["p50"], 1e-9), 3),
    }


def bench_session_point() -> dict:
    """Session-cache A/B (ROADMAP item 2 / ISSUE 11):
    two-turn conversations with ~zero natural cross-session overlap
    against a KV-routed 2-worker mocker pair — cold turn-0 vs cached
    turn-1 TTFT with explicit pinning + session affinity ON, and the
    same traffic with the markers OFF (implicit-overlap baseline).
    Target on silicon: cached-turn TTFT <= the kvbm G1 hit number
    (`kvbm_ttft` in scripts/bench_multi.py's report); here the mocker's
    measured v5e step physics stand in for the chips
    (docs/prompt-caching.md)."""
    import asyncio
    import uuid

    from dynamo_tpu.bench import MultiturnBench
    from dynamo_tpu.frontend import Frontend
    from dynamo_tpu.mocker import MockerConfig, MockerWorker
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

    def _cfg(cluster: str) -> RuntimeConfig:
        cfg = RuntimeConfig.from_env()
        cfg.discovery_backend = "mem"
        cfg.discovery_path = cluster
        cfg.request_plane = "tcp"
        cfg.tcp_host = "127.0.0.1"
        cfg.event_plane = "mem"
        cfg.system_enabled = False
        cfg.lease_ttl_secs = 1.0
        return cfg

    async def one_side(session_cache: bool) -> dict:
        cluster = uuid.uuid4().hex
        workers = []
        for _ in range(2):
            rt = await DistributedRuntime(_cfg(cluster)).start()
            worker = MockerWorker(
                rt, model_name="mock-model",
                config=MockerConfig.from_timing_preset(
                    "tpu-v5e-qwen3-0.6b", speedup_ratio=20.0,
                    num_blocks=4096),
                load_publish_interval=0.2)
            await worker.start()
            workers.append((rt, worker))
        frt = await DistributedRuntime(_cfg(cluster)).start()
        frontend = Frontend(frt, host="127.0.0.1", port=0,
                            router_mode="kv")
        await frontend.start()
        try:
            for _ in range(100):
                if frontend.manager.get("mock-model") is not None:
                    break
                await asyncio.sleep(0.05)
            # ~13 mock-tokenizer tokens per synthetic word: 128 words
            # is ~1.7k prompt tokens — two turns stay inside the mock
            # card's 8k context with a prefill big enough to dominate
            # TTFT.
            bench = MultiturnBench(
                f"http://127.0.0.1:{frontend.port}", "mock-model",
                turns=2, isl_mean=128, osl_mean=8,
                followup_isl_mean=8, session_cache=session_cache)
            level = await bench.run_level(concurrency=4,
                                          conversations=16)
            return level.summary()
        finally:
            await frontend.close()
            await frt.shutdown()
            for rt, worker in workers:
                await worker.close()
                await rt.shutdown()

    async def both() -> tuple[dict, dict]:
        return await one_side(True), await one_side(False)

    on, off = asyncio.run(both())

    def turn_ttft(summary: dict, turn: int):
        return summary.get("ttft_ms_by_turn", {}).get(str(turn))

    cold = turn_ttft(on, 0)
    cached = turn_ttft(on, 1)
    return {
        "profile": "2-worker v5e mocker, kv router, 2-turn sessions, "
                   "~zero cross-session overlap",
        "pinned_cold_ttft_ms": cold,
        "pinned_cached_ttft_ms": cached,
        "cached_speedup": (round(cold / cached, 2)
                           if cold and cached else None),
        "unpinned_cold_ttft_ms": turn_ttft(off, 0),
        "unpinned_cached_ttft_ms": turn_ttft(off, 1),
        "errors": on.get("errors", 0) + off.get("errors", 0),
    }


def bench_drain_point() -> dict:
    """Graceful-drain point for BENCH_r06 (ISSUE 15 / docs/
    fault-tolerance.md departure ladder): evict one worker of a mocker
    fleet mid-decode and record what the departure cost — wall time of
    the drain (announce -> handoff -> deregistration-ready), sequences
    per ladder rung, and the re-prefilled-token count on the KV-handoff
    path (the zero-drop headline: 0 on the handoff rung vs a full
    prompt re-prefill per stream on the replay fallback). Runs the same
    in-process scenario the chaos-drain CI job gates on
    (dynamo_tpu/mocker/drain_chaos.py)."""
    import asyncio

    from dynamo_tpu.mocker.drain_chaos import DrainChaosParams, run_scenario

    params = DrainChaosParams(n_workers=2, n_streams=8, max_tokens=40,
                              decode_base_ms=20.0)
    report = asyncio.run(run_scenario(params, fallback_pass=True))

    def rungs(key: str) -> dict:
        rep = report[key]["drain_report"] or {}
        return {"handoff": len(rep.get("handoff") or []),
                "replay": len(rep.get("replay") or []),
                "errored": rep.get("errored", 0),
                "duration_ms": rep.get("duration_ms"),
                "reprefill_tokens": report[key]["reprefill_tokens"]}

    return {
        "profile": (f"{params.n_workers}-worker mocker fleet, "
                    f"{params.n_streams} live streams, evict 1 "
                    "mid-decode"),
        "deadline_secs": params.deadline_secs,
        "passed": report["passed"],
        "handoff_path": rungs("drain_handoff"),
        "replay_fallback": rungs("drain_replay"),
        "bit_identical": all(
            c["ok"] for c in report["assertions"]
            if c["name"] == "bit_identical_to_undrained_run"),
    }


def bench_cold_start_point() -> dict:
    """Cold-start ladder A/B for BENCH_r07 (ISSUE 17 / docs/
    elasticity.md fast-start plane). Two layers:

    * a closed-form matrix from the v5e-calibrated cold-start preset
      (mocker/engine.py coldstart_phases): arrival total with peer
      striping vs single-source G4 fetch, crossed with warm vs cold
      compile cache — the headline speedups the fast-start plane buys;
    * a measured point: the quick chaos-spot scenario (evict+replace
      under a live ramp, dynamo_tpu/mocker/spot_chaos.py) recording the
      replacement's wall-clock first-token and capacity-recovery times
      against its pinned budget — the same contract the chaos-spot CI
      job gates on."""
    import asyncio

    from dynamo_tpu.mocker.engine import MockerConfig, TIMING_PRESETS
    from dynamo_tpu.mocker.engine import coldstart_phases
    from dynamo_tpu.mocker.spot_chaos import SpotChaosParams, run_scenario

    preset = TIMING_PRESETS["tpu-v5e-coldstart"]

    def cell(striped: bool, warm: bool) -> dict:
        cfg = MockerConfig(**{**preset, "fetch_striped": striped,
                              "compile_cache_warm": warm})
        phases = coldstart_phases(cfg)
        return {"phases_s": {k: round(v, 3) for k, v in phases.items()},
                "total_s": round(sum(phases.values()), 3)}

    matrix = {
        "striped_warm": cell(True, True),
        "striped_cold": cell(True, False),
        "single_warm": cell(False, True),
        "single_cold": cell(False, False),
    }
    params = SpotChaosParams(n_workers=2, n_streams=10,
                             evict_cycles=1, streams_before_evict=3)
    report = asyncio.run(run_scenario(params))
    cycles = report["spot"]["cycles"]
    return {
        "profile": (f"v5e preset: {preset['weight_bytes'] / 1e9:.1f}GB "
                    f"weights, {preset['fetch_donors']} donors x "
                    f"{preset['fetch_gbps_per_donor']:.0f}Gbps striped "
                    f"vs {preset['fetch_gbps_single']:.0f}Gbps single"),
        "modeled": matrix,
        "striped_fetch_speedup": round(
            matrix["single_warm"]["phases_s"]["fetch"]
            / matrix["striped_warm"]["phases_s"]["fetch"], 2),
        "warm_cache_speedup": round(
            matrix["striped_cold"]["total_s"]
            / matrix["striped_warm"]["total_s"], 2),
        "measured_spot": {
            "passed": report["passed"],
            "budget_secs": params.coldstart_budget_secs,
            "first_token_secs": [
                c["coldstart"] and round(c["coldstart"]["total_secs"], 3)
                for c in cycles],
            "capacity_recovered_secs": [
                c["recovered_secs"] and round(c["recovered_secs"], 3)
                for c in cycles],
        },
    }


def bench_goodput_point() -> dict:
    """Goodput-vs-load curve with the overload-control loop off vs on
    (ROADMAP item 4 / ISSUE 9) — a chip-free robustness point. An
    open-loop
    Poisson ramp walks offered load past the mocker cluster's capacity
    knee twice; per offered-rate bucket the curve reports SLO-good
    requests/s and the shed fraction. The headline is dominance past the
    knee: the deadline-aware admission loop sheds early instead of
    FCFS-ing doomed work into late 504s, so goodput flattens instead of
    collapsing (dynamo_tpu/mocker/overload.py, the same scenario the
    chaos-overload CI job gates on)."""
    import asyncio

    from dynamo_tpu.mocker.overload import OverloadParams, run_scenario

    params = OverloadParams(ramp_secs=16.0, ramp_end_rps=28.0)
    report = asyncio.run(run_scenario(params, pd_sweep=False))

    def curve(key: str) -> list[dict]:
        return [{"offered_rps": b["offered_rps"],
                 "goodput_rps": b["goodput_rps"],
                 "shed_frac": b["shed_frac"]}
                for b in report[key]["buckets"]]

    knee = report.get("knee_bucket", 0)
    on = report["ramp_on"]["buckets"]
    off = report["ramp_off"]["buckets"]
    past = range(knee + 1, min(len(on), len(off)))
    return {
        "profile": (f"{params.n_decode}-worker mocker, open-loop ramp "
                    f"{params.ramp_start_rps}->{params.ramp_end_rps} rps"),
        "slo_ttft_ms": params.slo_ttft_ms,
        "deadline_secs": params.deadline_secs,
        "knee_bucket": knee,
        "loop_on": curve("ramp_on"),
        "loop_off": curve("ramp_off"),
        "past_knee_goodput_on": round(
            sum(on[i]["goodput_rps"] for i in past), 2),
        "past_knee_goodput_off": round(
            sum(off[i]["goodput_rps"] for i in past), 2),
        "assertions_passed": report["passed"],
    }


def bench_two_class_point() -> dict:
    """Two-class goodput A/B (ROADMAP item 5 / ISSUE 14): an
    interactive tenant at a fixed below-knee rate plus a
    batch tenant ramping ~2x past the knee, served twice — untagged
    FCFS vs the full QoS plane (priority classes, fair-share quotas,
    class-strict queues, preempt-to-park). The headline: the
    interactive goodput curve holds flat past the knee at <= 10% total
    goodput cost, with batch absorbing the shed and the preemptions
    (dynamo_tpu/mocker/overload.py, the same scenario the
    chaos-two-tenant CI job gates on; docs/multi-tenancy.md)."""
    import asyncio

    from dynamo_tpu.mocker.overload import (
        TwoTenantParams,
        run_two_tenant_scenario,
    )

    params = TwoTenantParams(ramp_secs=16.0, batch_end_rps=20.0)
    report = asyncio.run(run_two_tenant_scenario(params))

    def tenant_curve(key: str, tenant: str) -> list[dict]:
        return [{"offered_rps": b["offered_rps"],
                 "goodput_rps": b["goodput_rps"],
                 "shed_frac": b["shed_frac"]}
                for b in report[key]["tenant_buckets"].get(tenant, [])]

    qos, base = report["qos_on"], report["qos_off"]
    return {
        "profile": (f"{params.n_decode}-worker mocker; interactive "
                    f"{params.interactive_rps} rps fixed, batch "
                    f"{params.batch_start_rps}->{params.batch_end_rps} "
                    "rps ramp"),
        "slo_ttft_ms": params.slo_ttft_ms,
        "knee_bucket": report.get("knee_bucket", 0),
        "interactive_qos": tenant_curve("qos_on", "interactive"),
        "interactive_fcfs": tenant_curve("qos_off", "interactive"),
        "batch_qos": tenant_curve("qos_on", "batch"),
        "batch_fcfs": tenant_curve("qos_off", "batch"),
        "good_total_qos": qos["good_total"],
        "good_total_fcfs": base["good_total"],
        "total_cost_frac": (round(1 - qos["good_total"]
                                  / base["good_total"], 4)
                            if base["good_total"] else None),
        "preempt": {k: qos["metrics"][f"preempt_{k}"]
                    for k in ("park", "migrate", "resume")},
        "tenant_shed": {
            "batch": qos["metrics"]["tenant_shed_batch"],
            "interactive": qos["metrics"]["tenant_shed_interactive"],
        },
        "assertions_passed": report["passed"],
    }


def main() -> None:
    import jax

    device = jax.devices()[0]
    device_kind = getattr(device, "device_kind", "cpu").lower()

    env_model = os.environ.get("DYNT_BENCH_MODEL")
    model_path = os.environ.get("DYNT_BENCH_MODEL_PATH")
    if env_model or model_path:
        # Single-model mode: bench exactly what the caller asked for.
        result = bench_one(
            env_model or "qwen3-0.6b", model_path=model_path,
            batch=int(os.environ.get("DYNT_BENCH_BS", "8")),
            kv_dtype=os.environ.get("DYNT_BENCH_KV_DTYPE", "model"),
            weight_dtype=os.environ.get("DYNT_BENCH_WEIGHT_DTYPE",
                                        "model"),
            num_pages=int(os.environ.get("DYNT_BENCH_PAGES", "1024")),
            prompt_len=int(os.environ.get("DYNT_BENCH_CTX", "256")),
            decode_steps=int(os.environ.get("DYNT_BENCH_STEPS", "256")),
            prefill_chunk=int(os.environ.get("DYNT_BENCH_PREFILL_CHUNK",
                                             "1024")),
            do_prefill=os.environ.get("DYNT_BENCH_PREFILL", "1") != "0",
            do_ttft=os.environ.get("DYNT_BENCH_TTFT", "1") != "0",
            device_kind=device_kind,
        )
        print(json.dumps(result))
        return

    if "cpu" in device_kind:
        # CPU smoke: only the toy — a 7B random-init forward on CPU is
        # tens of minutes of compile+run for zero perf signal.
        result = bench_one("qwen3-0.6b", device_kind=device_kind)
        if os.environ.get("DYNT_BENCH_DISAGG", "1") != "0":
            result["disagg"] = bench_disagg_point()
        if os.environ.get("DYNT_BENCH_GOODPUT", "1") != "0":
            result["goodput_vs_load"] = bench_goodput_point()
        if os.environ.get("DYNT_BENCH_TWO_CLASS", "1") != "0":
            result["two_class_goodput"] = bench_two_class_point()
        if os.environ.get("DYNT_BENCH_SESSION", "1") != "0":
            result["session_cache"] = bench_session_point()
        if os.environ.get("DYNT_BENCH_DRAIN", "1") != "0":
            result["drain"] = bench_drain_point()
        if os.environ.get("DYNT_BENCH_COLD_START", "1") != "0":
            result["cold_start"] = bench_cold_start_point()
        print(json.dumps(result))
        return

    # Flagship-first (VERDICT r4 item 3): the driver-captured headline is
    # the representative 7B config in its FASTEST serving shape — W4A16
    # weights (packed-int4 Pallas matmuls, ops/q4_linear.py: 2.87x decode
    # over bf16 weights / 1.70x over W8A16, measured r5) + int8 KV (the
    # capacity lever; at 7B bf16 weights + bf16 KV exceed HBM).
    # Secondaries: the int8- and bf16-weight 7B configs and the toy.
    # BENCH_r06 capture prep (ROADMAP item 1): speculation ON for the
    # flagship serving block (the spec block records acceptance_rate and
    # the DYNT_SPEC_MAX_K it ran) so spec, kvbm_offload and disagg are
    # all captured by ONE `python bench.py` on silicon.
    os.environ.setdefault("DYNT_SPEC_ENABLE", "1")
    result = bench_one("mistral-7b", kv_dtype="int8",
                       weight_dtype="int4", num_pages=448,
                       device_kind=device_kind)
    secondary = []
    for label, kwargs in (
        ("mistral-7b int8 weights",
         dict(kv_dtype="int8", weight_dtype="int8", num_pages=448,
              do_ttft=False)),
        ("mistral-7b bf16 weights",
         dict(kv_dtype="int8", num_pages=448, do_ttft=False)),
        ("qwen3-0.6b", dict(do_ttft=False)),
    ):
        gc.collect()
        jax.clear_caches()
        try:
            secondary.append(bench_one(
                "mistral-7b" if "mistral" in label else "qwen3-0.6b",
                device_kind=device_kind, **kwargs))
        except Exception as exc:  # noqa: BLE001 — the flagship number
            # must survive a secondary-bench failure
            secondary.append({"metric": label, "error": repr(exc)})
    result["secondary"] = secondary
    if os.environ.get("DYNT_BENCH_DISAGG", "1") != "0":
        try:
            result["disagg"] = bench_disagg_point()
        except Exception as exc:  # noqa: BLE001 — chip-free point must
            # never cost the round its silicon numbers
            result["disagg"] = {"error": repr(exc)}
    if os.environ.get("DYNT_BENCH_GOODPUT", "1") != "0":
        try:
            result["goodput_vs_load"] = bench_goodput_point()
        except Exception as exc:  # noqa: BLE001 — chip-free point must
            # never cost the round its silicon numbers
            result["goodput_vs_load"] = {"error": repr(exc)}
    if os.environ.get("DYNT_BENCH_TWO_CLASS", "1") != "0":
        try:
            result["two_class_goodput"] = bench_two_class_point()
        except Exception as exc:  # noqa: BLE001 — chip-free point must
            # never cost the round its silicon numbers
            result["two_class_goodput"] = {"error": repr(exc)}
    if os.environ.get("DYNT_BENCH_SESSION", "1") != "0":
        try:
            result["session_cache"] = bench_session_point()
        except Exception as exc:  # noqa: BLE001 — chip-free point must
            # never cost the round its silicon numbers
            result["session_cache"] = {"error": repr(exc)}
    if os.environ.get("DYNT_BENCH_DRAIN", "1") != "0":
        try:
            result["drain"] = bench_drain_point()
        except Exception as exc:  # noqa: BLE001 — chip-free point must
            # never cost the round its silicon numbers
            result["drain"] = {"error": repr(exc)}
    if os.environ.get("DYNT_BENCH_COLD_START", "1") != "0":
        try:
            result["cold_start"] = bench_cold_start_point()
        except Exception as exc:  # noqa: BLE001 — chip-free point must
            # never cost the round its silicon numbers
            result["cold_start"] = {"error": repr(exc)}
    print(json.dumps(result))


if __name__ == "__main__":
    main()
