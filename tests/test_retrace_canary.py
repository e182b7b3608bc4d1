"""Retrace canary: the runtime half of dynajit's DJ1xx static pass.

The compile listener (engine/model_runner.py, jax.monitoring) counts
every XLA backend compile into dynamo_jit_compiles_total{fn}. This tier
drives a mocker-free decode loop — varying batch occupancy, sequence
lengths, speculation on and off — and pins the two properties the
checked-in jit-signature registry (tools/dynajit/signatures/) predicts:

  * warmup compiles EXACTLY one executable per (entry point, bounded
    cache key) combination exercised — no hidden variants;
  * steady state compiles NOTHING: occupancy, lengths, and sampling
    params are data, not cache keys.

A regression that adds a per-request value to a jit key (the DJ1xx
hazard class) fails the steady-state assertion here even if dynajit's
static view was evaded.
"""

import json
import pathlib

import numpy as np
import pytest

from dynamo_tpu.engine import ModelRunner, PrefillRow, RunnerConfig
from dynamo_tpu.models import get_config
from dynamo_tpu.parallel import MeshConfig, make_mesh
from dynamo_tpu.runtime.metrics import REGISTRY

REGISTRY_PATH = (pathlib.Path(__file__).parent.parent / "tools" /
                 "dynajit" / "signatures" / "jit_surface.json")

# Entry-point labels the compile listener attributes serving compiles to.
SCOPES = ("decode", "decode_multi", "decode_spec", "prefill",
          "prefill_batch", "prefill_ring", "embed", "unscoped")


def _snapshot() -> dict:
    return {fn: REGISTRY.get_sample_value("dynamo_jit_compiles_total",
                                          {"fn": fn}) or 0.0
            for fn in SCOPES}


def _delta(before: dict, after: dict) -> dict:
    return {fn: after[fn] - before[fn] for fn in SCOPES
            if after[fn] != before[fn]}


def _runner():
    return ModelRunner(
        get_config("tiny-test"),
        RunnerConfig(page_size=4, num_pages=64, max_batch=4,
                     max_pages_per_seq=16, prefill_buckets=(8, 16, 32)),
        make_mesh(MeshConfig()),
        seed=0,
    )


class TestRetraceCanary:
    def test_registry_predicts_bounded_serving_surface(self):
        """Every call-form jit site in the runner's serving methods has
        a bounded disposition in the checked-in registry (a dict cache
        or an attribute — never per-call): the static prediction the
        runtime assertions below are checked against."""
        assert REGISTRY_PATH.exists(), (
            "jit-signature registry missing; run "
            "`python -m tools.dynajit --registry-update`")
        sites = json.loads(REGISTRY_PATH.read_text())["sites"]
        runner_sites = [
            s for s in sites
            if s["file"].endswith("engine/model_runner.py")
            and s["scope"].startswith("ModelRunner.")
            and s["scope"].split(".")[-1] not in ("__init__", "reshard")
            and s["form"] == "call"]
        assert runner_sites, "registry lost the runner's jit surface"
        for site in runner_sites:
            assert site["disposition"].startswith(("cached:", "attr:",
                                                   "returned")), site

    def test_steady_state_decode_compiles_are_bounded(self):
        pre = _snapshot()
        runner = _runner()
        if sum(_snapshot().values()) == sum(pre.values()):
            # Engine construction compiles param/KV init; observing
            # nothing means this jax does not emit the backend-compile
            # monitoring event (the counter is inert, not broken).
            pytest.skip("jax.monitoring compile events not observed")
        b, p = 4, 16
        base = _snapshot()

        def prefill(tokens):
            runner.prefill_chunk(
                np.asarray(tokens, np.int32), 0,
                np.arange(1, p + 1, dtype=np.int32) % runner.config.num_pages,
                len(tokens), (0.0, 1.0, 0, 0))

        def decode(active, kv_lens, seeds=0):
            runner.decode(
                np.zeros(b, np.int32), np.asarray(kv_lens, np.int32) - 1,
                np.tile(np.arange(1, p + 1, dtype=np.int32)
                        % runner.config.num_pages, (b, 1)),
                np.asarray(kv_lens, np.int32),
                np.asarray(active, bool), np.ones(b, np.float32),
                np.ones(b, np.float32), np.zeros(b, np.int32),
                np.full(b, seeds, np.uint32))

        def spec(kv_lens):
            runner.decode_spec(
                np.zeros(b, np.int32), np.ones((b, 2), np.int32),
                np.asarray(kv_lens, np.int32) - 1,
                np.tile(np.arange(1, p + 1, dtype=np.int32)
                        % runner.config.num_pages, (b, 1)),
                np.asarray(kv_lens, np.int32), np.ones(b, bool),
                np.ones(b, np.float32), np.ones(b, np.float32),
                np.zeros(b, np.int32), np.zeros(b, np.uint32))

        # -- warmup: touch each (entry, cache-key) combo once ----------
        prefill([1] * 5)        # bucket 8
        prefill([1] * 12)       # bucket 16
        decode([1, 1, 1, 1], [4, 4, 4, 4])
        spec([6, 6, 6, 6])
        warm = _delta(base, _snapshot())
        # Registry-predicted key space for the combos exercised:
        # decode -> attr:_decode_fn (1), prefill -> cached:_prefill_fns
        # keyed by bucket (2 buckets touched), decode_spec ->
        # cached:_decode_spec_fns keyed (t, want_logits) (1 combo).
        assert warm.get("decode") == 1, warm
        assert warm.get("prefill") == 2, warm
        assert warm.get("decode_spec") == 1, warm

        # -- steady state: occupancy/lengths/seeds are DATA ------------
        steady = _snapshot()
        prefill([2] * 7)                 # bucket 8 again
        prefill([3] * 15)                # bucket 16 again
        for step in range(6):
            active = [1, 1, 1, 1] if step % 2 == 0 else [1, 0, 1, 0]
            lens = [4 + step, 5 + step, 4, 6]
            decode(active, lens, seeds=step)
        spec([12, 13, 14, 15])
        assert _delta(steady, _snapshot()) == {}, (
            "steady-state decode recompiled: a per-request value leaked "
            "into a jit cache key (DJ1xx hazard) — "
            f"{_delta(steady, _snapshot())}")

    def test_prewarm_compiles_exactly_the_predicted_key_space(self):
        """The fast-start pre-warm pass (docs/elasticity.md): prewarm()
        compiles the registry-predicted steady-state surface — decode
        (one key), EVERY prefill bucket, the configured spec-verify
        combo — and NOTHING after it compiles again: a warm-cache
        arrival that replays these from the persistent compile cache
        serves its whole steady state without a single trace."""
        pre = _snapshot()
        runner = _runner()
        if sum(_snapshot().values()) == sum(pre.values()):
            pytest.skip("jax.monitoring compile events not observed")
        base = _snapshot()
        runner.prewarm(spec_widths=[2])
        warm = _delta(base, _snapshot())
        assert warm.get("decode") == 1, warm
        assert warm.get("prefill") == len(runner.config.prefill_buckets), \
            warm
        assert warm.get("decode_spec") == 1, warm

        # prewarm is idempotent — the warm-arrival shape
        again = _snapshot()
        runner.prewarm(spec_widths=[2])
        assert _delta(again, _snapshot()) == {}, _delta(again, _snapshot())

        # steady state after prewarm compiles NOTHING: every bucket,
        # varying occupancy/lengths/seeds, and the spec-verify path
        b, p = 4, 16
        steady = _snapshot()
        for n in (5, 12, 20):  # lands in buckets 8, 16, 32
            runner.prefill_chunk(
                np.full(n, 2, np.int32), 0,
                np.arange(1, p + 1, dtype=np.int32)
                % runner.config.num_pages,
                n, (0.0, 1.0, 0, 0))
        for step in range(4):
            kv = np.asarray([4 + step, 5, 6, 4 + step], np.int32)
            runner.decode(
                np.zeros(b, np.int32), kv - 1,
                np.tile(np.arange(1, p + 1, dtype=np.int32)
                        % runner.config.num_pages, (b, 1)),
                kv, np.asarray([1, step % 2, 1, 1], bool),
                np.ones(b, np.float32), np.ones(b, np.float32),
                np.zeros(b, np.int32), np.full(b, step, np.uint32))
        runner.decode_spec(
            np.zeros(b, np.int32), np.ones((b, 2), np.int32),
            np.full(b, 7, np.int32),
            np.tile(np.arange(1, p + 1, dtype=np.int32)
                    % runner.config.num_pages, (b, 1)),
            np.full(b, 8, np.int32), np.ones(b, bool),
            np.ones(b, np.float32), np.ones(b, np.float32),
            np.zeros(b, np.int32), np.zeros(b, np.uint32))
        assert _delta(steady, _snapshot()) == {}, (
            "post-prewarm steady state recompiled — the pre-warm pass "
            "missed part of the predicted key space: "
            f"{_delta(steady, _snapshot())}")

    def test_program_keys_are_stable_and_bounded_by_the_prewarm_grid(self):
        """The key a program is built and launched under is the entry
        and its static shape, nothing of a request: two runners walk the
        same keys, traffic of every occupancy, length and seed reaches
        only keys `prewarm(launches=True)` derived from the runner's own
        buckets, row counts and widths, and builds nothing new."""
        from dynamo_tpu.engine.model_runner import bucket_table_width

        def warmed():
            runner = _runner()
            runner.prewarm(spec_widths=[2], launches=True, block=4)
            return runner

        runner, twin = warmed(), warmed()
        grid = set(runner.program_launches)
        assert grid == set(twin.program_launches)  # stable across runs
        buckets, b, p = (8, 16, 32), 4, 16
        widths = sorted({bucket_table_width(n, p) for n in range(1, p + 1)})
        assert grid == (
            {("decode", f"decode[w{p}]"),
             ("decode_spec", f"decode_spec[w{p},k2]")}
            | {("prefill", f"prefill[{t}]") for t in buckets}
            | {("prefill_batch", f"prefill_batch[{r}x{t}]")
               for r in (2, 4) for t in buckets}
            | {("decode_multi", f"decode_multi[w{w},b4,{src}]")
               for w in widths for src in ("fed", "chained")})
        assert all(row == [0, 0] for row in runner.program_launches.values())

        steady = _snapshot()
        table = np.arange(1, p + 1, dtype=np.int32) % runner.config.num_pages
        greedy = (0.0, 1.0, 0, 0)
        for n in (3, 8, 13, 30):
            runner.prefill_chunk(np.full(n, 2, np.int32), 0, table, n, greedy)
        for rows, n in ((2, 5), (3, 12), (4, 20)):
            runner.prefill_chunk_batch(
                [PrefillRow(np.full(n + i, 3, np.int32), 0, table, n + i,
                            greedy)
                 for i in range(rows)])
        ones, zeros = np.ones(b, np.float32), np.zeros(b, np.int32)
        for step, width in enumerate(widths * 2):
            kv = np.asarray([3 + step, 4, 5, 3 + step], np.int32)
            args = (kv - 1, np.tile(table[:width], (b, 1)), kv,
                    np.asarray([1, step % 2, 1, 1], bool), ones, ones,
                    zeros, np.full(b, step, np.uint32))
            toks = runner.decode_multi(zeros, *args, k=4, return_device=True)
            runner.decode_multi(toks[-1], *args, k=4, return_device=True)
        assert set(runner.program_launches) == grid, (
            set(runner.program_launches) - grid)
        assert _delta(steady, _snapshot()) == {}
        # launches and useful tokens land under the key of the shape
        launched = runner.program_launches
        assert launched[("prefill", "prefill[8]")] == [2, 11]
        assert launched[("prefill", "prefill[32]")] == [1, 30]
        assert launched[("prefill_batch", "prefill_batch[4x16]")] == [
            1, 12 + 13 + 14]
        assert launched[("prefill_batch", "prefill_batch[4x32]")] == [
            1, 20 + 21 + 22 + 23]
        for width in widths:
            for src in ("fed", "chained"):
                assert launched[("decode_multi",
                                 f"decode_multi[w{width},b4,{src}]")][0] == 2
        assert twin.program_launches[("prefill", "prefill[8]")] == [0, 0]
