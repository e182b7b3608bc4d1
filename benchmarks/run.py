#!/usr/bin/env python3
"""The benchmark: one cell, one run.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

A run is a new process tree on a machine that holds the cell's chip:

  set-up   native build (once), worker + frontend children, the cell's
           own shapes warmed by enumeration, a ramp of the real traffic
  window   `--seconds` of the cell's traffic, timed on this clock from
           the moment each request was due
  check    once the servers have exited: a seeded sample of the requests
           the window finished, their served token ids (from the
           frontend's recording) against the plain float32 reference

and its last line of standard output is the contract's JSON object. With
`--trace 1` the worker's /debug/profile captures a few seconds of steady
state inside the window and the line carries the per-layer metrics.

`--trace 2` is one run that measures, then traces (`trace_in_run` in
BENCHMARK.json). Up to the moment the window closes it is a `--trace 0`
run: the same set-up, warm-up, ramp, window and scrapes around it, no
profiler anywhere, and `correct`, `attempted`, `failed` and every
end-to-end number come from that closed window. The same traffic then
simply does not stop: in a tail after the window the worker's
/debug/profile is started and stopped once and that trace thrown away
(the first start's cost falls into no number), then it captures
CAPTURE_MS of the steady state. The tail's traffic lasts as long as the
traced span does (the profiler's start, which the program reports, plus
CAPTURE_MS and a margin), not as long as the capture takes to come back:
collecting and writing the trace takes several times the span and needs
no traffic, so what is in flight runs to its end meanwhile (the worker is
asked for the `.xplane.pb` alone, `export=xplane`: the reduction reads
nothing else). The tail's requests lie in no window. The last line carries the end-to-end
and the per-layer metrics side by side, `device.busy_s`/`window_s` of the
capture and `breakdown`. Readers of counters and of the client's
timelines get the measured window (untraced); readers of the trace get the
tail's capture. The report line before it says what tracing cost while it
was on (`tail`: the clients' tokens/s during the capture beside the
window's). The program's counters and stage histograms are always on; its
profiler spans (`prefill`/`decode` steps, `sched.*` sections) exist only
inside a capture, so /debug/profile is the one control.

This process never imports JAX: a chip belongs to one process.

Other modes, for benchmark PRs (none prints a result line):
  --sweep R1,R2,..     one server lifetime, open loop at each rate
  --check-seeds A,B,.. one server lifetime, a window per seed, then the
                       reference over every window's sample; with
                       --control N the first N also run the reference's
                       lower-precision controls, one axis at a time
  --control 1          in a plain run: the controls over this run's own
                       sample, in the report line (the driver never asks)
"""

from __future__ import annotations

import argparse
import ast
import asyncio
import importlib.util
import json
import os
import random
import re
import shutil
import subprocess
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from dtbench import shapes, stats, traffic  # noqa: E402
from dtbench.client import Client  # noqa: E402
from dtbench.fleet import Fleet, FleetError, build_native, tail  # noqa: E402

# Exit codes: 0 = a result line was printed on the cell's chip(s);
# 1 = the run failed; 2 = no accelerator (nothing on stdout);
# 3 = the program is not in this checkout (nothing on stdout);
# 10 = a CPU rehearsal ran to its end (never 0: it is no chip result).
EXIT_FAILED, EXIT_NO_CHIP, EXIT_NO_PROGRAM, EXIT_REHEARSAL = 1, 2, 3, 10

MODEL_WAIT_SECS = 900.0
CAPTURE_MS = 2500  # one capture of steady state per traced run
# --trace 2: the profiler's first start and stop in the worker are taken
# and thrown away before the capture that is read. The tail's traffic
# covers the traced span and a margin (the request's way to the worker,
# and a start that takes longer than the thrown-away one did); it ends at
# the latest TAIL_MAX_SECS after the window, and requests for that long
# are generated (the generator is prefix-stable in its count).
PROFILER_WARM_MS = 50
TRACED_SPAN_MARGIN_SECS = 2.0
TAIL_MAX_SECS = 45.0
# what a path the configuration's file names is made of (BENCHMARK.json's
# contract for a file under `paths`)
NAMED_PATH = re.compile(r"^[A-Za-z0-9_.\-][A-Za-z0-9_.\-/]{0,199}$")


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(name: str, path: str):
    """A file of the benchmark, loaded by the name something gave it."""
    spec = importlib.util.spec_from_file_location(
        re.sub(r"[^A-Za-z0-9_]", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Plan:
    """What BENCHMARK.json says about one cell, resolved to files: found
    by name, so a new cell, configuration, mix or per-layer metric is new
    files and new entries and no edit here. That holds for a
    configuration of another architecture too: what depends on the
    architecture is named by the configuration's own file, each a path
    from BENCHMARK.json's directory to a file under its `paths`, and
    absent means the dense code that is here:

      reference.module   its plain reference (the contract is at the top
                         of dtbench/reference.py; absent: the dense one)
      shapes             its byte and operation counts (the interface is
                         in dtbench/shapes.py's docstring; absent: that
                         module), which every reader gets as ctx["shapes"]
      serve.worker_args  further flags for its worker, after the seven
                         every worker gets (absent: none)

    All of it is resolved here, before any child starts: a missing file
    or a module without its interface is a SystemExit that names the
    configuration, the key and the path."""

    def __init__(self, bench_path: str, workload: str) -> None:
        self.bench = load_json(bench_path)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; "
                             f"have {sorted(cells)}")
        self.cell = cells[workload]
        entry = {c["name"]: c for c in self.bench["configs"]}[
            self.cell["config"]]
        self.root = os.path.dirname(os.path.abspath(bench_path))
        self.config = load_json(os.path.join(self.root, entry["file"]))
        self.mix = load_json(os.path.join(
            HERE, "mixes", self.cell["traffic"] + ".json"))
        self.peaks = load_json(os.path.join(HERE, "peaks.json"))
        self.reference_module = self.named_reference()
        self.shapes = self.named_shapes()
        self.worker_args = self.config["serve"].get("worker_args", [])
        if not (isinstance(self.worker_args, list) and all(
                isinstance(a, str) for a in self.worker_args)):
            self.refuse("serve.worker_args", self.worker_args,
                        "is not a list of strings")

    def refuse(self, key: str, value, why: str):
        raise SystemExit(
            f"benchmarks/run.py: configuration {self.cell['config']!r}, "
            f"key {key!r}: {value!r} {why}; nothing was run and there is "
            "no result")

    def named_file(self, key: str, rel) -> str:
        """The file a key of the configuration names: a relative path
        under one of BENCHMARK.json's `paths`, so that the yardstick
        stays where later PRs cannot change it."""
        if not (isinstance(rel, str) and NAMED_PATH.match(rel)
                and rel.endswith(".py") and ".." not in rel.split("/")):
            self.refuse(key, rel, "is no relative path of a .py file")
        if not any(rel.startswith(p.rstrip("/") + "/")
                   for p in self.bench["paths"]):
            self.refuse(key, rel, f"lies under none of the benchmark's "
                                  f"paths {self.bench['paths']}")
        path = os.path.join(self.root, rel)
        if not os.path.isfile(path):
            self.refuse(key, rel, f"is missing ({path})")
        return path

    def named_reference(self) -> str | None:
        """The path of the configuration's own reference, or None for the
        dense one. It runs in the reference child (it imports JAX, and
        this process may not), so its interface is read off its source."""
        rel = self.config["reference"].get("module")
        if rel is None:
            return None
        path = self.named_file("reference.module", rel)
        with open(path) as f:
            try:
                tree = ast.parse(f.read(), path)
            except SyntaxError as exc:
                self.refuse("reference.module", rel, f"does not parse: {exc}")
        if not any(isinstance(node, ast.FunctionDef)
                   and node.name == "logits_for" for node in tree.body):
            self.refuse("reference.module", rel,
                        f"defines no logits_for(samples, cfg, pad_to, "
                        f"lower=None) ({path})")
        return path

    def named_shapes(self):
        """The configuration's own counts, loaded here (readers run in
        this process: it imports no JAX), or dtbench.shapes."""
        rel = self.config.get("shapes")
        if rel is None:
            return shapes
        path = self.named_file("shapes", rel)
        had_jax = "jax" in sys.modules
        module = load_module("shapes_of_" + self.cell["config"], path)
        if "jax" in sys.modules and not had_jax:
            self.refuse("shapes", rel, "imports JAX, and this process "
                                       "may not: a chip belongs to one")
        missing = [fn for fn in shapes.INTERFACE
                   if not callable(getattr(module, fn, None))]
        if missing:
            self.refuse("shapes", rel, f"lacks {missing} ({path})")
        return module

    def worker_flags(self) -> list[str]:
        """The worker's command line after `-m dynamo_tpu.worker`: seven
        flags every configuration states, then its own."""
        serve = self.config["serve"]
        return ["--model", serve["model"],
                "--weight-dtype", serve["weight_dtype"],
                "--kv-dtype", serve["kv_dtype"],
                "--page-size", str(serve["page_size"]),
                "--num-pages", str(serve["num_pages"]),
                "--max-batch", str(serve["max_batch"]),
                "--max-pages-per-seq", str(serve["max_pages_per_seq"]),
                *self.worker_args]

    def reference_job(self, sets: list[dict]) -> dict:
        """What the reference child is handed. `config` is what the dense
        reference reads: the file's scalars with the `reference` object's
        keys on top. `file` is the whole file, nested keys too, which a
        named module gets merged under `config`; `module` is its path."""
        cfg = dict(self.config)
        ref = cfg.pop("reference")
        return {
            "config": {**{k: v for k, v in cfg.items()
                          if not isinstance(v, (dict, list))}, **ref},
            "pad_to": -(-int(self.mix["max_total_tokens"]) // 256) * 256,
            "controls": self.config["check"]["controls"],
            "sets": sets,
            "file": self.config,
            "module": self.reference_module,
        }

    def context(self, **of_the_run) -> dict:
        """What a per-layer reader is handed: the cell's files, the
        configuration's counts, the client's arithmetic, the other
        readers, and what the run measured."""
        ctx = {"config": self.config, "mix": self.mix, "cell": self.cell,
               "shapes": self.shapes, "stats": stats, "layer": Plan.layer,
               **of_the_run}
        ctx["read"] = lambda name: Plan.reader(name)(ctx)
        return ctx

    def metrics(self, group: str) -> list[dict]:
        name = self.cell["name"]
        return [m for m in self.bench[group]
                if "workloads" not in m or name in m["workloads"]]

    @staticmethod
    def layer(metric: str):
        """The module layers/<metric>.py, loaded by the metric's name."""
        return load_module("layer_" + metric,
                           os.path.join(HERE, "layers", metric + ".py"))

    @staticmethod
    def reader(metric: str):
        """The metric's reader. A number the client's own arithmetic
        already gives (stats.CLIENT_METRICS) needs no file: it is read
        from the window's summary."""
        if os.path.isfile(os.path.join(HERE, "layers", metric + ".py")):
            return Plan.layer(metric).read
        if metric in stats.CLIENT_METRICS:
            return lambda ctx: ctx["client"].get(metric)
        raise SystemExit(f"no reader layers/{metric}.py")


class Run:
    def __init__(self, plan: Plan, rehearse: bool) -> None:
        self.plan = plan
        self.rehearse = rehearse
        self.serve = plan.config["serve"]
        self.model = self.serve["model"]
        self.vocab = plan.config["vocab_size"]
        cache_root = os.path.join(ROOT, ".bench_cache")
        # A fixed path per cell and mode, never one made from a pid or
        # the time; the compile cache is shared by every run here.
        self.scratch = os.path.join(
            cache_root, "run", plan.cell["name"])
        shutil.rmtree(self.scratch, ignore_errors=True)
        os.makedirs(os.path.join(self.scratch, "profile"))
        compile_cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                         or os.path.join(cache_root, "jax"))
        os.makedirs(compile_cache, exist_ok=True)
        self.record_path = os.path.join(self.scratch, "record.jsonl")
        env = dict(
            os.environ, PYTHONPATH=os.pathsep.join(
                p for p in (ROOT, os.environ.get("PYTHONPATH")) if p),
            JAX_COMPILATION_CACHE_DIR=compile_cache,
            DYNT_COMPILE_CACHE_DIR=compile_cache,
            DYNT_DISCOVERY_BACKEND="file",
            DYNT_DISCOVERY_PATH=os.path.join(self.scratch, "discovery"),
            DYNT_EVENT_JOURNAL_PATH=os.path.join(self.scratch, "events"),
            DYNT_SNAPSHOT_DIR=os.path.join(self.scratch, "snapshot"),
            DYNT_PROF_DIR=os.path.join(self.scratch, "profile"),
            # Scheduler defaults otherwise. The worker's own warm-up
            # stays at decode + the smallest prefill bucket: the shapes
            # this cell's traffic reaches are warmed below, by
            # enumeration. The stream-idle timeout is a deployment
            # setting that has to exceed a cold compile (PERF.md, PR 21).
            DYNT_PREWARM="0",
            DYNT_STREAM_IDLE_TIMEOUT_SECS="1100",
            # Requests cut at the window's end may still hold slots when
            # SIGTERM comes; their hand-off has nobody to go to.
            DYNT_DRAIN_DEADLINE_SECS="3")
        env.pop("BENCH_RUN", None)  # the driver's own; no business here
        if "decode_block" in self.serve:  # the program's default is 8
            env["DYNT_DECODE_BLOCK"] = str(self.serve["decode_block"])
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        self.env = env
        self.fleet = Fleet(ROOT, env, self.scratch, plan.worker_flags(),
                           self.record_path)
        self.engine: dict = {}
        self.warm_report: dict = {}

    # -- set-up ---------------------------------------------------------

    def start(self) -> None:
        build_native(ROOT, self.env)
        self.fleet.start()
        self.engine = self.fleet.wait_for_model(self.model, MODEL_WAIT_SECS)
        log(f"model listed; engine {self.engine}")

    def on_the_cells_chips(self) -> bool:
        want = "cpu" if self.rehearse else "tpu"
        devices = [d for d in self.engine.get("devices", "").split(",") if d]
        return (self.engine.get("platform") == want
                and len(devices) >= self.plan.cell["chips"])

    def compiles(self) -> dict:
        return compiles_of(self.fleet.scrape())

    async def warm_up(self, client: Client) -> None:
        """Every program the mix can reach, by enumeration: single-row
        prefill per bucket (one token out, so no decode at a small table
        width), batched prefill per (rows, bucket) as a group that lands
        behind a long blocker, and the fused decode block per table
        width, alone, so that both its host-fed and its device-fed
        variant run. Repeated (the second pass is cheap) until
        `dynamo_jit_compiles_total` has stopped growing."""
        warm = self.plan.mix["warm"]
        before = self.compiles()
        expected = warm.get("programs", {})
        for attempt in range(3):
            t0, start = time.monotonic(), self.compiles()
            tag = f"warm{attempt}"
            for n in warm["lone_prefill"]:
                await client.together(
                    [traffic.crafted(self.vocab, n, 1, f"{tag}/lone/{n}")],
                    f"{tag}-lone{n}")
            for g, group in enumerate(warm["groups"]):
                blocker = traffic.crafted(self.vocab, warm["blocker"], 1,
                                          f"{tag}/blocker/{g}")
                rows = [traffic.crafted(self.vocab, n, 1,
                                        f"{tag}/group/{g}/{i}")
                        for i, n in enumerate(group)]
                await client.together([blocker] + rows, f"{tag}-group{g}",
                                      stagger_s=0.04)
            for n in warm["decode"]:
                await client.together(
                    [traffic.crafted(self.vocab, n, warm["decode_tokens"],
                                     f"{tag}/decode/{n}")],
                    f"{tag}-decode{n}")
            self.fleet.check_alive()
            now = self.compiles()
            grew = grown(now, start)
            log(f"warm-up pass {attempt}: {time.monotonic() - t0:.1f}s, "
                f"new programs {grew or 'none'}")
            if not grew or (expected and all(
                    now.get(fn, 0) >= n for fn, n in expected.items())):
                break  # nothing new, or every listed program has run
        bad = [t for t in client.timelines if not t.ok]
        if bad:
            raise FleetError(f"warm-up request failed: {bad[0].error or bad[0]}")
        self.warm_report = {
            "programs_at_listing": before, "programs_after_warm_up": now,
            "passes": attempt + 1}

    # -- traffic --------------------------------------------------------

    async def traffic_window(self, client: Client, seed: int,
                             seconds: float, tag: str,
                             capture: bool = False,
                             trace_after: bool = False) -> dict:
        """Ramp, then `seconds` of the cell's traffic. Returns the
        window's bounds on this clock and what was scraped around it.
        `capture` traces inside the window (--trace 1); `trace_after`
        leaves the window alone and keeps the same traffic going behind
        it until a capture there is done (--trace 2)."""
        mix = self.plan.mix
        ramp = float(mix["ramp_seconds"])
        tail = TAIL_MAX_SECS if trace_after else 0.0
        total = ramp + seconds + tail
        if mix["loop"] == "closed":
            callers = (self.serve["max_batch"] if mix["callers"] == "max_batch"
                       else int(mix["callers"]))
            count = int(callers + total * 12)
        else:
            count = int(total * float(mix["rate_rps"]) * 3 + 50)
        reqs = traffic.requests(mix, self.vocab, seed, count)
        start = time.monotonic()
        t0 = start + ramp
        stop = t0 + seconds
        out: dict = {"t0": t0, "seconds": seconds}

        def until_the_tail_is_done():
            for req in reqs:
                if "tail_done" in out or time.monotonic() >= out.get(
                        "traffic_until", stop + tail):
                    return
                yield req

        async def around_window() -> None:
            await asyncio.sleep(max(0.0, t0 - time.monotonic()))
            out["before"] = await asyncio.to_thread(self.fleet.scrape)
            if capture:
                await asyncio.sleep(seconds / 3.0)
                out["capture_at"] = time.monotonic()
                out["capture"] = await asyncio.to_thread(
                    self.fleet.profile, CAPTURE_MS)
                out["capture_end"] = time.monotonic()
                out["mid"] = await asyncio.to_thread(self.fleet.scrape)
            await asyncio.sleep(max(0.0, stop - time.monotonic()))
            out["after"] = await asyncio.to_thread(self.fleet.scrape)
            if trace_after:
                try:
                    await asyncio.to_thread(self.trace_the_tail, out)
                finally:
                    out["tail_done"] = time.monotonic()

        side = asyncio.ensure_future(around_window())
        if mix["loop"] == "closed":
            await client.closed_loop(
                until_the_tail_is_done(), callers, stop + tail, tag,
                float(mix.get("start_spread_seconds", 0)) / callers)
        else:
            await client.open_loop(until_the_tail_is_done(), start,
                                   stop + tail, tag)
        await side
        self.fleet.check_alive()
        return out

    def trace_the_tail(self, out: dict) -> None:
        """--trace 2, behind the closed window: start and stop the
        worker's profiler once and throw that trace away, then capture
        CAPTURE_MS of the traffic that is still running. `capture_at` to
        `capture_end` is the traced span where the program says how long
        its profiler took to start (else the whole call, as --trace 1
        has it, with the traffic kept up throughout)."""
        t = time.monotonic()
        warm = self.fleet.profile(PROFILER_WARM_MS, xplane_only=True)
        shutil.rmtree(warm["trace_dir"], ignore_errors=True)
        span = CAPTURE_MS / 1e3
        out["capture_at"] = time.monotonic()
        out["profiler_warm_s"] = out["capture_at"] - t
        if "start_s" in warm:
            out["traffic_until"] = (out["capture_at"] + warm["start_s"]
                                    + span + TRACED_SPAN_MARGIN_SECS)
        out["capture"] = self.fleet.profile(CAPTURE_MS, xplane_only=True)
        out["capture_returned"] = time.monotonic()
        if "traffic_until" in out:
            out["capture_end"] = (out["capture_at"]
                                  + out["capture"]["start_s"] + span)
            if out["capture_end"] > out["traffic_until"]:
                log("the tail's traffic ended before the traced span did: "
                    "the capture's numbers are of a draining batch")
        else:
            out["capture_end"] = out["capture_returned"]
        out["end"] = self.fleet.scrape()

    # -- the check --------------------------------------------------------

    def served_tokens(self) -> dict:
        """{client tag: served token ids} from the frontend's recording
        (the engine's wire outputs: HTTP text cannot carry ids)."""
        tags: dict = {}
        tokens: dict = {}
        with open(self.record_path) as f:
            for line in f:
                ev = json.loads(line)
                rid = ev["request_id"]
                if ev["event"] == "request":
                    tags[rid] = ev["data"]["body"].get("user")
                elif ev["event"] == "output":
                    tokens.setdefault(rid, []).extend(ev["data"].get("t") or [])
        return {tags[rid]: toks for rid, toks in tokens.items()
                if tags.get(rid)}

    def sample(self, timelines: list, window: dict, seed: int,
               tag: str) -> list[dict]:
        """A seeded sample of the requests the window finished, with the
        longest in it; their prompts are generated again from the seed."""
        top = max((t.index for t in timelines), default=-1)
        reqs_by_tag = {
            f"{tag}-{r.index}": r.prompt for r in traffic.requests(
                self.plan.mix, self.vocab, seed, top + 1)}
        t0, t1 = window["t0"], window["t0"] + window["seconds"]
        done = [t for t in timelines
                if t.ok and t.end is not None and t0 <= t.end < t1
                and t.tag in reqs_by_tag]
        if not done:
            return []
        done.sort(key=lambda t: t.tag)
        longest = max(done, key=lambda t: (t.n_prompt + t.want_tokens, t.tag))
        rest = [t for t in done if t is not longest]
        random.Random(f"check/{seed}").shuffle(rest)
        picked = [longest] + rest[:self.plan.config["check"]["sample"] - 1]
        return [{"tag": t.tag, "prompt": list(reqs_by_tag[t.tag])}
                for t in picked]

    def reference(self, sets: list[dict]) -> dict:
        """The reference child. The servers have exited: the chip is free."""
        job_path = os.path.join(self.scratch, "reference_job.json")
        out_path = os.path.join(self.scratch, "reference_out.json")
        with open(job_path, "w") as f:
            json.dump(self.plan.reference_job(sets), f)
        with open(os.path.join(self.scratch, "reference.log"), "w") as err:
            child = subprocess.run(
                [sys.executable, os.path.join(HERE, "dtbench", "reference.py"),
                 job_path, out_path], cwd=ROOT, env=self.env, stdout=err,
                stderr=subprocess.STDOUT, timeout=900)
        if child.returncode:
            raise FleetError("reference child failed:\n" + tail(
                os.path.join(self.scratch, "reference.log")))
        out = load_json(out_path)
        log(f"reference {out['module']}: {out['seconds']:.1f}s on "
            f"{out['device']}")
        return out

    def start_reduction(self, capture: dict) -> subprocess.Popen:
        """Trace -> numbers, in a child held to the CPU."""
        files = [os.path.join(capture["trace_dir"], f)
                 for f in capture["files"] if f.endswith(".xplane.pb")]
        if not files:
            raise FleetError(f"the capture holds no .xplane.pb: {capture}")
        return subprocess.Popen(
            [sys.executable, os.path.join(HERE, "dtbench", "trace_reduce.py"),
             files[0], os.path.join(self.scratch, "trace.json")], cwd=ROOT,
            env=dict(self.env, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)

    def reduced_trace(self, child: subprocess.Popen) -> dict:
        try:
            _out, err = child.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            child.kill()
            child.communicate()
            raise
        if child.returncode:
            raise FleetError("trace reduction failed: " + err[-800:])
        return load_json(os.path.join(self.scratch, "trace.json"))

    def tail_report(self, window: dict, timelines: list) -> dict:
        """What tracing cost while it was on (--trace 2): the clients'
        tokens/s while the profiler ran (its thrown-away first start and
        stop, then the traced span), to set beside the window's
        `out_tok_s`, and how long the capture took to come back. Also the
        window's mean TTFT (the line has percentiles), which the program's
        stage means should add up to."""
        def out_tok_s(a: float, b: float) -> float:
            return stats.window_summary(timelines, a, b - a)[
                "metrics"]["out_tok_s"]

        a, b = window["capture_at"], window["capture_end"]
        t0, t1 = window["t0"], window["t0"] + window["seconds"]
        ttft = [(t.first - t.due) * 1e3 for t in timelines
                if t.ok and t.first is not None and t0 <= t.end < t1]
        return {"window_ttft_mean_ms": sum(ttft) / max(1, len(ttft)),
                "profiler_warm_s": window["profiler_warm_s"],
                "profiler_warm_out_tok_s": out_tok_s(
                    a - window["profiler_warm_s"], a),
                "traced_span_s": b - a, "traced_out_tok_s": out_tok_s(a, b),
                "traffic_covered_the_span": b <= window.get(
                    "traffic_until", b),
                "capture_s": window["capture_returned"] - a,
                "capture_start_s": window["capture"].get("start_s"),
                "capture_stop_s": window["capture"].get("stop_s"),
                "tail_s": window["tail_done"] - t1}


def verdict(plan: Plan, numbers: dict, counts_ok: bool) -> tuple[bool, list]:
    """`correct`: every request returned exactly its max_tokens, and every
    number compared lies within its limit. Prints each beside its limit."""
    rows = []
    ok = counts_ok
    for name, limit in plan.config["check"]["limits"].items():
        value = numbers.get(name)
        within = value is not None and limit is not None and value <= limit
        rows.append({"number": name, "value": value, "limit": limit,
                     "within": within})
        ok = ok and within
    return ok, rows


async def one_run(run: Run, args) -> dict:
    plan = run.plan
    async with Client(run.fleet.base, run.model) as warm_client:
        await run.warm_up(warm_client)
    async with Client(run.fleet.base, run.model) as client:
        window = await run.traffic_window(
            client, args.seed, args.seconds, f"s{args.seed}",
            capture=args.trace == 1, trace_after=args.trace == 2)
        timelines = client.timelines
    setup_s = window["t0"] - T_START
    summary = stats.window_summary(timelines, window["t0"], args.seconds)
    log(f"window: {summary['completed']} completed, {summary['failed']} "
        f"failed of {summary['attempted']}; generator ran late by "
        f"{summary['generator_lag_ms_max']:.1f} ms at most "
        f"(median {summary['generator_lag_ms_p50']:.2f} ms)")
    return {"window": window, "timelines": timelines, "summary": summary,
            "setup_s": setup_s}


def compiles_of(scrape: dict) -> dict:
    return {labels["fn"]: int(v) for labels, v in
            scrape.get("dynamo_jit_compiles_total", [])}


def grown(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def hbm(scrape: dict, kind: str) -> int:
    return int(max((v for labels, v in scrape.get(
        "dynamo_device_hbm_bytes", []) if labels["kind"] == kind),
        default=0))


def main() -> int:
    parser = argparse.ArgumentParser("benchmarks/run.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, default=0, choices=(0, 1, 2))
    parser.add_argument("--benchmark-json",
                        default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="debug the harness on the CPU; prints "
                             "platform cpu and never exits 0")
    parser.add_argument("--sweep", default=None)
    parser.add_argument("--check-seeds", default=None)
    parser.add_argument("--control", type=int, default=0)
    args = parser.parse_args()
    plan = Plan(args.benchmark_json, args.workload)
    if args.seconds is None:
        args.seconds = float(plan.bench["run_seconds"])

    # Before anything may read as a result: the program is here, and this
    # machine is meant to have the chip.
    if not os.path.isfile(os.path.join(ROOT, "dynamo_tpu", "worker",
                                       "__main__.py")):
        print("benchmarks/run.py: the program (dynamo_tpu/) is not in "
              "this checkout; nothing was run and there is no result",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    held = os.environ.get("JAX_PLATFORMS", "")
    if not args.rehearse_cpu and held and "tpu" not in held.split(","):
        print(f"benchmarks/run.py: JAX is held to {held!r} here; this cell "
              f"needs {plan.cell['chips']} TPU chip(s). Nothing was run "
              "and there is no result", file=sys.stderr)
        return EXIT_NO_CHIP

    run = Run(plan, args.rehearse_cpu)
    try:
        run.start()
        if not run.on_the_cells_chips():
            print(f"benchmarks/run.py: the engine runs on {run.engine}; "
                  f"this cell needs {plan.cell['chips']} TPU chip(s). "
                  "There is no result", file=sys.stderr)
            return EXIT_NO_CHIP
        if args.sweep or args.check_seeds:
            from dtbench import modes

            return modes.main(run, args, log)
        result = asyncio.run(one_run(run, args))
        window, summary = result["window"], result["summary"]
        # the peak of the whole run: the tail's last scrape where there is one
        peak = hbm(window.get("end", window["after"]), "peak")
        shutdown = run.fleet.stop()
        log(f"servers stopped: {shutdown}")
    except (FleetError, OSError, subprocess.TimeoutExpired) as exc:
        log(f"FAILED: {type(exc).__name__}: {str(exc)[-1500:]}")
        return EXIT_FAILED
    finally:
        run.fleet.kill()  # a no-op once both children have exited

    # -- the check, with the chip free ---------------------------------------
    tag = f"s{args.seed}"
    served = run.served_tokens()
    picked = run.sample(result["timelines"], window, args.seed, tag)
    samples = [{"prompt": s["prompt"], "served": served.get(s["tag"], [])}
               for s in picked]
    wanted = {t.tag: t.want_tokens for t in result["timelines"]}
    counts_ok = (summary["failed"] == 0 and summary["completed"] > 0
                 and bool(samples) and all(
                     len(s["served"]) == wanted[p["tag"]]
                     for s, p in zip(samples, picked)))
    numbers: dict = {}
    controls: dict = {}
    reducing = None
    try:
        if args.trace == 2:  # on the CPU, beside the reference on the chip
            reducing = run.start_reduction(window["capture"])
        if samples and all(s["served"] for s in samples):
            ref = run.reference([{"label": tag, "samples": samples,
                                  "control": bool(args.control)}])
            numbers = ref["sets"][0]["served"]
            controls = ref["sets"][0].get("controls", {})
            for name, row in controls.items():
                log(f"control {name}: {json.dumps(row)}")
        if args.trace == 1:
            reducing = run.start_reduction(window["capture"])
        trace = run.reduced_trace(reducing) if reducing else None
        if args.trace == 2 and not args.rehearse_cpu:
            # reduced: the trace itself is not kept (a rehearsal's is,
            # in its scratch directory, to be looked at)
            shutil.rmtree(window["capture"]["trace_dir"], ignore_errors=True)
    except (FleetError, OSError, subprocess.TimeoutExpired) as exc:
        log(f"FAILED after the window: {type(exc).__name__}: "
            f"{str(exc)[-1500:]}")
        if reducing is not None and reducing.poll() is None:
            reducing.kill()
            reducing.communicate()
        return EXIT_FAILED
    correct, rows = verdict(plan, numbers, counts_ok)

    metrics: dict = {}
    if args.trace != 1:
        values = dict(summary["metrics"], setup_s=result["setup_s"])
        for m in plan.metrics("end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    if args.trace:
        ctx = plan.context(
            peaks=plan.peaks.get(run.engine.get("device_kind")),
            window=window, timelines=result["timelines"], trace=trace,
            client=summary["metrics"])
        if ctx["peaks"] is None and not args.rehearse_cpu:
            log(f"FAILED: no peaks for device kind "
                f"{run.engine.get('device_kind')!r} in peaks.json")
            return EXIT_FAILED
        for m in plan.metrics("per_layer"):
            value = Plan.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": run.engine.get("platform"),
              "kind": run.engine.get("device_kind"),
              "count": len(run.engine.get("devices", "").split(",")),
              "memory_peak_bytes": peak}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    report = {
        "workload": plan.cell["name"], "seed": args.seed,
        "seconds": args.seconds, "trace": args.trace,
        "worker_flags": plan.worker_flags(),
        "setup_s": result["setup_s"], "summary": {
            k: v for k, v in summary.items() if k != "metrics"},
        "client_metrics": summary["metrics"],
        "warm_up": run.warm_report,
        "window_programs_new": grown(compiles_of(window["after"]),
                                     compiles_of(window["before"])),
        "hbm_in_use_bytes": hbm(window["after"], "in_use"),
        "check": {"sampled": [p["tag"] for p in picked],
                  "served_tokens": sum(len(s["served"]) for s in samples),
                  "counts_ok": counts_ok, "numbers": numbers,
                  "compared": rows, "controls": controls},
        "shutdown": shutdown,
    }
    if args.trace == 2:
        report["tail"] = run.tail_report(window, result["timelines"])
    print(json.dumps(report))
    line = {"correct": bool(correct), "attempted": summary["attempted"],
            "failed": summary["failed"], "metrics": metrics,
            "device": device}
    if trace is not None:
        line["breakdown"] = trace["breakdown"]
    # each number compared beside its limit: on standard output as it
    # was, as the last lines of standard error, and last in the line
    line["compared"] = {row["number"]: {"value": row["value"],
                                        "limit": row["limit"]}
                        for row in rows}
    for row in rows:
        said = (f"compared {row['number']}: {row['value']} against limit "
                f"{row['limit']} -> "
                f"{'within' if row['within'] else 'OUTSIDE'}")
        print(said)
        print(said, file=sys.stderr)
    print(json.dumps(line), flush=True)
    if args.rehearse_cpu:
        return EXIT_REHEARSAL
    return 0


if __name__ == "__main__":
    sys.exit(main())
