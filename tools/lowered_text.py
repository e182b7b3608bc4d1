"""The step programs `ModelRunner` builds for a benchmark cell, lowered
for a TPU v5e that is described, not attached, and written out as text:
what two trees are compared by when a change must leave the compiled
programs alone (PR 47; the recipe is in .claude/skills/verify/SKILL.md).

    python -m tools.lowered_text --cell lfm2-8b-a1b-pp2 --out DIR
    python -m tools.lowered_text --diff DIR_A DIR_B
    python -m tools.lowered_text --cell <name> --out DIR --compile REGEX

`--compile` also COMPILES the programs whose key the regex finds, for
the described chip, and prints what the compiler says of each: bytes of
arguments, temporaries and output, or the refusal in Mosaic's or XLA's
own words (a kernel over its VMEM, a program over the chip's memory). A
quarter of a minute to two minutes a program; no chip time.

Nothing runs and nothing is allocated. Inside `lowering(device)` every
call of a function that `engine/model_runner.py` has jitted lowers it
for `device` and hands back shapes; so a `ModelRunner` is built by its own `__init__`
(params, cache and state are shapes) and `prewarm(launches=True)` walks
the launches the worker makes before it serves, through the host entry
points the scheduler calls, with the tree's own rows and arguments. The
script knows no calling convention, so one copy of it lowers any tree:
run it with the tree as the working directory. A program is named by the
key it is launched under (`program_key`); its text has locations and the
`jax.result_info` / `jax.arg_info` attributes stripped, which spell a
leaf's path in the Python pytree and nothing of the program. A Mosaic
kernel travels in its custom call as serialised MLIR that names the
file and line of every operation (the callers' too, so an edit above a
call site changes the bytes): each body is replaced by the digest of
its assembly printed without locations.
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import difflib
import hashlib
import json
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402

_STRIP = re.compile(r' loc\([^)]*\)|,? ?jax\.(result|arg)_info = "[^"]*"')
_KERNEL = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def normalise(text: str) -> str:
    """A lowered module's text without what names Python and not the
    program: locations (a Mosaic kernel's own among them), and the
    pytree path of each argument and result."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    tpu.register_dialect(context)
    context.allow_unregistered_dialects = True

    def kernel(match) -> str:
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(2)))
            asm = module.operation.get_asm(enable_debug_info=False)
        digest = hashlib.sha256(asm.encode()).hexdigest()
        return f"{match.group(1)}mosaic:{digest}{match.group(3)}"

    text = _KERNEL.sub(kernel, _STRIP.sub("", text))
    return re.sub(r"^#loc.*\n", "", text, flags=re.M).replace(" {}", "")


class Abstract(jax.ShapeDtypeStruct):
    """A shape standing where host code expects a device array: it can
    be indexed, read back as zeros and waited for."""

    def __getitem__(self, index):
        zeros = np.zeros(self.shape, bool)[index]
        return Abstract(zeros.shape, self.dtype, sharding=self.sharding)

    def __array__(self, dtype=None, copy=None):
        return np.zeros(self.shape, dtype or self.dtype)

    def block_until_ready(self):
        return self

    def is_ready(self) -> bool:
        return True


class Lowered(dict):
    """{key: normalised text}, and under `calls` the shapes each program
    was lowered for, as (args, kwargs)."""

    def __init__(self) -> None:
        super().__init__()
        self.calls: dict[str, tuple] = {}
        self.memory: dict[str, str] = {}  # what `--compile` was told
        self.compiled: dict[str, str] = {}  # and the optimised HLO


@contextlib.contextmanager
def lowering(device, key_of=None, compile_keys: str = ""):
    """Inside: a function jitted by `engine/model_runner.py` (the module
    sees a `jax` whose `jit` is ours) is lowered for `device` when it is
    called, and not run. Yields {key: normalised text}; `key_of(name)`
    names a program (default: the function's name, primed where it
    repeats)."""
    import dynamo_tpu.engine.model_runner as mr

    here = jax.sharding.SingleDeviceSharding(device)
    texts = Lowered()

    def shape_of(x, sharding=here):
        if isinstance(x, Abstract):
            return x
        x = x if hasattr(x, "dtype") else np.asarray(x)
        return Abstract(x.shape, x.dtype, sharding=sharding)

    class Lowering:
        def __init__(self, fn, **options):
            self.fn, self.options = fn, options
            self.jitted = jax.jit(fn, **options)

        def __call__(self, *args, **kwargs):
            args, kwargs = jax.tree.map(shape_of, (args, kwargs))
            lowered = self.jitted.lower(*args, **kwargs)
            name = getattr(self.fn, "__name__", "fn")
            key = key_of(name) if key_of else name
            text = normalise(lowered.as_text())
            while texts.get(key, text) != text:
                key += "'"
            if compile_keys and key not in texts and re.search(
                    compile_keys, key):
                try:
                    compiled = lowered.compile()
                    texts.memory[key] = str(compiled.memory_analysis())
                    texts.compiled[key] = compiled.as_text()
                except Exception as exc:  # noqa: BLE001 — the finding
                    texts.memory[key] = f"REFUSED: {str(exc)[-2000:]}"
            texts[key] = text
            texts.calls[key] = (args, kwargs)
            out = jax.eval_shape(self.fn, *args, **kwargs)
            spec = self.options.get("out_shardings")
            if spec is None:
                return jax.tree.map(shape_of, out)
            return jax.tree.map(
                lambda sharding, sub: jax.tree.map(
                    lambda x: shape_of(x, sharding), sub),
                spec, out, is_leaf=lambda s: isinstance(
                    s, jax.sharding.Sharding))

    class Jax:
        """`jax` as the runner's module sees it meanwhile."""

        jit = Lowering

        def __getattr__(self, name):
            return getattr(jax, name)

    mr.jax = Jax()
    try:
        yield texts
    finally:
        mr.jax = jax


def on_the_chip() -> None:
    """The kernel options resolve as they do on a TPU (`ops.kernel_path`:
    "pallas"), in every module that has taken the function's name."""
    import dynamo_tpu.engine.model_runner  # noqa: F401 — loads the ops
    import dynamo_tpu.models.hybrid  # noqa: F401

    for name, module in list(sys.modules.items()):
        if name.startswith("dynamo_tpu") and hasattr(module, "kernel_path"):
            module.kernel_path = lambda option: "pallas"


def described_chip():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]


def cell_runner(config_file: Path, device, buckets=None):
    """The `ModelRunner` the worker builds for a benchmark configuration
    (`serve` in benchmarks/configs/*.json), over one described chip.
    Call inside `lowering`. `buckets`: prefill buckets for a
    configuration that names none (its default seven make a grid of 50
    programs of 32 layers: name the narrowest, the widest and the one
    its traffic fills)."""
    from dynamo_tpu.engine import ModelRunner, RunnerConfig
    from dynamo_tpu.models.config import cut_config, get_config
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    serve = json.loads(config_file.read_text())["serve"]
    args = serve.get("worker_args", [])
    flags = dict(zip(args[::2], args[1::2]))
    if buckets:
        flags.setdefault("--prefill-buckets", buckets)
    layers = flags.get("--serve-layers")
    rows = flags.get("--vocab-rows")
    cfg = cut_config(get_config(serve["model"]),
                     int(layers) if layers else None,
                     flags.get("--experts-held"),
                     int(rows) if rows else None)
    extra = {}
    if "--prefill-buckets" in flags:
        extra["prefill_buckets"] = tuple(sorted(
            int(b) for b in flags["--prefill-buckets"].split(",")))
    rc = RunnerConfig(
        page_size=serve["page_size"], num_pages=serve["num_pages"],
        max_batch=serve["max_batch"],
        max_pages_per_seq=serve["max_pages_per_seq"],
        kv_dtype=serve["kv_dtype"], weight_dtype=serve["weight_dtype"],
        window_pages=int(flags.get("--window-pages", 0)), **extra)
    runner = ModelRunner(cfg, rc, make_mesh(MeshConfig(), [device]))
    return runner, int(serve.get("decode_block", 8))


def lower_cell(name: str, out: Path, only: str = "", buckets=None,
               compile_keys: str = "") -> dict:
    """Every program `prewarm(launches=True)` walks for the cell, under
    `out/<key>.mlir`; returns {key: sha256 of the text}. The programs
    `compile_keys` finds are compiled too, and what the compiler said
    goes to `out/memory.json` and is printed, the optimised HLO to
    `out/<key>.hlo`."""
    import dynamo_tpu.engine.model_runner as mr

    on_the_chip()
    device = described_chip()

    def key_of(name: str) -> str:
        build = getattr(mr._COMPILE_SCOPE, "build", None)
        return f"{build.key}.jit_{name}" if build else f"init.jit_{name}"

    with lowering(device, key_of, compile_keys) as texts:
        runner, block = cell_runner(
            Path("benchmarks/configs") / f"{name}.json", device, buckets)
        runner.prewarm(spec_widths=[], launches=True, block=block)
    out.mkdir(parents=True, exist_ok=True)
    index = {}
    for key, text in texts.items():
        if key.startswith("init.") or not re.search(only, key):
            continue
        (out / f"{key}.mlir").write_text(text)
        index[key] = hashlib.sha256(text.encode()).hexdigest()
    (out / "index.json").write_text(json.dumps(index, indent=1))
    if compile_keys:
        (out / "memory.json").write_text(json.dumps(texts.memory, indent=1))
        for key, said in texts.memory.items():
            print(f"compiled {key}: {said}")
        for key, hlo in texts.compiled.items():
            (out / f"{key}.hlo").write_text(hlo)
    return index


def diff(a: Path, b: Path) -> int:
    """Program by program: identical, or the head of the diff. Returns
    the number of programs that differ or are on one side only."""
    left = json.loads((a / "index.json").read_text())
    right = json.loads((b / "index.json").read_text())
    differ = 0
    for key in sorted(set(left) | set(right)):
        if left.get(key) == right.get(key):
            print(f"{key}: identical")
            continue
        differ += 1
        if key not in left or key not in right:
            print(f"{key}: only in {a if key in left else b}")
            continue
        lines = list(difflib.unified_diff(
            (a / f"{key}.mlir").read_text().splitlines(),
            (b / f"{key}.mlir").read_text().splitlines(),
            str(a), str(b), lineterm="", n=0))
        print(f"{key}: {len(lines)} lines of diff")
        print("\n".join(line[:240] for line in lines[:40]))
    return differ


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cell", help="a name under benchmarks/configs/")
    parser.add_argument("--out", type=Path)
    parser.add_argument("--only", default="",
                        help="keep program keys this regex finds")
    parser.add_argument("--buckets", help="--prefill-buckets for a "
                        "configuration whose worker_args name none")
    parser.add_argument("--compile", default="", metavar="REGEX",
                        help="compile the programs whose key this finds "
                        "for the described chip; print the memory each "
                        "takes, or the compiler's refusal")
    parser.add_argument("--diff", nargs=2, type=Path, metavar="DIR")
    args = parser.parse_args()
    if args.diff:
        return 1 if diff(*args.diff) else 0
    for key, digest in lower_cell(args.cell, args.out, args.only,
                                   args.buckets, args.compile).items():
        print(digest[:16], key)
    return 0


if __name__ == "__main__":
    sys.exit(main())
