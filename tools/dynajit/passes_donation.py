"""DJ3xx — buffer-donation discipline at the jit boundary.

Donation (`donate_argnums`) is how the engine steps a multi-GiB paged KV
pool without doubling HBM: the input buffer is retired as the output
materializes. It is also the sharpest tool in the box — a donated array
read after the call is a use-after-free XLA only sometimes catches
(`.delete()`d buffer errors on TPU, silent garbage in interpret mode),
and a donated self-attribute that is not rebound in the same statement
leaves every OTHER method holding a dead pointer.

Three rules:

  * DJ301 use-after-donate — an argument passed at a donated position is
    read again after the call without being rebound by it.
  * DJ302 donated-attr-not-rebound — a donated `self.X` must be rebound
    by the call statement's own targets (`self.X, ... = fn(...)`); any
    later method reading the stale attribute is undefined behavior.
  * DJ303 kv-param-donation-undeclared — a jit whose wrapped callable
    takes a KV-pool-shaped parameter (`kv`, `kv_cache`, `kv_pool`,
    `cache`) must carry an explicit `donate_argnums` — donating it, or
    `donate_argnums=()` to declare the read-only intent (the
    ops/block_copy.py gather convention). Donation on the largest
    buffers in the program must never be implicit.

Donating callables are resolved through the idioms this codebase uses:
direct `jax.jit(..., donate_argnums=...)` calls (immediate or bound to
a local), and locals assigned from `self._build_*` builder methods whose
returned jit donates — including the `fn(*args)` dispatch form when
`args` is a local list literal.
"""

from __future__ import annotations

import ast
from typing import Iterable, Optional

from tools.dynalint.core import Finding, ProjectRule, Rule, SourceFile

from .jit_surface import _jit_callee, _jit_kwargs, jit_sites

KV_PARAM_NAMES = {"kv", "kv_cache", "kv_pool", "cache"}


def _donated_nums(call: ast.Call) -> tuple[int, ...]:
    return _jit_kwargs(call)["donate_argnums"]


def _file_builders(src: SourceFile) -> dict[str, tuple[int, ...]]:
    """Method/function name -> donated argnums of the jit it returns."""
    out: dict[str, tuple[int, ...]] = {}
    for fn in ast.walk(src.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Return)
                    and isinstance(node.value, ast.Call)):
                continue
            call = _jit_callee(node.value)
            if call is not None and _donated_nums(call):
                out[fn.name] = _donated_nums(call)
    return out


def _expr_key(node: ast.expr) -> Optional[str]:
    """Stable key for a donated argument expression: a bare name or a
    self-attribute. Anything else (calls, subscripts) is a fresh value
    the caller cannot re-read."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute) and isinstance(node.value,
                                                      ast.Name) \
            and node.value.id == "self":
        return f"self.{node.attr}"
    return None


def _targets_rebinding(stmt: ast.stmt) -> set[str]:
    """Keys rebound by an assignment statement's targets (tuple targets
    flattened)."""
    out: set[str] = set()
    if not isinstance(stmt, ast.Assign):
        return out
    stack: list[ast.expr] = list(stmt.targets)
    while stack:
        tgt = stack.pop()
        if isinstance(tgt, (ast.Tuple, ast.List)):
            stack.extend(tgt.elts)
            continue
        key = _expr_key(tgt)
        if key is not None:
            out.add(key)
    return out


class _DonationAnalysis:
    """Per-function resolution of donating calls and their donated
    argument expressions."""

    def __init__(self, src: SourceFile, fn,
                 builders: dict[str, tuple[int, ...]]) -> None:
        self.src = src
        self.fn = fn
        self.builders = builders
        # local name -> donated argnums (jit assignments + builder calls)
        self.donating_locals: dict[str, tuple[int, ...]] = {}
        # local list literals (for the `fn(*args)` dispatch form)
        self.list_locals: dict[str, ast.List] = {}
        self._collect()

    def _collect(self) -> None:
        for node in ast.walk(self.fn):
            if not isinstance(node, ast.Assign):
                continue
            val = node.value
            nums: tuple[int, ...] = ()
            jit = _jit_callee(val) if isinstance(val, ast.Call) else None
            if jit is not None:
                nums = _donated_nums(jit)
            elif isinstance(val, ast.Call):
                f = val.func
                tail = f.attr if isinstance(f, ast.Attribute) else (
                    f.id if isinstance(f, ast.Name) else "")
                nums = self.builders.get(tail, ())
            if isinstance(val, ast.List):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.list_locals[tgt.id] = val
            if nums:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.donating_locals[tgt.id] = nums

    def donating_calls(self) -> list[tuple[ast.Call, tuple[int, ...]]]:
        out = []
        for node in ast.walk(self.fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            jit = _jit_callee(f) if isinstance(f, ast.Call) else None
            if jit is not None and _donated_nums(jit):
                out.append((node, _donated_nums(jit)))
            elif isinstance(f, ast.Name) \
                    and f.id in self.donating_locals:
                out.append((node, self.donating_locals[f.id]))
        return out

    def positional_args(self, call: ast.Call) -> list[ast.expr]:
        """Positional arguments, expanding `*args` when args is a local
        list literal (the ModelRunner dispatch idiom)."""
        out: list[ast.expr] = []
        for arg in call.args:
            if isinstance(arg, ast.Starred):
                if isinstance(arg.value, ast.Name) \
                        and arg.value.id in self.list_locals:
                    out.extend(self.list_locals[arg.value.id].elts)
                else:
                    return out  # opaque splat: stop resolving positions
            else:
                out.append(arg)
        return out


def _statement_of(fn, node: ast.AST) -> Optional[ast.stmt]:
    """Innermost statement containing `node` plus the flat statement
    sequence (pre-order) of the function for after-the-call scanning."""
    for stmt in ast.walk(fn):
        if isinstance(stmt, ast.stmt):
            if any(sub is node for sub in ast.walk(stmt)):
                found = stmt
                for child in ast.iter_child_nodes(stmt):
                    if isinstance(child, ast.stmt) and any(
                            sub is node for sub in ast.walk(child)):
                        return _statement_of_inner(child, node)
                return found
    return None


def _statement_of_inner(stmt: ast.stmt, node: ast.AST) -> ast.stmt:
    for child in ast.iter_child_nodes(stmt):
        if isinstance(child, ast.stmt) and any(
                sub is node for sub in ast.walk(child)):
            return _statement_of_inner(child, node)
    return stmt


def _reads_after(fn, call_stmt: ast.stmt, key: str) -> Optional[ast.AST]:
    """First read of `key` in statements AFTER call_stmt (document
    order), stopping at the first rebind."""
    stmts = [s for s in ast.walk(fn) if isinstance(s, ast.stmt)]
    stmts.sort(key=lambda s: (s.lineno, s.col_offset))
    started = False
    for stmt in stmts:
        if stmt is call_stmt:
            started = True
            continue
        if not started or stmt.lineno <= call_stmt.lineno:
            continue
        if key in _targets_rebinding(stmt):
            # rebound before any read: the stale buffer is unreachable
            value_read = _read_in(stmt.value, key) \
                if isinstance(stmt, ast.Assign) else None
            return value_read
        read = _read_in(stmt, key)
        if read is not None:
            return read
    return None


def _read_in(node: Optional[ast.AST], key: str) -> Optional[ast.AST]:
    if node is None:
        return None
    for sub in ast.walk(node):
        if _expr_key(sub) == key and isinstance(
                getattr(sub, "ctx", ast.Load()), ast.Load):
            return sub
    return None


class UseAfterDonate(ProjectRule):
    id = "DJ301"
    name = "use-after-donate"
    description = (
        "an argument passed at a donated position of a jit-compiled "
        "call is read again after the call without being rebound: the "
        "buffer was retired by XLA — on device this is a deleted-buffer "
        "error at best and silent garbage at worst")

    def check_project(self, files: list[SourceFile]) -> Iterable[Finding]:
        for src in files:
            builders = _file_builders(src)
            for fn in ast.walk(src.tree):
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                yield from self._check_fn(src, fn, builders)

    def _check_fn(self, src: SourceFile, fn,
                  builders: dict) -> Iterable[Finding]:
        analysis = _DonationAnalysis(src, fn, builders)
        for call, nums in analysis.donating_calls():
            args = analysis.positional_args(call)
            stmt = _statement_of(fn, call)
            if stmt is None:
                continue
            rebound = _targets_rebinding(stmt)
            for num in nums:
                if num >= len(args):
                    continue
                key = _expr_key(args[num])
                if key is None or key in rebound:
                    continue
                read = _reads_after(fn, stmt, key)
                if read is not None:
                    yield Finding(
                        self.id, self.name, src.rel,
                        getattr(read, "lineno", call.lineno),
                        getattr(read, "col_offset", 0),
                        f"{key!r} was donated at position {num} of the "
                        f"jit call on line {call.lineno} and is read "
                        "again here without being rebound — the buffer "
                        "no longer exists")


class DonatedAttrNotRebound(ProjectRule):
    id = "DJ302"
    name = "donated-attr-not-rebound"
    description = (
        "a donated `self.<attr>` must be rebound by the donating call's "
        "own statement (`self.cache, ... = fn(...)`): the attribute "
        "outlives this function, and any other method reading it after "
        "the call holds a retired buffer")

    def check_project(self, files: list[SourceFile]) -> Iterable[Finding]:
        for src in files:
            builders = _file_builders(src)
            for fn in ast.walk(src.tree):
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                analysis = _DonationAnalysis(src, fn, builders)
                for call, nums in analysis.donating_calls():
                    args = analysis.positional_args(call)
                    stmt = _statement_of(fn, call)
                    if stmt is None:
                        continue
                    rebound = _targets_rebinding(stmt)
                    for num in nums:
                        if num >= len(args):
                            continue
                        key = _expr_key(args[num])
                        if key is None or not key.startswith("self.") \
                                or key in rebound:
                            continue
                        yield Finding(
                            self.id, self.name, src.rel, call.lineno,
                            call.col_offset,
                            f"{key} is donated here but the statement "
                            "does not rebind it — every later reader "
                            "of the attribute holds a retired buffer; "
                            "rebind it in the same statement")


class KvParamDonationUndeclared(Rule):
    id = "DJ303"
    name = "kv-param-donation-undeclared"
    description = (
        "a jit-compiled callable takes a KV-pool-shaped parameter "
        "(kv/kv_cache/kv_pool/cache) with NO donate_argnums "
        "declaration: donation on the largest buffers in the program "
        "must be explicit — donate it, or declare `donate_argnums=()` "
        "to pin the read-only intent (the ops/block_copy.py gather "
        "convention)")

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        for site in jit_sites([src]):
            if site.donate_declared:
                continue
            hits = [p for p in site.target_params if p in KV_PARAM_NAMES]
            if not hits:
                continue
            node = site.node
            yield Finding(
                self.id, self.name, src.rel,
                getattr(node, "lineno", site.line),
                getattr(node, "col_offset", 0),
                f"jit({site.target}) takes KV-pool parameter(s) "
                f"{', '.join(hits)} with no donate_argnums declaration; "
                "donate them or declare donate_argnums=() explicitly")
