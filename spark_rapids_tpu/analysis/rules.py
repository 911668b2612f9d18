"""tpulint rule passes: one class per engine invariant.

Each rule is a pure function of one file's AST (`FileContext` in, raw
`Finding`s out); suppressions and the baseline are applied by the
engine (core.py), so a rule never needs to know about either.  The
rules encode invariants established by PRs 1-10 — the PR that learned
each lesson is named in the rule docstring and in docs/dev-guide.md.

Static analysis is approximate by design: a rule fires on the lexical
shape of a violation.  Where the shape is legitimately reachable by
safe code (a host-side `np.asarray`, a daemon server parked on its
socket), the remedy is a per-line suppression WITH a reason — which is
itself enforced (`bad-suppress`).
"""
from __future__ import annotations

import ast
import os
import re
from typing import Optional

from spark_rapids_tpu.analysis.core import (FileContext, Finding,
                                            _iter_py_files)


def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _receiver(call_func: ast.AST) -> Optional[str]:
    """Dotted receiver of a method call ('self._queue' for
    self._queue.get), else None (computed receivers)."""
    if isinstance(call_func, ast.Attribute):
        return dotted(call_func.value)
    return None


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def _is_literalish(node: ast.AST) -> bool:
    """Constant-ish expressions that cannot hold a device array."""
    return isinstance(node, (ast.Constant, ast.List, ast.Tuple,
                             ast.Dict, ast.Set, ast.ListComp,
                             ast.GeneratorExp, ast.JoinedStr))


class Rule:
    rule_id = "?"
    doc = ""

    def check(self, ctx: FileContext) -> list[Finding]:
        raise NotImplementedError

    def finding(self, ctx: FileContext, node: ast.AST,
                message: str) -> Finding:
        return Finding(self.rule_id, ctx.relpath,
                       getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message)


# ---------------------------------------------------------------------------
class HostSyncRule(Rule):
    """Rule 1 (PR 2, the host-sync diet): a device->host blocking
    materialization on a hot path (exec/, ops/, shuffle/, exprs/,
    plan/) must be accounted via `utils.checks.note_host_sync` — the
    enclosing function must call it (or the site carries a reasoned
    suppression when the value is host-resident).  Detected shapes:
    `np.asarray(...)`, `.item()`, `jax.device_get(...)`, `.to_py()`,
    `.block_until_ready()` — and therefore also the `int()/float()/
    bool()` wrappers around them."""

    rule_id = "host-sync"
    doc = ("device->host materializations on hot paths must route "
           "through utils.checks.note_host_sync(site=...)")

    _NP_NAMES = {"np", "numpy", "_np", "onp"}

    def check(self, ctx: FileContext) -> list[Finding]:
        if not ctx.is_hot_path:
            return []
        out: list[Finding] = []
        self._walk(ctx, ctx.tree, noted=False, out=out)
        return out

    @staticmethod
    def _has_note(fn: ast.AST) -> bool:
        for n in ast.walk(fn):
            if isinstance(n, ast.Call):
                d = dotted(n.func)
                if d is not None and d.split(".")[-1] == "note_host_sync":
                    return True
        return False

    def _walk(self, ctx, node, noted, out):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            noted = noted or self._has_note(node)
        elif isinstance(node, ast.Call) and not noted:
            m = self._sync_kind(node)
            if m is not None:
                out.append(self.finding(
                    ctx, node,
                    f"{m} is a blocking device->host readback; "
                    "call utils.checks.note_host_sync(site=...) in "
                    "this function (or suppress with a reason if "
                    "the value is host-resident)"))
        for child in ast.iter_child_nodes(node):
            self._walk(ctx, child, noted, out)

    def _sync_kind(self, call: ast.Call) -> Optional[str]:
        f = call.func
        d = dotted(f)
        if d == "jax.device_get" or d == "device_get":
            return "jax.device_get()"
        if isinstance(f, ast.Attribute):
            if (f.attr == "asarray"
                    and isinstance(f.value, ast.Name)
                    and f.value.id in self._NP_NAMES):
                if call.args and _is_literalish(call.args[0]):
                    return None
                return f"{f.value.id}.asarray()"
            if f.attr == "item" and not call.args:
                return ".item()"
            if f.attr == "to_py":
                return ".to_py()"
            if f.attr == "block_until_ready":
                return ".block_until_ready()"
        return None


# ---------------------------------------------------------------------------
#: dotted-name suffixes that are sanctioned cancellable waits — the
#: watchdog's bounded-poll helpers (PR 4) and the seeded injectors,
#: which sleep cancellably by construction
_CANCELLABLE = ("cancellable_sleep", "cancellable_wait",
                "check_cancelled", "maybe_hang", "maybe_slow")


def _is_cancellable_helper(call: ast.Call) -> bool:
    d = dotted(call.func)
    return d is not None and d.split(".")[-1] in _CANCELLABLE


def _queue_style_get(call: ast.Call) -> bool:
    """`.get()` shapes that BLOCK: zero-arg, or block=True/positional
    True without a timeout.  `d.get(key[, default])` is dict access."""
    if _kw(call, "timeout") is not None:
        return False
    if not call.args and not call.keywords:
        return True
    blk = _kw(call, "block")
    if blk is not None:
        return not (isinstance(blk, ast.Constant) and blk.value is False)
    if (len(call.args) == 1 and isinstance(call.args[0], ast.Constant)
            and call.args[0].value is True):
        return True
    return False


class BlockingWhileHoldingRule(Rule):
    """Rule 2 (PR 2/6): code lexically inside a `with ...held():`
    region (the task holds the TPU semaphore) must not call anything
    that can block — queue get/put, socket recv, Event.wait, sleep,
    lock acquire, thread join — without first entering
    `TpuSemaphore.yielded()` or using a cancellable watchdog wait.  A
    task parked while holding the semaphore starves every other
    query's device access (the fair-share rewrite made the semaphore
    the engine's admission point, which makes holding-while-blocked
    strictly worse than pre-PR-6)."""

    rule_id = "sem-blocking"
    doc = ("blocking calls inside a semaphore-held region must use "
           "TpuSemaphore.yielded() or a cancellable watchdog wait")

    _BLOCK_ATTRS = {"get", "put", "recv", "wait", "acquire", "join",
                    "sleep"}

    def check(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        self._walk(ctx, ctx.tree, held=False, out=out)
        return out

    def _walk(self, ctx, node, held, out):
        if isinstance(node, ast.With):
            attrs = {c.func.attr for c in
                     (i.context_expr for i in node.items)
                     if isinstance(c, ast.Call)
                     and isinstance(c.func, ast.Attribute)}
            if "yielded" in attrs:
                held = False     # the hold is released for this body
            elif "held" in attrs:
                held = True
            for b in node.body:
                self._walk(ctx, b, held, out)
            return
        if (isinstance(node, ast.Call) and held
                and not _is_cancellable_helper(node)):
            m = self._blocking_kind(node)
            if m is not None:
                out.append(self.finding(
                    ctx, node,
                    f"{m} can block while the TPU semaphore is "
                    "held; wrap the wait in TpuSemaphore.yielded() "
                    "or use a cancellable watchdog wait"))
        for child in ast.iter_child_nodes(node):
            self._walk(ctx, child, held, out)

    def _blocking_kind(self, call: ast.Call) -> Optional[str]:
        f = call.func
        if isinstance(f, ast.Name) and f.id == "sleep":
            return "sleep()"
        if not isinstance(f, ast.Attribute):
            return None
        if f.attr not in self._BLOCK_ATTRS:
            return None
        recv = _receiver(f) or ""
        last = recv.split(".")[-1] if recv else ""
        if f.attr == "get":
            if last[:1].isupper():          # Singleton.get()
                return None
            if not _queue_style_get(call):  # dict.get(key)
                return None
            return ".get()"
        if f.attr == "join":
            if call.args:                   # sep.join(...) / path.join
                return None
            return ".join()"
        if f.attr == "sleep" and last not in ("time", ""):
            return None
        return f".{f.attr}()"


# ---------------------------------------------------------------------------
class UnboundedWaitRule(Rule):
    """Rule 3 (PR 4): every indefinite wait in the engine must be a
    bounded poll + CancelToken check — a `wait()`/`get()`/`join()`/
    `acquire()` with no timeout, or a socket `recv` in a function with
    no cancellation/timeout discipline, can outlive its query and
    either hang the process or leak the thread past watchdog
    cancellation."""

    rule_id = "unbounded-wait"
    doc = ("wait()/get()/join()/acquire() need a timeout (bounded "
           "poll + CancelToken check); recv needs settimeout or "
           "check_cancelled in scope")

    def check(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        self._walk(ctx, ctx.tree, guarded=False, out=out)
        return out

    @staticmethod
    def _fn_guards_recv(fn: ast.AST) -> bool:
        for n in ast.walk(fn):
            if isinstance(n, ast.Call):
                d = dotted(n.func) or ""
                leaf = d.split(".")[-1]
                if leaf in ("check_cancelled", "settimeout"):
                    return True
        return False

    def _walk(self, ctx, node, guarded, out):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            guarded = guarded or self._fn_guards_recv(node)
        elif isinstance(node, ast.Call):
            m = self._unbounded_kind(node, guarded)
            if m is not None:
                out.append(self.finding(
                    ctx, node,
                    f"{m} — every indefinite wait must be a "
                    "bounded poll + CancelToken check (see "
                    "utils.watchdog.cancellable_wait/"
                    "cancellable_sleep)"))
        for child in ast.iter_child_nodes(node):
            self._walk(ctx, child, guarded, out)

    def _unbounded_kind(self, call: ast.Call,
                        guarded: bool) -> Optional[str]:
        f = call.func
        if not isinstance(f, ast.Attribute):
            return None
        recv = _receiver(f) or ""
        last = recv.split(".")[-1] if recv else ""
        a = f.attr
        no_args = not call.args and not call.keywords
        if a == "wait":
            to = call.args[0] if call.args else _kw(call, "timeout")
            if to is None and no_args:
                return ".wait() without a timeout"
            if (isinstance(to, ast.Constant) and to.value is None):
                return ".wait(None) is indefinite"
            return None
        if a == "join" and no_args:
            return ".join() without a timeout"
        if a == "get":
            if last[:1].isupper():
                return None
            if _queue_style_get(call):
                return ".get() without a timeout"
            return None
        if a == "acquire":
            if _kw(call, "timeout") is not None or call.args:
                return None
            blk = _kw(call, "blocking")
            if (isinstance(blk, ast.Constant) and blk.value is False):
                return None
            if no_args or blk is not None:
                return ".acquire() without a timeout"
            return None
        if a == "recv" and not guarded:
            return (".recv() in a function with neither settimeout "
                    "nor check_cancelled")
        return None


# ---------------------------------------------------------------------------
_CONF_KEY_RE = re.compile(r"^spark\.rapids\.[A-Za-z0-9_.]+$")


class ConfDisciplineRule(Rule):
    """Rule 4 (PR 2's captured-conf bug class, closed at the resolver
    in PR 6): (a) every `spark.rapids.*` string literal must be a key
    registered in config.py — an unregistered literal is a typo'd or
    undocumented conf that silently resolves to its hardcoded default;
    (b) plan/ node constructors and class bodies must not resolve
    confs (`get_active_conf`) — conf values captured at plan build
    leak one session's settings into another's execution (the q15
    f32/f64 mismatch); resolve at execute_partitions/kernel-build
    time instead."""

    rule_id = "conf-discipline"
    doc = ("spark.rapids.* literals must be registered in config.py; "
           "plan/ constructors must not resolve confs")

    def check(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        if not ctx.relpath.endswith("spark_rapids_tpu/config.py"):
            self._check_literals(ctx, ctx.tree, out)
        if ctx.in_package("plan"):
            self._check_plan_init(ctx, out)
        return out

    def _check_literals(self, ctx, node, out, in_fstring=False):
        for child in ast.iter_child_nodes(node):
            if (isinstance(child, ast.Constant)
                    and isinstance(child.value, str)
                    and not in_fstring
                    and _CONF_KEY_RE.match(child.value)
                    and child.value not in ctx.conf_keys):
                out.append(self.finding(
                    ctx, child,
                    f"conf key '{child.value}' is not registered in "
                    "config.py — register it with conf(...) so it is "
                    "typed, documented, and covered by the configs.md "
                    "drift gate"))
            self._check_literals(
                ctx, child, out,
                in_fstring or isinstance(child, ast.JoinedStr))

    def _check_plan_init(self, ctx, out):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for stmt in node.body:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    if stmt.name not in ("__init__", "__post_init__"):
                        continue
                for call in ast.walk(stmt):
                    if (isinstance(call, ast.Call)
                            and (dotted(call.func) or "")
                            .split(".")[-1] == "get_active_conf"):
                        out.append(self.finding(
                            ctx, call,
                            "conf lookup in a plan/ node constructor "
                            "or class body: confs must resolve at "
                            "execution time (execute_partitions / "
                            "kernel build), never plan build — the "
                            "PR 2 captured-conf bug class"))


# ---------------------------------------------------------------------------
class ConfUnreadRule(Rule):
    """Rule 4's reverse: `conf-discipline` proves that a key that is
    USED is registered; nothing proved that a key that is registered
    is used, and five entries stayed in config.py and docs/configs.md
    for thirty PRs promising behaviour no module had.  A `ConfEntry`
    bound to a module-level name in a `config.py` must be referenced
    — by that name, or by its key as a string literal — somewhere in
    config.py's own package (its directory, recursively) outside the
    binding itself.  An option nothing reads is deleted, not
    documented."""

    rule_id = "conf-unread"
    doc = ("a ConfEntry bound to a module-level name in config.py must "
           "be read by some module of the package")

    def check(self, ctx: FileContext) -> list[Finding]:
        if ctx.components[-1] != "config.py":
            return []
        entries = self.entries(ctx.tree)
        if not entries:
            return []
        skip = {id(n) for _, _, stmt in entries
                for n in (stmt.targets[0], stmt.value.args[0])}
        seen = self._mentions(ctx.tree, skip)
        for path in _iter_py_files([os.path.dirname(ctx.path)]):
            if path == ctx.path:
                continue
            try:
                with open(path) as f:
                    seen |= self._mentions(ast.parse(f.read()), ())
            except (OSError, SyntaxError):
                continue        # run_lint reports it as parse-error
        return [self.finding(
                    ctx, stmt,
                    f"conf entry {name} ('{key}') is read by no module "
                    "of the package — delete it (and regenerate "
                    "docs/configs.md) or make something read it")
                for name, key, stmt in entries
                if name not in seen and key not in seen]

    @staticmethod
    def entries(tree: ast.Module) -> list:
        """(name, key, binding statement) of every module-level
        `NAME = conf("key", ...)`."""
        return [(stmt.targets[0].id, stmt.value.args[0].value, stmt)
                for stmt in tree.body
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and isinstance(stmt.value, ast.Call)
                and dotted(stmt.value.func) == "conf"
                and stmt.value.args
                and isinstance(stmt.value.args[0], ast.Constant)]

    @staticmethod
    def _mentions(tree: ast.AST, skip) -> set:
        """Every identifier, attribute name and string literal of a
        module, but for the nodes in `skip`."""
        out = set()
        for node in ast.walk(tree):
            if id(node) in skip:
                continue
            if isinstance(node, ast.Name):
                out.add(node.id)
            elif isinstance(node, ast.Attribute):
                out.add(node.attr)
            elif isinstance(node, ast.alias):
                out.add(node.name)
            elif (isinstance(node, ast.Constant)
                  and isinstance(node.value, str)):
                out.add(node.value)
        return out


# ---------------------------------------------------------------------------
class CompileUnderLockRule(Rule):
    """Rule 5 (PR 2/7): XLA trace/compile runs seconds-to-minutes, so
    it must never happen inside a `with <lock>:` body — KernelCache's
    single-flight path exists precisely so concurrent builders wait on
    an Event while the compile runs OUTSIDE the lock.  A jit (or a
    KernelCache build, which may compile) under a lock serializes
    every other query behind one compile."""

    rule_id = "compile-under-lock"
    doc = ("no jax.jit / named_jit / kernel build inside a 'with lock:' "
           "body — compile outside the lock (KernelCache single-flight)")

    _COMPILE_ATTRS = {"jit", "named_jit", "pallas_call", "get_or_build",
                      "_build_watched"}

    def check(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        self._walk(ctx, ctx.tree, locked=False, out=out)
        return out

    @staticmethod
    def _is_lock_expr(expr: ast.AST) -> bool:
        d = dotted(expr)
        if d is None:
            return False
        last = d.split(".")[-1].lower()
        return "lock" in last or last == "_cv"

    def _walk(self, ctx, node, locked, out):
        if isinstance(node, ast.With):
            locked = locked or any(
                self._is_lock_expr(i.context_expr)
                for i in node.items)
            for b in node.body:
                self._walk(ctx, b, locked, out)
            return
        if isinstance(node, ast.Call) and locked:
            d = dotted(node.func) or ""
            leaf = d.split(".")[-1]
            if leaf in self._COMPILE_ATTRS:
                out.append(self.finding(
                    ctx, node,
                    f"{leaf}() inside a 'with lock:' body — XLA "
                    "compiles run seconds-to-minutes; compile "
                    "outside the lock (see KernelCache's "
                    "single-flight path)"))
        for child in ast.iter_child_nodes(node):
            self._walk(ctx, child, locked, out)


# ---------------------------------------------------------------------------
class CollectiveDisciplineRule(Rule):
    """Rule 6 (PR 11/12, the mesh lanes): a mesh collective
    (`lax.all_to_all` / `psum` / `all_gather` / `ppermute`) blocks
    EVERY participant when one goes dark, so each dispatch must run
    under the collective-class watchdog (`watched_collective`,
    parallel/collective_exchange.py) — which also feeds the movement
    ledger's collective edge.  A call site is sanctioned when it is
    (a) lexically inside a `watched_collective(...)` argument (the
    dispatch thunk), or (b) inside an SPMD body registered with the
    watchdog by construction: a function passed to `shard_map`, any
    function it (transitively, same file) calls, or a function nested
    inside one — those run INSIDE a dispatch the caller already
    watches.  Anything else is a naked collective: a hang there is
    invisible to the watchdog and unaccounted by the ledger."""

    rule_id = "collective-discipline"
    doc = ("lax.all_to_all/psum/all_gather/ppermute must run under "
           "watched_collective or inside a shard_map/SPMD body")

    _COLLECTIVES = {"all_to_all", "psum", "all_gather", "ppermute"}

    def check(self, ctx: FileContext) -> list[Finding]:
        defs: dict[str, list] = {}          # name -> def nodes
        calls_in: dict[int, set] = {}       # id(def) -> called names
        nested_in: dict[int, set] = {}      # id(def) -> nested def names
        seeds: set = set()                  # shard_map/watched fn names
        sites: list = []                    # (node, def-name chain, watched?)

        def leaf(call) -> str:
            d = dotted(call.func)
            return d.split(".")[-1] if d else ""

        def walk(node, fn_stack, watched):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)
                for f in fn_stack:
                    nested_in.setdefault(id(f), set()).add(node.name)
                fn_stack = fn_stack + [node]
            elif isinstance(node, ast.Call):
                name = leaf(node)
                if name in ("shard_map", "watched_collective"):
                    for a in list(node.args) + [k.value
                                                for k in node.keywords]:
                        if isinstance(a, ast.Name):
                            seeds.add(a.id)
                    if name == "watched_collective":
                        # the dispatch thunk (usually a lambda) and
                        # everything lexically inside it is watched
                        watched = True
                elif name in self._COLLECTIVES:
                    sites.append((node, [f.name for f in fn_stack],
                                  watched))
                if fn_stack and name:
                    calls_in.setdefault(id(fn_stack[-1]),
                                        set()).add(name)
            for child in ast.iter_child_nodes(node):
                walk(child, fn_stack, watched)

        walk(ctx.tree, [], False)

        # closure: a seed body sanctions everything it calls (same
        # file) and every function nested inside it
        sanctioned: set = set()
        work = list(seeds)
        while work:
            name = work.pop()
            if name in sanctioned:
                continue
            sanctioned.add(name)
            for d in defs.get(name, []):
                for callee in calls_in.get(id(d), ()):
                    if callee in defs and callee not in sanctioned:
                        work.append(callee)
                for nested in nested_in.get(id(d), ()):
                    if nested not in sanctioned:
                        work.append(nested)

        out: list[Finding] = []
        for node, chain, watched in sites:
            if watched or any(n in sanctioned for n in chain):
                continue
            out.append(self.finding(
                ctx, node,
                f"{leaf_name(node)} is a mesh collective outside "
                "watched_collective and outside any shard_map/SPMD "
                "body — a wedged dispatch here blocks every mesh "
                "participant invisibly; wrap the dispatch in "
                "parallel.collective_exchange.watched_collective"))
        return out


def leaf_name(call: ast.Call) -> str:
    d = dotted(call.func)
    return (d.split(".")[-1] + "()") if d else "<collective>()"


ALL_RULES = [HostSyncRule(), BlockingWhileHoldingRule(),
             UnboundedWaitRule(), ConfDisciplineRule(), ConfUnreadRule(),
             CompileUnderLockRule(), CollectiveDisciplineRule()]


def rule_ids() -> list[str]:
    return [r.rule_id for r in ALL_RULES]
