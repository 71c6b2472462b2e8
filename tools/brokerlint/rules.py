"""brokerlint rules: the concurrency/invariant contract this codebase
has repeatedly hand-fixed in review, encoded as ~8 AST checks.

Each rule exists because the defect class it catches has actually
shipped (or nearly shipped) in this repo — see README.md "Static
analysis" for the incident each rule encodes. The rules are heuristic
by design: they key on the repo's own conventions (``*_lock``
attribute names, ``*_locked`` method-name suffix meaning "caller holds
the lock", the ``mqtt_tpu_`` metric prefix) rather than attempting
whole-program analysis. Genuine exceptions carry an inline
``# brokerlint: ok=<RULE> <reason>`` pragma, so every suppression is a
documented decision at the site it covers.

Rule index
----------

- R1  no blocking calls / ``await`` under a held lock
- R2  thread-reachable code must not touch the event loop directly
- R3  no wall-clock ``time.time()`` (monotonic/perf_counter only)
- R4  no silent exception swallows (``except Exception: pass``)
- R5  no observer/hook callback invocation under a held lock
- R6  metric names: catalog drift + Prometheus naming scheme
- R7  every ``threading.Thread`` is explicit about ``daemon=`` and is
      bound somewhere it can be tracked/joined
- R8  no mutable default args; no module-level mutable-container
      singletons
"""

from __future__ import annotations

import ast
import fnmatch
import os
import re
from typing import Iterator, Optional

from .core import FileCtx, Finding

# -- shared helpers ---------------------------------------------------------

_LOCK_NAME_RE = re.compile(r"(^|_)(lock|mutex)$", re.IGNORECASE)

# functions whose NAME declares "caller holds the lock" — the repo's
# convention (_trip_locked, _transition_locked); their whole body is
# treated as a lock-held scope
_LOCKED_SUFFIX = "_locked"


def _terminal_name(node: ast.AST) -> Optional[str]:
    """The final identifier of a Name/Attribute chain (``self._lock`` ->
    ``_lock``), or None."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_lock_expr(node: ast.AST) -> bool:
    name = _terminal_name(node)
    return name is not None and bool(_LOCK_NAME_RE.search(name))


def _iter_scope(body: list) -> Iterator[ast.AST]:
    """Walk statements without descending into nested function/class
    definitions or lambdas — their bodies execute later, outside the
    enclosing lock scope. The prune happens at pop so a ``def`` sitting
    DIRECTLY in ``body`` (a callback defined inside a ``with`` block)
    is skipped exactly like one nested deeper."""
    stack = list(body)
    while stack:
        node = stack.pop()
        if isinstance(
            node,
            (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef),
        ):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _lock_scopes(ctx: FileCtx) -> Iterator[tuple[list, str]]:
    """Yield ``(body, lock_desc)`` for every lock-held scope: the body of
    each synchronous ``with <lock>:`` and the whole body of every
    ``*_locked`` function."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.With):
            for item in node.items:
                if _is_lock_expr(item.context_expr):
                    yield node.body, _dotted(item.context_expr) or "lock"
                    break
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name.endswith(_LOCKED_SUFFIX):
                yield node.body, f"{node.name}() [caller-held lock]"


# -- R1: blocking calls under a held lock -----------------------------------

_BLOCKING_DOTTED = {
    "time.sleep",
    "tempfile.mkdtemp",
    "tempfile.mkstemp",
    "os.makedirs",
    "os.replace",
    "os.unlink",
    "os.remove",
    "os.rename",
    "os.fsync",
    "shutil.rmtree",
    "shutil.copy",
    "subprocess.run",
    "subprocess.call",
    "subprocess.check_call",
    "subprocess.check_output",
    "subprocess.Popen",
    "json.dump",
    "json.load",
    "socket.create_connection",
}
_BLOCKING_BARE = {"open", "sleep"}
_BLOCKING_METHODS = {"recv", "recv_into", "sendall", "accept"}
_THREADISH_RE = re.compile(r"thread|worker|writer|proc|^t$|^w$", re.IGNORECASE)


def _is_blocking_call(call: ast.Call) -> Optional[str]:
    fn = call.func
    dotted = _dotted(fn)
    if dotted is not None and dotted in _BLOCKING_DOTTED:
        return dotted
    if isinstance(fn, ast.Name) and fn.id in _BLOCKING_BARE:
        return fn.id
    if isinstance(fn, ast.Attribute):
        if fn.attr in _BLOCKING_METHODS:
            return f"<sock>.{fn.attr}"
        if fn.attr == "join" and not isinstance(fn.value, ast.Constant):
            recv = _terminal_name(fn.value) or ""
            has_timeout = any(k.arg == "timeout" for k in call.keywords)
            if not call.args and not call.keywords:
                return f"{recv}.join"
            if has_timeout or _THREADISH_RE.search(recv):
                return f"{recv}.join"
    return None


def check_r1(ctx: FileCtx) -> list[Finding]:
    out = []
    flagged: set[int] = set()  # a node in nested lock scopes flags once
    for body, desc in _lock_scopes(ctx):
        for node in _iter_scope(body):
            if id(node) in flagged:
                continue
            if isinstance(node, ast.Await):
                out.append(
                    ctx.finding(
                        "R1", node,
                        f"`await` while holding {desc}: the lock pins the "
                        "event loop for every other holder",
                    )
                )
            elif isinstance(node, ast.Call):
                what = _is_blocking_call(node)
                if what is not None:
                    out.append(
                        ctx.finding(
                            "R1", node,
                            f"blocking call {what}() while holding {desc}; "
                            "move the I/O outside the critical section",
                        )
                    )
    return out


# -- R2: thread-reachable code touching the event loop ----------------------

# extra thread entry points the AST cannot see (queue-dispatched workers,
# executor targets resolved dynamically); keyed on the file's basename
THREAD_ENTRY_EXTRA: dict[str, set[str]] = {}

_LOOP_MUTATORS = {
    "call_soon",
    "call_later",
    "call_at",
    "create_task",
    "set_result",
    "set_exception",
}


def _module_functions(tree: ast.Module) -> dict[str, ast.AST]:
    out: dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, node)
    return out


def _thread_entries(ctx: FileCtx) -> set[str]:
    entries = set(THREAD_ENTRY_EXTRA.get(os.path.basename(ctx.rel), ()))
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted not in ("threading.Thread", "Thread"):
            continue
        for kw in node.keywords:
            if kw.arg == "target":
                name = _terminal_name(kw.value)
                if name:
                    entries.add(name)
    return entries


def _called_names(fn_node: ast.AST) -> set[str]:
    names: set[str] = set()
    for node in _iter_scope(getattr(fn_node, "body", [])):
        if isinstance(node, ast.Call):
            name = _terminal_name(node.func)
            if name:
                names.add(name)
    return names


def check_r2(ctx: FileCtx) -> list[Finding]:
    funcs = _module_functions(ctx.tree)
    entries = _thread_entries(ctx) & set(funcs)
    if not entries:
        return []
    # NOTE deliberately no exemption for functions passed to
    # call_soon_threadsafe: being *scheduled* onto the loop never puts a
    # function in `reachable` (an argument reference is not a call), and
    # a function a thread ALSO calls directly is exactly the
    # partial-fix shape this rule exists to catch
    reachable: set[str] = set()
    frontier = list(entries)
    while frontier:
        fn = frontier.pop()
        if fn in reachable:
            continue
        reachable.add(fn)
        for callee in _called_names(funcs[fn]):
            if callee in funcs and callee not in reachable:
                frontier.append(callee)
    out = []
    for fn in sorted(reachable):
        node = funcs[fn]
        if isinstance(node, ast.AsyncFunctionDef):
            continue  # coroutines run on the loop; Thread targets cannot be
        for sub in _iter_scope(node.body):
            if not isinstance(sub, ast.Call):
                continue
            attr = (
                sub.func.attr if isinstance(sub.func, ast.Attribute) else None
            )
            if attr in _LOOP_MUTATORS:
                out.append(
                    ctx.finding(
                        "R2", sub,
                        f"{attr}() inside `{fn}`, which is reachable from a "
                        "thread entry point; cross-thread loop wakes must go "
                        "through loop.call_soon_threadsafe",
                    )
                )
            elif _dotted(sub.func) in ("asyncio.ensure_future",):
                out.append(
                    ctx.finding(
                        "R2", sub,
                        f"asyncio.ensure_future() inside thread-reachable "
                        f"`{fn}`; use run_coroutine_threadsafe",
                    )
                )
    return out


# -- R3: wall-clock time in timing code -------------------------------------


def check_r3(ctx: FileCtx) -> list[Finding]:
    out = []
    # `from time import time` would hide the call behind a bare name
    bare_time = any(
        isinstance(n, ast.ImportFrom)
        and n.module == "time"
        and any(a.name == "time" for a in n.names)
        for n in ast.walk(ctx.tree)
    )
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        hit = dotted == "time.time" or (
            bare_time and isinstance(node.func, ast.Name)
            and node.func.id == "time"
        )
        if hit:
            out.append(
                ctx.finding(
                    "R3", node,
                    "wall-clock time.time(): latency/uptime/deadline math "
                    "must use time.monotonic()/perf_counter() (NTP steps "
                    "bend wall time); genuine wall-time uses take a "
                    "reasoned pragma",
                )
            )
    return out


# -- R4: silent exception swallows ------------------------------------------


def _body_is_silent(body: list) -> bool:
    """True when a handler body does nothing observable: only pass /
    continue / docstring."""
    for stmt in body:
        if isinstance(stmt, (ast.Pass, ast.Continue)):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant):
            continue  # docstring/ellipsis
        return False
    return True


def check_r4(ctx: FileCtx) -> list[Finding]:
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            out.append(
                ctx.finding(
                    "R4", node,
                    "bare `except:` catches SystemExit/KeyboardInterrupt "
                    "too; name the exceptions",
                )
            )
            continue
        names = []
        types = (
            node.type.elts if isinstance(node.type, ast.Tuple) else [node.type]
        )
        for t in types:
            name = _terminal_name(t)
            if name:
                names.append(name)
        if any(n in ("Exception", "BaseException") for n in names):
            if _body_is_silent(node.body):
                out.append(
                    ctx.finding(
                        "R4", node,
                        "`except Exception` swallowed without a counter or "
                        "log; count it, log it, or pragma it with the reason "
                        "it is safe to drop",
                    )
                )
    return out


# -- R5: observer/hook callbacks invoked under a held lock ------------------

_OBSERVER_ATTR_RE = re.compile(
    r"^on_[a-z_]+$|(_observer|_callback|_hook|_listener)s?$"
)
_OBSERVER_CONTAINER_RE = re.compile(r"(_observers|_callbacks|_hooks|_listeners)$")


def _observer_locals(body: list) -> set[str]:
    """Names bound (in this scope) from observer-ish attributes or by
    iterating an observer container: ``cb = self.on_trip`` /
    ``for fn in self._observers``."""
    out: set[str] = set()
    for node in _iter_scope(body):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Attribute):
            if _OBSERVER_ATTR_RE.search(node.value.attr):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        out.add(tgt.id)
        elif isinstance(node, ast.For):
            src = _terminal_name(node.iter)
            if src and _OBSERVER_CONTAINER_RE.search(src):
                if isinstance(node.target, ast.Name):
                    out.add(node.target.id)
    return out


def _funcs_called_only_under_locks(ctx: FileCtx) -> set[str]:
    """One level of interprocedural propagation: a module function whose
    every same-module call site sits inside a lock scope is itself a
    lock-held scope (the trie's ``_notify`` pattern)."""
    funcs = _module_functions(ctx.tree)
    lock_bodies = [body for body, _ in _lock_scopes(ctx)]

    def calls_in(nodes: Iterator[ast.AST]) -> set[str]:
        out = set()
        for node in nodes:
            if isinstance(node, ast.Call):
                name = _terminal_name(node.func)
                if name in funcs:
                    out.add(name)
        return out

    in_lock: set[str] = set()
    for body in lock_bodies:
        in_lock |= calls_in(_iter_scope(body))
    # called at least once, and only ever from lock scopes
    out = set()
    for name in in_lock:
        total = sum(
            1
            for node in ast.walk(ctx.tree)
            if isinstance(node, ast.Call) and _terminal_name(node.func) == name
        )
        locked = sum(
            1
            for body in lock_bodies
            for node in _iter_scope(body)
            if isinstance(node, ast.Call) and _terminal_name(node.func) == name
        )
        if total and total == locked:
            out.add(name)
    return out


def check_r5(ctx: FileCtx) -> list[Finding]:
    out = []
    scopes = [(body, desc) for body, desc in _lock_scopes(ctx)]
    for name in _funcs_called_only_under_locks(ctx):
        node = _module_functions(ctx.tree)[name]
        scopes.append(
            (node.body, f"{name}() [only ever called under a lock]")
        )
    for body, desc in scopes:
        local_cbs = _observer_locals(body)
        for node in _iter_scope(body):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) and _OBSERVER_ATTR_RE.search(fn.attr):
                out.append(
                    ctx.finding(
                        "R5", node,
                        f"observer callback `{fn.attr}` invoked while "
                        f"holding {desc}; capture it under the lock, call "
                        "it after release (a re-registering or slow "
                        "observer deadlocks/stalls every other holder)",
                    )
                )
            elif isinstance(fn, ast.Name) and fn.id in local_cbs:
                out.append(
                    ctx.finding(
                        "R5", node,
                        f"observer callback `{fn.id}` invoked while holding "
                        f"{desc}; call it after the lock is released",
                    )
                )
    return out


# -- R6: metric-name catalog drift + naming scheme (project rule) -----------

_METRIC_PREFIX = "mqtt_tpu_"
_METRIC_NAME_RE = re.compile(r"^mqtt_tpu_[a-z][a-z0-9_]*$")
_METRIC_FACTORIES = {"counter", "gauge", "histogram"}


def _factory_calls(tree: ast.Module) -> Iterator[ast.Call]:
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _METRIC_FACTORIES
            and node.args
        ):
            yield node


def _metric_tables(tree: ast.Module) -> dict[str, tuple[list[str], str]]:
    """NamedTuple classes that declare metrics, by class name: their
    field names in order and the default of their ``kind`` field (a
    class with ``metric`` and ``kind`` fields)."""
    out = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        if not any(_terminal_name(b) == "NamedTuple" for b in node.bases):
            continue
        fields = []
        kind_default = ""
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target, ast.Name):
                fields.append(stmt.target.id)
                if (
                    stmt.target.id == "kind"
                    and isinstance(stmt.value, ast.Constant)
                    and isinstance(stmt.value.value, str)
                ):
                    kind_default = stmt.value.value
        if "metric" in fields and "kind" in fields:
            out[node.name] = (fields, kind_default)
    return out


def _call_field(call: ast.Call, fields: list[str], field: str) -> Optional[ast.AST]:
    """The argument a call to a NamedTuple class passes for ``field``."""
    for kw in call.keywords:
        if kw.arg == field:
            return kw.value
    i = fields.index(field)
    return call.args[i] if i < len(call.args) else None


def _code_metrics(ctxs: list[FileCtx]) -> list[tuple[FileCtx, ast.Call, str, str]]:
    """Every metric name the code registers, as ``(ctx, call-node, kind,
    name)``. Covers the direct literal form, the repo's loop form::

        for name, attr in (("mqtt_tpu_x", "x"), ...):
            r.counter(name, ...)

    and rows of a table whose NamedTuple class has ``metric`` and
    ``kind`` fields (``Row("x", "mqtt_tpu_x_total", ..., kind="gauge")``).
    """
    out = []
    for ctx in ctxs:
        tables = _metric_tables(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in tables
            ):
                continue
            fields, kind_default = tables[node.func.id]
            name = _call_field(node, fields, "metric")
            kind = _call_field(node, fields, "kind")
            kind_s = kind_default
            if isinstance(kind, ast.Constant) and isinstance(kind.value, str):
                kind_s = kind.value
            if (
                isinstance(name, ast.Constant)
                and isinstance(name.value, str)
                and name.value.startswith(_METRIC_PREFIX)
                and kind_s in _METRIC_FACTORIES
            ):
                out.append((ctx, node, kind_s, name.value))
        for node in _factory_calls(ctx.tree):
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                if arg.value.startswith(_METRIC_PREFIX):
                    out.append((ctx, node, node.func.attr, arg.value))
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, ast.For):
                continue
            # which loop-target name feeds a factory's first argument?
            tgt_names = []
            if isinstance(loop.target, ast.Name):
                tgt_names = [(loop.target.id, None)]
            elif isinstance(loop.target, ast.Tuple):
                tgt_names = [
                    (e.id, i)
                    for i, e in enumerate(loop.target.elts)
                    if isinstance(e, ast.Name)
                ]
            for call in _factory_calls(ast.Module(body=loop.body, type_ignores=[])):
                arg = call.args[0]
                if not isinstance(arg, ast.Name):
                    continue
                idx = next(
                    (i for n, i in tgt_names if n == arg.id), "missing"
                )
                if idx == "missing":
                    continue
                if not isinstance(loop.iter, (ast.Tuple, ast.List)):
                    continue
                for item in loop.iter.elts:
                    elem = item
                    if idx is not None:
                        if not isinstance(item, (ast.Tuple, ast.List)):
                            continue
                        if idx >= len(item.elts):
                            continue
                        elem = item.elts[idx]
                    if isinstance(elem, ast.Constant) and isinstance(
                        elem.value, str
                    ) and elem.value.startswith(_METRIC_PREFIX):
                        out.append((ctx, call, call.func.attr, elem.value))
    return out


def _catalog_patterns(root: str) -> Optional[set[str]]:
    """Metric names/globs from the README catalog table: every backticked
    token in the lines between the "Metrics catalog" heading and the next
    blank-after-table. Returns None when the catalog cannot be found."""
    readme = os.path.join(root, "README.md")
    if not os.path.exists(readme):
        return None
    with open(readme, encoding="utf-8") as f:
        text = f.read()
    m = re.search(r"Metrics catalog[^\n]*\n\s*\n?((?:\|[^\n]*\n)+)", text)
    if m is None:
        return None
    pats: set[str] = set()
    for row in m.group(1).splitlines():
        cells = row.split("|")
        if len(cells) < 2:
            continue
        first = cells[1]
        for tok in re.findall(r"`([^`]+)`", first):
            tok = tok.strip()
            if not tok or tok in ("name",):
                continue
            if not tok.startswith(_METRIC_PREFIX):
                tok = _METRIC_PREFIX + tok
            pats.add(tok)
    return pats or None


def check_r6(ctxs: list[FileCtx], root: str) -> list[Finding]:
    metrics = _code_metrics(ctxs)
    out: list[Finding] = []
    for ctx, node, kind, name in metrics:
        if not _METRIC_NAME_RE.match(name):
            out.append(
                ctx.finding(
                    "R6", node,
                    f"metric {name!r} violates the naming scheme "
                    "(^mqtt_tpu_[a-z][a-z0-9_]*$)",
                )
            )
            continue
        if kind == "counter" and not name.endswith("_total"):
            out.append(
                ctx.finding(
                    "R6", node,
                    f"counter {name!r} must end in `_total` (Prometheus "
                    "counter convention)",
                )
            )
        elif kind == "histogram" and not name.endswith(
            ("_seconds", "_ratio", "_bytes")
        ):
            out.append(
                ctx.finding(
                    "R6", node,
                    f"histogram {name!r} must carry a unit suffix "
                    "(_seconds/_ratio/_bytes)",
                )
            )
        elif kind == "gauge" and name.endswith("_total"):
            out.append(
                ctx.finding(
                    "R6", node,
                    f"gauge {name!r} must not end in `_total` (reads as a "
                    "counter on the scrape side)",
                )
            )
    pats = _catalog_patterns(root)
    if pats is None:
        if metrics:
            ctx = metrics[0][0]
            out.append(
                Finding(
                    "R6", "README.md", 1, 0,
                    "README metrics catalog not found (expected a table "
                    "under a 'Metrics catalog' heading)", "",
                )
            )
        return out
    code_names = {name for _, _, _, name in metrics}
    for ctx, node, _, name in metrics:
        if not any(fnmatch.fnmatchcase(name, p) for p in pats):
            out.append(
                ctx.finding(
                    "R6", node,
                    f"metric {name!r} missing from the README metrics "
                    "catalog (doc drift)",
                )
            )
    # the reverse direction covers globs too: a catalog row (literal or
    # wildcard) that no registered metric matches is stale documentation
    for p in sorted(pats):
        if not any(fnmatch.fnmatchcase(n, p) for n in code_names):
            out.append(
                Finding(
                    "R6", "README.md", 1, 0,
                    f"catalog lists {p!r} but no code registers a "
                    "matching metric (doc drift)", "",
                )
            )
    return out


# -- R7: threads without explicit daemon= / any tracking binding ------------


def _thread_calls(ctx: FileCtx) -> Iterator[ast.Call]:
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            if _dotted(node.func) in ("threading.Thread", "Thread"):
                yield node


def check_r7(ctx: FileCtx) -> list[Finding]:
    out = []
    # map each Thread(...) call to its parent statement context
    parents: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(ctx.tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    for call in _thread_calls(ctx):
        if not any(k.arg == "daemon" for k in call.keywords):
            out.append(
                ctx.finding(
                    "R7", call,
                    "threading.Thread without explicit daemon=: decide "
                    "whether interpreter exit may abandon this thread",
                )
            )
        parent = parents.get(call)
        tracked = False
        if isinstance(parent, (ast.Assign, ast.AnnAssign, ast.NamedExpr)):
            tracked = True
        elif isinstance(parent, ast.Call):
            fn = parent.func
            if isinstance(fn, ast.Attribute) and fn.attr in ("append", "add"):
                tracked = True
        elif isinstance(parent, ast.Attribute):
            # threading.Thread(...).start() — constructed and dropped
            tracked = False
        if not tracked:
            out.append(
                ctx.finding(
                    "R7", call,
                    "thread constructed without a binding (no join/tracking "
                    "path); assign it so shutdown can account for it",
                )
            )
    return out


# -- R8: mutable defaults / module-level mutable singletons -----------------


def _is_mutable_literal(node: ast.AST, empty_only: bool) -> bool:
    if isinstance(node, (ast.List, ast.Set)):
        return not node.elts if empty_only else True
    if isinstance(node, ast.Dict):
        return not node.keys if empty_only else True
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        if node.func.id in ("list", "dict", "set", "deque", "defaultdict"):
            return not node.args and not node.keywords if empty_only else True
    return False


def check_r8(ctx: FileCtx) -> list[Finding]:
    out = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = node.args
            for default in list(args.defaults) + [
                d for d in args.kw_defaults if d is not None
            ]:
                if _is_mutable_literal(default, empty_only=False):
                    out.append(
                        ctx.finding(
                            "R8", default,
                            f"mutable default argument in {node.name}(): "
                            "shared across every call; default to None",
                        )
                    )
    for stmt in ctx.tree.body:
        targets: list = []
        value = None
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        if value is None or not _is_mutable_literal(value, empty_only=True):
            continue
        names = [t.id for t in targets if isinstance(t, ast.Name)]
        if names:
            out.append(
                ctx.finding(
                    "R8", stmt,
                    f"module-level mutable singleton {names[0]!r}: shared "
                    "unlocked state across every importer/thread; scope it "
                    "to an owner object",
                )
            )
    return out


def _loop_rule(name: str):
    # late import: loopgraph imports helpers from this module
    def run(ctx: FileCtx) -> list[Finding]:
        from . import loopgraph

        return getattr(loopgraph, name)(ctx)

    run.__name__ = name
    return run


FILE_RULES = {
    "R1": check_r1,
    "R2": check_r2,
    "R3": check_r3,
    "R4": check_r4,
    "R5": check_r5,
    "R7": check_r7,
    "R8": check_r8,
    "R10": _loop_rule("check_r10"),
    "R11": _loop_rule("check_r11"),
    "R12": _loop_rule("check_r12"),
    "R13": _loop_rule("check_r13"),
    "R14": _loop_rule("check_r14"),
    "R15": _loop_rule("check_r15"),
}

def _check_r9(ctxs: list[FileCtx], root: str) -> list[Finding]:
    # late import: lockgraph imports helpers from this module
    from .lockgraph import check_r9

    return check_r9(ctxs, root)


PROJECT_RULES = {
    "R6": check_r6,
    "R9": _check_r9,
}

RULE_DOC = {
    "R1": "no blocking I/O or await while holding a lock",
    "R2": "thread-reachable code must use call_soon_threadsafe",
    "R3": "no wall-clock time.time() in timing code",
    "R4": "no silent exception swallows",
    "R5": "no observer callbacks invoked under a held lock",
    "R6": "metric names: README catalog sync + naming scheme",
    "R7": "threads: explicit daemon= and a tracking binding",
    "R8": "no mutable default args / module-level mutable singletons",
    "R9": "lock-order graph: acyclic and consistent with LOCK_ORDER",
    "R10": "loop-affine objects: foreign threads marshal via "
           "call_soon_threadsafe (N-shard generalization of R2)",
    "R11": "no blocking calls inside async bodies or loop callbacks",
    "R12": "futures resolve on their creation loop or via a marshal seam",
    "R13": "every spawned task is bound or registered in a tracked set",
    "R14": "no await/blocking calls in functions only called under locks",
    "R15": "no implicit device->host syncs on the device hot path",
}
