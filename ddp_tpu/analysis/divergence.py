"""AST lint for multi-host control-flow divergence.

SPMD's contract is that every process runs the SAME sequence of
collectives.  A collective (a ``psum``, a process-level barrier, the
preemption stop decision, the straggler gather) that is only *sometimes*
reached — under a rank check, inside an exception handler, behind
queue/timing state — is the classic whole-pod hang: the hosts that enter
it wait forever for the hosts that didn't.  This pass flags exactly that
shape, host-side (the traced SPMD bodies are uniform by construction —
``lax.cond`` traces both branches — and belong to the jaxpr auditor, so
``step.py``/``zero.py``/``epoch.py``/``layers.py`` are excluded here).

Two rules, per function:

1. **Guarded collective** — a collective call lexically under a
   condition the pass cannot prove uniform across hosts (anything but
   constants, ``process_count``/``device_count``-style topology reads,
   and locals derived only from those).  ``except`` handlers are
   host-local by definition (one host's I/O error is not another's).
   A collective in an ``if``'s TEST position is fine — the test itself
   executes unconditionally (the preemption guard's
   ``if _process_any(mesh, local):`` is the sanctioned pattern: decide
   *collectively*, then branch).
2. **Host-local early exit** — a ``return`` under a non-uniform
   condition, followed later in the same function by a collective: the
   host that returned early skips a collective the others enter.  Same
   deadlock, no lexical nesting.

Deliberate exceptions carry ``# analysis: divergence-ok(<why all hosts
agree>)`` on the flagged line, the line above, or the guard line — the
same greppable decision-trail vocabulary as ``host-sync-ok`` /
``unlocked-ok``.  The annotation's argument should say why the condition
is in fact uniform (constructor-time config identical on every host, a
value that is itself the result of a collective, ...).
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

from .findings import Finding, make_finding

SCAN_PACKAGES = ("train", "resilience", "obs", "parallel", "serve", "data")

# Traced-SPMD module basenames: uniform by construction, owned by the
# jaxpr auditor (collectives there live under jnp/lax control flow that
# traces both sides).
EXCLUDE_BASENAMES = ("step.py", "zero.py", "epoch.py", "layers.py")

# A call is "a collective" when its last dotted component is one of
# these: the jax named-axis collectives plus this codebase's host-level
# coordination helpers (each is, or transitively runs, a cross-process
# rendezvous).
COLLECTIVE_CALLS = frozenset((
    "psum", "pmean", "pmax", "pmin", "all_gather", "reduce_scatter",
    "psum_scatter", "ppermute", "all_to_all", "pbroadcast",
    "process_allgather", "sync_global_devices", "broadcast_one_to_all",
    # repo coordination helpers (resilience/, obs/):
    "should_stop", "_process_any", "straggler_report",
    "epoch_straggler_record", "_gather_host_rows",
))

# Calls whose result is identical on every host: mesh topology reads.
# (``process_index`` is deliberately NOT here — a rank check is the
# canonical divergent condition.)
UNIFORM_CALLS = frozenset(("process_count", "device_count",
                           "local_device_count"))

_OK_RE = re.compile(r"#\s*analysis:\s*divergence-ok\(([^)]*)\)")


class _Guard(NamedTuple):
    lineno: int
    reason: str


class _Exit(NamedTuple):
    lineno: int
    guard: _Guard


def _annotated_ok(lines: List[str], *linenos: int) -> bool:
    for ln in linenos:
        for cand in (ln, ln - 1):
            if 1 <= cand <= len(lines) and _OK_RE.search(lines[cand - 1]):
                return True
    return False


def _call_name(node: ast.Call) -> str:
    parts: List[str] = []
    f = node.func
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
    return ".".join(reversed(parts))


def _describe(node: ast.AST, limit: int = 48) -> str:
    try:
        text = ast.unparse(node)
    except Exception:
        text = type(node).__name__
    return text if len(text) <= limit else text[:limit - 3] + "..."


def _own_statements(fn: ast.AST):
    """Walk a function's own statements, not those of nested defs."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if not isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                stack.append(child)


_NONUNIFORM = ast.Call(func=ast.Name(id="<nonuniform>", ctx=ast.Load()),
                       args=[], keywords=[])


def _uniform_names(fn: ast.AST) -> frozenset:
    """Locals provably uniform: assigned only from uniform expressions
    (fixpoint, so ``multi = dist.process_count() > 1`` then
    ``quiet = not multi`` both qualify).  A name bound by a loop target,
    an augmented assignment, tuple unpacking, or a ``with ... as`` is
    never provable."""
    assigns: Dict[str, List[ast.AST]] = {}

    def taint(target: ast.AST) -> None:
        for t in ast.walk(target):
            if isinstance(t, ast.Name):
                assigns.setdefault(t.id, []).append(_NONUNIFORM)

    for node in _own_statements(fn):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    assigns.setdefault(tgt.id, []).append(node.value)
                else:
                    taint(tgt)
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            if isinstance(node.target, ast.Name):
                assigns.setdefault(node.target.id, []).append(node.value)
            else:
                taint(node.target)
        elif isinstance(node, (ast.AugAssign, ast.For)):
            taint(node.target)
        elif isinstance(node, ast.With):
            for item in node.items:
                if item.optional_vars is not None:
                    taint(item.optional_vars)
        elif isinstance(node, ast.NamedExpr):
            taint(node.target)
    uniform: set = set()
    for _ in range(len(assigns) + 1):
        changed = False
        for name, values in assigns.items():
            if name in uniform:
                continue
            if all(_is_uniform(v, frozenset(uniform)) for v in values):
                uniform.add(name)
                changed = True
        if not changed:
            break
    return frozenset(uniform)


def _is_uniform(node: ast.AST, uniform_names: frozenset) -> bool:
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Name):
        return node.id in uniform_names
    if isinstance(node, ast.UnaryOp):
        return _is_uniform(node.operand, uniform_names)
    if isinstance(node, ast.BoolOp):
        return all(_is_uniform(v, uniform_names) for v in node.values)
    if isinstance(node, ast.Compare):
        return (_is_uniform(node.left, uniform_names)
                and all(_is_uniform(c, uniform_names)
                        for c in node.comparators))
    if isinstance(node, ast.BinOp):
        return (_is_uniform(node.left, uniform_names)
                and _is_uniform(node.right, uniform_names))
    if isinstance(node, ast.IfExp):
        return all(_is_uniform(n, uniform_names)
                   for n in (node.test, node.body, node.orelse))
    if isinstance(node, ast.Call):
        name = _call_name(node).rsplit(".", 1)[-1]
        return (name in UNIFORM_CALLS
                and all(_is_uniform(a, uniform_names) for a in node.args))
    return False


class _FunctionScan:
    def __init__(self, path: str, lines: List[str], fn: ast.AST):
        self.path = path
        self.lines = lines
        self.fn = fn
        self.uniform = _uniform_names(fn)
        self.findings: List[Finding] = []
        self.exits: List[_Exit] = []
        self.unguarded: List[Tuple[int, str]] = []

    def run(self) -> List[Finding]:
        self._scan(self.fn.body, [])
        for lineno, name in self.unguarded:
            prior = [e for e in self.exits if e.lineno < lineno]
            if not prior:
                continue
            e = prior[0]
            if _annotated_ok(self.lines, lineno, e.lineno, e.guard.lineno):
                continue
            self.findings.append(make_finding(
                "error", "divergence", f"{self.path}:{lineno}",
                f"collective {name}() is only reached past a host-local "
                f"early return at line {e.lineno} (condition at line "
                f"{e.guard.lineno}: {e.guard.reason}) — a host that "
                "returns early skips a collective the others enter and "
                "the pod hangs; make the exit condition uniform or "
                "annotate '# analysis: divergence-ok(why all hosts "
                "agree)'"))
        return self.findings

    # -- statement walk ---------------------------------------------------

    def _scan(self, stmts, guards: List[_Guard]) -> None:
        for node in stmts:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue                       # scanned as its own function
            if isinstance(node, ast.If):
                self._check_expr(node.test, guards)
                new = guards
                if not _is_uniform(node.test, self.uniform):
                    new = guards + [_Guard(node.lineno,
                                           f"`{_describe(node.test)}`")]
                self._scan(node.body, new)
                self._scan(node.orelse, new)
            elif isinstance(node, ast.While):
                self._check_expr(node.test, guards)
                new = guards
                if not _is_uniform(node.test, self.uniform):
                    new = guards + [_Guard(node.lineno,
                                           f"`{_describe(node.test)}`")]
                self._scan(node.body, new)
                self._scan(node.orelse, new)
            elif isinstance(node, ast.For):
                self._check_expr(node.iter, guards)
                self._scan(node.body, guards)
                self._scan(node.orelse, guards)
            elif isinstance(node, ast.Try):
                self._scan(node.body, guards)
                for handler in node.handlers:
                    hg = guards + [_Guard(
                        handler.lineno,
                        "except handler (a host-local failure path)")]
                    self._scan(handler.body, hg)
                self._scan(node.orelse, guards)
                self._scan(node.finalbody, guards)
            elif isinstance(node, ast.With):
                for item in node.items:
                    self._check_expr(item.context_expr, guards)
                self._scan(node.body, guards)
            elif isinstance(node, ast.Return):
                if guards:
                    self.exits.append(_Exit(node.lineno, guards[-1]))
                if node.value is not None:
                    self._check_expr(node.value, guards)
            else:
                self._check_expr(node, guards)

    def _check_expr(self, node: ast.AST, guards: List[_Guard]) -> None:
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            name = _call_name(call)
            if name.rsplit(".", 1)[-1] not in COLLECTIVE_CALLS:
                continue
            if not guards:
                self.unguarded.append((call.lineno, name))
                continue
            g = guards[-1]
            if _annotated_ok(self.lines, call.lineno, g.lineno):
                continue
            self.findings.append(make_finding(
                "error", "divergence", f"{self.path}:{call.lineno}",
                f"collective {name}() under a host-local condition "
                f"(line {g.lineno}: {g.reason}) — hosts that disagree on "
                "it run different collective sequences and the pod "
                "hangs; decide collectively first (the "
                "`if _process_any(...)` pattern), make the condition "
                "uniform, or annotate '# analysis: divergence-ok(why "
                "all hosts agree)'"))


def _functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def scan_source(path: str, source: str) -> List[Finding]:
    """Divergence findings for one module's source text."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [make_finding("warning", "divergence", path,
                             f"unparseable: {e}")]
    lines = source.splitlines()
    out: List[Finding] = []
    for fn in _functions(tree):
        out.extend(_FunctionScan(path, lines, fn).run())
    return out


def scan_packages(root: str,
                  packages: Tuple[str, ...] = SCAN_PACKAGES,
                  exclude: Tuple[str, ...] = EXCLUDE_BASENAMES
                  ) -> List[Finding]:
    """Walk the given subpackages of the ddp_tpu package root."""
    out: List[Finding] = []
    for pkg in packages:
        pkg_dir = os.path.join(root, pkg)
        if not os.path.isdir(pkg_dir):
            continue
        for dirpath, _dirs, files in os.walk(pkg_dir):
            for fname in sorted(files):
                if not fname.endswith(".py") or fname in exclude:
                    continue
                fpath = os.path.join(dirpath, fname)
                rel = os.path.relpath(fpath, os.path.dirname(root))
                with open(fpath, "r", encoding="utf-8") as fh:
                    out.extend(scan_source(rel, fh.read()))
    return out
