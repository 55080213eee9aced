"""Retrace/host-sync pass: trace-poisoning patterns in jit-reachable code.

The capture/replay compiler (``models/compiled.py``) executes the op
library's Python bodies under ``jax.jit`` tracing.  In that world a
``float()``/``int()``/``bool()``/``.item()`` on a device value is a
ConcretizationError at best and a silent per-call host sync at worst,
Python branching on an array
value bakes one side into the trace, and iterating an unordered ``set``
into a fingerprint makes "same plan" hash differently run to run — the
bug class behind PR 11's silent ``jax.default_device`` recompile.

Rules (scope: ``ops/``, ``rowconv/``, ``plan/lower.py``,
``models/compiled.py`` — the traced-reachable tree; ``trace-iter``
additionally runs package-wide over fingerprint/cache-key functions):

``trace-host-sync``
    ``int()``/``float()``/``bool()`` whose argument contains a
    ``jnp.``/``jax.`` expression (or a device-style reduction method
    like ``.sum()``), any ``.item()`` call, and ``np.asarray``/
    ``np.array`` over a ``jnp`` expression.  The one sanctioned funnel
    is ``utils.syncs.scalar`` — it counts the sync and resolves from the
    tape under replay.

``trace-branch``
    ``if``/``while`` predicates containing a direct ``jnp.``/``jax.``
    call — data-dependent Python control flow does not trace.

``trace-iter``
    Iteration over a ``set``/``frozenset`` inside a function whose name
    says it computes a fingerprint/cache key — unordered iteration feeds
    nondeterminism straight into plan identity.
"""

from __future__ import annotations

import ast
import re

from .core import Finding, Source

__all__ = ["run", "TRACE_SCOPE_DIRS", "TRACE_SCOPE_FILES"]

TRACE_SCOPE_DIRS = ("spark_rapids_jni_tpu/ops/",
                    "spark_rapids_jni_tpu/rowconv/")
TRACE_SCOPE_FILES = ("spark_rapids_jni_tpu/plan/lower.py",
                     "spark_rapids_jni_tpu/models/compiled.py")

_REDUCTIONS = {"sum", "min", "max", "mean", "prod", "any", "all",
               "argmin", "argmax"}
_KEY_FN_RE = re.compile(
    r"fingerprint|cache_key|plan_key|size_key|_fp\b|\bfp_|hash_", re.I)


def in_trace_scope(rel: str) -> bool:
    return rel.startswith(TRACE_SCOPE_DIRS) or rel in TRACE_SCOPE_FILES


def _is_sanctioned_sync(node: ast.Call) -> bool:
    """``syncs.scalar(...)`` / ``scalar(...)`` — the one approved funnel.
    It counts the sync eagerly and resolves from the tape under replay
    (returning a plain int), so its result is host-safe to branch on."""
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "scalar" \
            and isinstance(f.value, ast.Name) and f.value.id == "syncs":
        return True
    return isinstance(f, ast.Name) and f.id == "scalar"


def _contains_device_expr(node: ast.expr) -> bool:
    """Heuristic: does the expression tree contain a ``jnp.``/``jax.``
    call or a reduction-style method call?  That is our stand-in for "a
    traced value" — a static pass can't see dynamic types, and this
    shape covers every host-sync regression this repo has actually had.
    ``syncs.scalar(...)`` subtrees are pruned: their results are tape
    ints, not traced values."""
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, ast.Call):
            if _is_sanctioned_sync(n):
                continue                      # prune: result is a host int
            f = n.func
            if isinstance(f, ast.Attribute):
                root = f.value
                while isinstance(root, ast.Attribute):
                    root = root.value
                if isinstance(root, ast.Name) and root.id in ("jnp", "jax",
                                                              "lax"):
                    return True
                # x.sum()/x.max()/... counts only when the receiver itself
                # involves jnp/jax — bare numpy host arrays (offs_np etc.)
                # reduce with the same method names and are NOT syncs
                if f.attr in _REDUCTIONS and any(
                        isinstance(d, ast.Name)
                        and d.id in ("jnp", "jax", "lax")
                        for d in ast.walk(f.value)):
                    return True
        stack.extend(ast.iter_child_nodes(n))
    return False


def _host_sync_findings(src: Source) -> list[Finding]:
    out = []
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "item" \
                and not node.args and not node.keywords:
            out.append(Finding(
                rule="trace-host-sync", path=src.rel, line=node.lineno,
                message=".item() forces a device->host sync in traced "
                        "code; route sizes through syncs.scalar"))
            continue
        name = None
        if isinstance(f, ast.Name) and f.id in ("int", "float", "bool"):
            name = f.id
        elif (isinstance(f, ast.Attribute)
              and f.attr in ("asarray", "array")
              and isinstance(f.value, ast.Name) and f.value.id == "np"):
            name = f"np.{f.attr}"
        if name is None or not node.args:
            continue
        if _contains_device_expr(node.args[0]):
            out.append(Finding(
                rule="trace-host-sync", path=src.rel, line=node.lineno,
                message=f"{name}() over a device expression forces a "
                        "host sync in traced code; route through "
                        "syncs.scalar"))
    return out


def _branch_findings(src: Source) -> list[Finding]:
    out = []
    for node in ast.walk(src.tree):
        if isinstance(node, (ast.If, ast.While)) \
                and _contains_device_expr(node.test):
            kw = "if" if isinstance(node, ast.If) else "while"
            out.append(Finding(
                rule="trace-branch", path=src.rel, line=node.lineno,
                message=f"`{kw}` predicate evaluates a device expression "
                        "— data-dependent Python control flow does not "
                        "trace (use jnp.where / lax.cond)"))
    return out


def _is_set_expr(node: ast.expr) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id in ("set", "frozenset"):
        return True
    return False


def _iter_findings(src: Source) -> list[Finding]:
    out = []
    for fn in ast.walk(src.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _KEY_FN_RE.search(fn.name):
            continue
        for node in ast.walk(fn):
            it = None
            if isinstance(node, ast.For):
                it = node.iter
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.GeneratorExp)):
                it = node.generators[0].iter
            if it is not None and _is_set_expr(it):
                out.append(Finding(
                    rule="trace-iter", path=src.rel, line=node.lineno,
                    message=f"unordered set iteration inside key/"
                            f"fingerprint function `{fn.name}` — sort "
                            "before hashing"))
    return out


def run(sources: list[Source]) -> list[Finding]:
    findings: list[Finding] = []
    for src in sources:
        if in_trace_scope(src.rel):
            findings += _host_sync_findings(src)
            findings += _branch_findings(src)
        if src.rel.startswith("spark_rapids_jni_tpu/"):
            findings += _iter_findings(src)
    return findings
