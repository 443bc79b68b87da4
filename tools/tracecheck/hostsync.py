"""host-sync pass (TC1xx): implicit device→host transfers on hot paths.

Hot = every function reachable from ``lm.decode_many`` or any
``DeviceRunner`` method (the per-token and per-admission device paths the
serving engine's host-syncs/token metric measures).  Rules:

* TC101 — ``.item()`` call in a hot function (each is one blocking sync);
* TC102 — ``int()``/``float()``/``bool()`` applied to an array-valued
  expression in a hot function;
* TC103 — ``jax.device_get`` in a hot function (the *designed* syncs — one
  per decode chunk, one per admission — live in the baseline);
* TC104 — ``np.asarray``/``np.array`` on an array value in a hot function
  (silent d2h copy; use an explicit ``jax.device_get`` if intended);
* TC105 — Python ``if``/``while`` on an array value inside traced code
  (jit-decorated defs, scan bodies, and helpers they call) — a
  ConcretizationError at best, a silent sync under eager fallback.

Array-valued-ness is a local taint: names assigned from ``jnp.*`` /
``jax.*`` / ``lax.*`` calls (and arithmetic/indexing thereof), minus
metadata reads (``.shape``/``.ndim``/``.dtype``/``len``).  Function
parameters are deliberately *not* tainted — config/static-arg branching
is ubiquitous and legitimate; the bug class this catches is branching on
*computed* device values.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Sequence, Set

from . import callgraph
from .core import Finding, Repo

HOT_ROOTS = [
    "repro.models.lm.decode_many",
    "repro.serving.runner.DeviceRunner",
]

# attribute reads that leave the device-value world
_META_ATTRS = {"shape", "ndim", "dtype", "size", "nbytes", "sharding"}
# methods that already ARE host syncs (flagged separately, not taint)
_HOST_METHODS = {"item", "tolist", "block_until_ready"}
_ARRAY_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "lax.", "jax.nn.",
                   "jax.random.")
_ARRAY_CALLS = {"jax.device_put", "jax.eval_shape"}
# jax/lax calls that return Python ints (mesh metadata), not arrays
_STATIC_CALLS = {"axis_size"}


def _text_dotted(expr: ast.AST) -> Optional[str]:
    """Attribute chain exactly as written (no import resolution)."""
    parts: List[str] = []
    node = expr
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _is_array_call(expr: ast.Call) -> bool:
    d = _text_dotted(expr.func)
    if d is None:
        return False
    if d in _ARRAY_CALLS:
        return True
    if any(d.startswith(p) for p in _ARRAY_PREFIXES):
        tail = d.rsplit(".", 1)[-1]
        return tail not in _META_ATTRS | _STATIC_CALLS
    return False


def expr_tainted(expr: ast.AST, tainted: Set[str]) -> bool:
    """Does ``expr`` evaluate to a device array, given tainted names?"""
    if isinstance(expr, ast.Name):
        return expr.id in tainted
    if isinstance(expr, ast.Call):
        if _is_array_call(expr):
            return True
        if isinstance(expr.func, ast.Attribute):
            # x.astype(...) / x.sum() on a tainted x stays tainted; x.item()
            # and friends leave the device
            if expr.func.attr in _HOST_METHODS | _META_ATTRS:
                return False
            return expr_tainted(expr.func.value, tainted)
        return False
    if isinstance(expr, ast.Attribute):
        if expr.attr in _META_ATTRS | _HOST_METHODS:
            return False
        return expr_tainted(expr.value, tainted)
    if isinstance(expr, ast.Subscript):
        return expr_tainted(expr.value, tainted)
    if isinstance(expr, ast.BinOp):
        return expr_tainted(expr.left, tainted) or expr_tainted(
            expr.right, tainted)
    if isinstance(expr, ast.UnaryOp):
        return expr_tainted(expr.operand, tainted)
    if isinstance(expr, ast.Compare):
        return expr_tainted(expr.left, tainted) or any(
            expr_tainted(c, tainted) for c in expr.comparators)
    if isinstance(expr, ast.BoolOp):
        return any(expr_tainted(v, tainted) for v in expr.values)
    if isinstance(expr, ast.IfExp):
        return expr_tainted(expr.body, tainted) or expr_tainted(
            expr.orelse, tainted)
    if isinstance(expr, (ast.Tuple, ast.List)):
        return any(expr_tainted(e, tainted) for e in expr.elts)
    return False


def _target_names(tgt: ast.AST) -> List[str]:
    if isinstance(tgt, ast.Name):
        return [tgt.id]
    if isinstance(tgt, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in tgt.elts:
            out.extend(_target_names(e))
        return out
    return []


def taint_names(fn: ast.AST) -> Set[str]:
    """Fixpoint over assignments: names holding device arrays."""
    tainted: Set[str] = set()
    for _ in range(4):
        changed = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                if expr_tainted(node.value, tainted):
                    for t in node.targets:
                        for n in _target_names(t):
                            if n not in tainted:
                                tainted.add(n)
                                changed = True
            elif isinstance(node, ast.AugAssign):
                if (isinstance(node.target, ast.Name)
                        and expr_tainted(node.value, tainted)
                        and node.target.id not in tainted):
                    tainted.add(node.target.id)
                    changed = True
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                if (isinstance(node.target, ast.Name)
                        and expr_tainted(node.value, tainted)
                        and node.target.id not in tainted):
                    tainted.add(node.target.id)
                    changed = True
        if not changed:
            break
    return tainted


def _own_body(fn: ast.AST):
    """Walk ``fn`` without descending into nested defs (they are separate
    FuncInfos with their own taint scope)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


def _static_names(cg: callgraph.CallGraph, fi: callgraph.FuncInfo) -> Set[str]:
    """Names listed in static_argnames of the def's jit decorator(s)."""
    out: Set[str] = set()
    for d in getattr(fi.node, "decorator_list", []):
        if not isinstance(d, ast.Call):
            continue
        for kw in d.keywords:
            if kw.arg == "static_argnames":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(n.value,
                                                                  str):
                        out.add(n.value)
    return out


def check(repo: Repo, roots: Optional[Sequence[str]] = None) -> List[Finding]:
    cg = callgraph.build(repo)
    hot = cg.reachable(list(roots) if roots is not None else HOT_ROOTS)
    out: List[Finding] = []

    for q, fi in cg.funcs.items():
        in_hot = q in hot
        in_traced = q in cg.traced
        if not (in_hot or in_traced):
            continue
        tainted = taint_names(fi.node)
        static = _static_names(cg, fi)
        for node in _own_body(fi.node):
            if in_hot and isinstance(node, ast.Call):
                d = _text_dotted(node.func)
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item" and not node.args):
                    out.append(Finding(
                        "TC101", fi.module.path, node.lineno,
                        f"`.item()` in hot function {q} — blocking "
                        f"device→host sync"))
                elif d in ("jax.device_get",):
                    out.append(Finding(
                        "TC103", fi.module.path, node.lineno,
                        f"jax.device_get in hot function {q} — every call "
                        f"is a blocking sync; baseline it if designed"))
                elif (d in ("np.asarray", "np.array", "numpy.asarray",
                            "numpy.array") and node.args
                      and expr_tainted(node.args[0], tainted)):
                    out.append(Finding(
                        "TC104", fi.module.path, node.lineno,
                        f"{d} on device value in hot function {q} — "
                        f"implicit d2h copy; use jax.device_get explicitly"))
                elif (isinstance(node.func, ast.Name)
                      and node.func.id in ("int", "float", "bool")
                      and node.args
                      and expr_tainted(node.args[0], tainted)):
                    out.append(Finding(
                        "TC102", fi.module.path, node.lineno,
                        f"{node.func.id}() on device value in hot function "
                        f"{q} — implicit blocking sync"))
            if in_traced and isinstance(node, (ast.If, ast.While)):
                test = node.test
                # exemptions: `is None`, isinstance, static_argnames
                if isinstance(test, ast.Compare) and any(
                        isinstance(op, (ast.Is, ast.IsNot))
                        for op in test.ops):
                    continue
                if (isinstance(test, ast.Call)
                        and isinstance(test.func, ast.Name)
                        and test.func.id in ("isinstance", "hasattr",
                                             "callable")):
                    continue
                names = {n.id for n in ast.walk(test)
                         if isinstance(n, ast.Name)}
                if names & static:
                    continue
                if expr_tainted(test, tainted):
                    kind = "if" if isinstance(node, ast.If) else "while"
                    out.append(Finding(
                        "TC105", fi.module.path, node.lineno,
                        f"Python `{kind}` on traced array value in {q} — "
                        f"use lax.cond/jnp.where (ConcretizationError "
                        f"under jit)"))
    return out
