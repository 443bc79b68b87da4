"""serving-invariant pass (TC4xx): the engine split's ownership contract.

PR 3 split the engine into host policy (``Scheduler``) × device execution
(``DeviceRunner``); PR 5 added the paged pool with the "decode never
allocates" guarantee.  These are structural invariants the type system
can't express, so the analyzer pins them:

* TC401 — block-table state is mutated only inside ``runner.py`` (the
  device side) — a table write anywhere else can race the allocator's
  host bookkeeping;
* TC402 — no device-memory allocation (``jnp.zeros/…/asarray/stack``,
  ``jax.device_put``, ``init_decode_state``) in serving modules outside
  ``runner.py`` — host policy code must stay array-free so its cost
  model (pure Python) stays honest;
* TC403 — nothing reachable from the decode path calls
  ``BlockAllocator.allocate``/``_take`` or ``init_decode_state`` —
  admission reserves everything up front; decode is read-only on the
  block table;
* TC404 — the ``TTQEngine`` facade keeps its back-compat surface (the
  properties tests/benchmarks/examples consume) and
  ``serving/__init__`` keeps re-exporting the public names;
* TC405 — device placement and mesh construction stay funneled:
  ``jax.device_put`` / ``jax.make_mesh`` / ``jax.sharding.Mesh`` appear
  only under ``parallel/``, in ``launch/mesh.py`` or in
  ``serving/runner.py`` (repo-wide, call or argument position — passing
  ``jax.device_put`` to ``tree.map`` places arrays just the same).
  Scattered placement is how mixed-layout trees and silent resharding
  transfers creep in; the mesh-sharded engine relies on every array
  entering the device through one of these three doors.
* TC406 — no broad exception handlers (bare ``except``,
  ``except Exception``, ``except BaseException``) in serving hot paths.
  A swallowed error in the scheduler/runner/engine loop turns a crash
  into silent token corruption; fault *containment* is the job of the
  designated boundary module ``serving/faults.py`` (exempt by name) and
  of typed handlers (``except MemoryError`` stays legal).
* TC407 — no device dispatch or allocation from coroutine bodies in
  serving modules.  The async front end (``serving/server.py``,
  DESIGN.md §13) runs on the event-loop thread; every engine call
  (``submit``/``step``/``cancel``/…) and every ``jnp.``/``jax.``
  operation must happen on the dedicated worker thread.  An engine call
  inside an ``async def`` either blocks the loop for a whole device
  dispatch or races the worker thread on device state — both are bugs
  the type system can't see.
"""
from __future__ import annotations

import ast
from typing import List, Optional

from . import callgraph
from .core import Finding, Module, Repo

_ALLOC_CALLS = {
    "jnp.zeros", "jnp.ones", "jnp.full", "jnp.empty", "jnp.arange",
    "jnp.asarray", "jnp.array", "jnp.stack", "jnp.concatenate",
    "jnp.zeros_like", "jnp.ones_like", "jax.device_put",
    "jax.numpy.zeros", "jax.numpy.asarray", "jax.numpy.stack",
}
_DECODE_ROOTS = [
    "repro.serving.runner.DeviceRunner.decode_block",
    "repro.models.lm.decode_many",
]
_ALLOCATOR_FNS = {
    "repro.serving.blocks.BlockAllocator.allocate",
    "repro.serving.blocks.BlockAllocator._take",
    "repro.models.lm.init_decode_state",
}

# TC405: placement/mesh primitives and the modules allowed to use them
_PLACEMENT_ATTRS = {"jax.device_put", "jax.make_mesh", "jax.sharding.Mesh"}

# TC407: engine entry points that dispatch device work (or mutate device
# state) — none may be called from a coroutine body in a serving module
_ENGINE_ENTRY = {
    "step", "run_all", "admit", "submit", "cancel", "decode_block",
    "admit_group", "prefill_chunk", "release_slots", "_requantize",
    "place_params", "calibrate", "requantize",
}


def _placement_allowed(path: str) -> bool:
    return ("/parallel/" in path or path.endswith("launch/mesh.py")
            or path.endswith("serving/runner.py"))


# the facade surface consumers (tests/benchmarks/examples) rely on
ENGINE_ATTRS = [
    "decode_params", "qparams", "n_requants", "lowrank_tree",
    "layers_requantized", "layers_skipped", "agg_stats", "stat_count",
    "admits_since_cal", "queue", "slot_req", "finished", "state", "pos",
    "cur_tok", "host_syncs", "allocator", "kv_pool_utilization",
    "prefix_hit_rate", "preemptions", "prefill_tokens",
    "calib_rejections", "quarantine", "requant_rejections", "lane_faults",
    "deadline_expirations", "admission_failures", "degrade_level",
    "submit", "cancel", "admit", "step", "run_all",
    "queue_depth", "queue_rejections", "prefill_chunks",
    "latency_percentiles", "set_stream_callbacks", "telemetry",
]
SERVING_EXPORTS = ["BlockAllocator", "DeviceRunner", "EngineConfig",
                   "Fault", "FaultInjector", "GenResult", "QueueFull",
                   "Request", "RequestFailed", "Scheduler", "TTQEngine",
                   "TTQServer", "VirtualClock"]


def _text(expr: ast.AST) -> Optional[str]:
    parts: List[str] = []
    node = expr
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _is_serving(mod: Module) -> bool:
    return "serving" in mod.path.split("/")


def _walk_own(fn: ast.AsyncFunctionDef):
    """Walk a coroutine's body without descending into nested ``def``s
    (a nested sync function may legitimately run on the worker thread;
    nested coroutines are visited by the module-level walk on their own)."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _touches_block_table(tgt: ast.AST) -> bool:
    for n in ast.walk(tgt):
        if isinstance(n, ast.Constant) and n.value == "block_table":
            return True
        if isinstance(n, ast.Attribute) and n.attr == "block_table":
            return True
    return False


def check(repo: Repo) -> List[Finding]:
    cg = callgraph.build(repo)
    out: List[Finding] = []

    serving_mods = [m for m in repo if _is_serving(m)]
    for mod in serving_mods:
        base = mod.path.rsplit("/", 1)[-1]
        if base in ("runner.py", "blocks.py"):
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                tgts = (node.targets if isinstance(node, ast.Assign)
                        else [node.target])
                for t in tgts:
                    if _touches_block_table(t):
                        out.append(Finding(
                            "TC401", mod.path, node.lineno,
                            f"block-table mutation outside runner.py "
                            f"({base}) — device block tables belong to "
                            f"DeviceRunner"))
            if isinstance(node, ast.Call):
                d = _text(node.func)
                if d in _ALLOC_CALLS:
                    out.append(Finding(
                        "TC402", mod.path, node.lineno,
                        f"device allocation `{d}` in serving module {base} "
                        f"— array staging belongs to DeviceRunner"))
                fi = cg.resolve_func(cg.dotted(mod, node.func))
                if (fi is not None
                        and fi.qualname.endswith(".init_decode_state")):
                    out.append(Finding(
                        "TC402", mod.path, node.lineno,
                        f"init_decode_state call in serving module {base} "
                        f"— decode state belongs to DeviceRunner"))

    # TC403: decode path never allocates pool state
    decode = cg.reachable(_DECODE_ROOTS)
    for q in sorted(decode):
        fi = cg.funcs.get(q)
        if fi is None:
            continue
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            d = cg.dotted(fi.module, node.func, fi.class_name)
            fi2 = cg.resolve_func(d)
            target = fi2.qualname if fi2 is not None else d
            if target in _ALLOCATOR_FNS:
                out.append(Finding(
                    "TC403", fi.module.path, node.lineno,
                    f"{target.split('.')[-1]} called from decode-reachable "
                    f"{q} — decode must never allocate (admission reserves "
                    f"up front)"))
            # self.allocator.allocate(...) textual form
            t = _text(node.func)
            if t and t.endswith("allocator.allocate"):
                out.append(Finding(
                    "TC403", fi.module.path, node.lineno,
                    f"allocator.allocate called from decode-reachable {q} "
                    f"— decode must never allocate"))

    # TC405: placement/mesh primitives only behind the three doors
    for mod in repo:
        if _placement_allowed(mod.path):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Attribute):
                continue
            d = _text(node)
            if d in _PLACEMENT_ATTRS:
                out.append(Finding(
                    "TC405", mod.path, node.lineno,
                    f"`{d}` outside parallel/, launch/mesh.py, "
                    f"serving/runner.py — device placement and mesh "
                    f"construction are funneled (DESIGN.md §10)"))

    # TC406: broad exception handlers stay inside the fault boundary
    _BROAD = {"Exception", "BaseException"}
    for mod in serving_mods:
        if mod.path.endswith("faults.py"):   # the designated fault boundary
            continue
        base = mod.path.rsplit("/", 1)[-1]
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            t = node.type
            names = ([] if t is None
                     else [n for n in (getattr(e, "id", getattr(e, "attr",
                                                                None))
                           for e in (t.elts if isinstance(t, ast.Tuple)
                                     else [t])) if n])
            if t is None or any(n in _BROAD for n in names):
                what = "bare except" if t is None else \
                    f"except {'/'.join(n for n in names if n in _BROAD)}"
                out.append(Finding(
                    "TC406", mod.path, node.lineno,
                    f"{what} in serving module {base} — broad handlers "
                    f"mask corruption in the serving loop; contain faults "
                    f"in serving/faults.py or catch the specific error"))

    # TC407: coroutine bodies in serving modules stay device-free
    for mod in serving_mods:
        base = mod.path.rsplit("/", 1)[-1]
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            for node in _walk_own(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = _text(node.func)
                if d is not None and (d.startswith("jnp.")
                                      or d.startswith("jax.")):
                    out.append(Finding(
                        "TC407", mod.path, node.lineno,
                        f"`{d}` inside coroutine `{fn.name}` ({base}) — "
                        f"device ops run on the engine worker thread, "
                        f"never the event loop"))
                elif (isinstance(node.func, ast.Attribute)
                        and node.func.attr in _ENGINE_ENTRY):
                    out.append(Finding(
                        "TC407", mod.path, node.lineno,
                        f"engine call `.{node.func.attr}(...)` inside "
                        f"coroutine `{fn.name}` ({base}) — engine entry "
                        f"points dispatch device work; hand the command "
                        f"to the worker thread instead"))

    # TC404: facade surface + package re-exports
    eng = cg.classes.get("repro.serving.engine.TTQEngine")
    if eng is not None:
        have = set()
        for node in eng.node.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                have.add(node.name)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        have.add(t.id)
        init = cg.funcs.get("repro.serving.engine.TTQEngine.__init__")
        if init is not None:
            for node in ast.walk(init.node):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if (isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self"):
                            have.add(t.attr)
        for a in ENGINE_ATTRS:
            if a not in have:
                out.append(Finding(
                    "TC404", eng.module.path, eng.node.lineno,
                    f"TTQEngine facade lost back-compat attr `{a}` — "
                    f"consumers (tests/benchmarks/examples) depend on it"))
    pkg = cg.repo.by_name.get("repro.serving")
    if pkg is not None:
        table = cg.imports.get("repro.serving", {})
        for name in SERVING_EXPORTS:
            if name not in table:
                out.append(Finding(
                    "TC404", pkg.path, 1,
                    f"repro.serving no longer re-exports `{name}`"))
    return out
