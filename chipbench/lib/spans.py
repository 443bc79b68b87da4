"""Counters and host spans around the calls into each layer, from outside.

The harness wraps bound methods of the live instances (the program's
classes stay untouched): every wrapped call is counted, and in a traced
run it is also a ``jax.profiler.TraceAnnotation`` named ``<layer>.<call>``,
so the profiler's trace shows what the host was doing in each device gap.
"""
from __future__ import annotations

import collections
import functools

# (attribute path from the engine, span name); the layers of PERF.md
CALLS = (
    ("step", "engine.step"),
    ("admit", "engine.admit"),
    ("runner.decode_block", "runner.decode_block"),
    ("runner.admit_group", "runner.admit_group"),
    ("runner.prefill_chunk", "runner.prefill_chunk"),
    ("qmodel.requantize", "requant.requantize"),
)


class Counters:
    """Call counts per span name, plus the decode blocks' steps and tokens
    (``decode_steps`` counts K per call: K=1 when the program's
    degradation ladder swaps in its small decode program)."""

    def __init__(self):
        self.calls = collections.Counter()
        self.decode_steps = 0
        self.decode_slot_steps = 0
        self.decode_tokens = 0
        self.group_sizes = collections.Counter()   # admission groups by size

    def snapshot(self) -> dict:
        d = dict(self.calls)
        d.update(decode_steps=self.decode_steps,
                 decode_slot_steps=self.decode_slot_steps,
                 decode_tokens=self.decode_tokens)
        d.update({f"groups_of_{n}": c for n, c in self.group_sizes.items()})
        return d


def _owner(eng, path: str):
    obj = eng
    *parts, attr = path.split(".")
    for p in parts:
        obj = getattr(obj, p)
    return obj, attr


def install(eng, counters: Counters, traced: bool):
    """Wrap the engine's layer calls on the instances; returns a function
    that removes the wrappers."""
    import jax

    undo = []
    for path, name in CALLS:
        obj, attr = _owner(eng, path)
        fn = getattr(obj, attr)

        def wrapped(*a, _fn=fn, _name=name, **kw):
            counters.calls[_name] += 1
            if traced:
                with jax.profiler.TraceAnnotation(_name):
                    out = _fn(*a, **kw)
            else:
                out = _fn(*a, **kw)
            if _name == "runner.decode_block":
                _count_decode(eng, counters, out, kw.get("small_chunk"))
            elif _name == "runner.admit_group":
                counters.group_sizes[len(a[1].requests)] += 1
            return out

        setattr(obj, attr, functools.wraps(fn)(wrapped))
        undo.append((obj, attr))
    return lambda: [delattr(o, a) for o, a in undo]


def _count_decode(eng, counters: Counters, out, small_chunk):
    import numpy as np

    K = 1 if small_chunk else max(1, eng.ecfg.decode_chunk)
    counters.decode_steps += K
    counters.decode_slot_steps += K * eng.ecfg.max_slots
    counters.decode_tokens += int(np.asarray(out[1]).sum())
