"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

Device planes are those named ``/device:TPU:<n>``.  On each, the line of
XLA ops gives the device's busy intervals and each op's time; the line of
XLA modules gives each program execution.  The host plane's threads carry
the harness's ``TraceAnnotation`` spans (``engine.step``,
``runner.decode_block``, ...), which label the device's idle gaps, and the
``chipbench.window`` span that bounds the measured window.

An op event's name is its HLO instruction (``%ttq_gemm.28 = f32[16,9216]
custom-call(...)``); its short name is the instruction's name without the
``%`` and the numeric suffix.  A kernel is found by the stable name of its
jitted entry (``ttq_gemm``, ``ttq_quantize``,
``ttq_paged_decode_attention``) inside that instruction name
(``vmap_jit_ttq_quantize__.1`` is ``ttq_quantize``).  Control-flow ops
(``while``, ``conditional``, ``call``) enclose the ops of their bodies on
the same line, so they count towards busy time only through that union.
Module names are not stable (``jit__unknown(<hash>)``), so a program is
found by a kernel that runs inside its executions.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNELS = ("ttq_gemm", "ttq_quantize", "ttq_paged_decode_attention")
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Event:
    name: str
    start: float            # ns
    end: float              # ns


def op_name(name: str) -> str:
    """``%ttq_gemm.28 = f32[...] custom-call(...)`` → ``ttq_gemm.28``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def short_name(name: str) -> str:
    """The op's kernel entry if it is a kernel, else its instruction name
    without the numeric suffix (``fusion.94`` → ``fusion``)."""
    op = op_name(name)
    for k in KERNELS:
        if k in op:
            return k
    return re.sub(r"[.\d]+$", "", op) or op


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]             # ns, host clock of the trace
    devices: Dict[str, List[Event]]         # plane → op events in window
    modules: Dict[str, List[Event]]         # plane → module executions
    host: List[Event]                       # harness spans, all threads

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def _events(line):
    return [Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str, span_names=()) -> Trace:
    """Read an xplane file; keep the op events that overlap the window
    span, clipped to it, the module executions wholly inside it, and the
    host spans named in ``span_names``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices, modules = [], {}, {}
    keep = set(span_names) | {WINDOW_SPAN}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host += [e for e in _events(line) if e.name in keep]
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if spans:
        window = (spans[0].start, spans[0].end)
    else:
        allev = [e for evs in devices.values() for e in evs]
        window = (min(e.start for e in allev), max(e.end for e in allev)) \
            if allev else (0.0, 0.0)
    lo, hi = window

    def clip(evs):
        return [dataclasses.replace(e, start=max(e.start, lo),
                                    end=min(e.end, hi))
                for e in evs if e.end > lo and e.start < hi]
    return Trace(window, {k: clip(v) for k, v in devices.items()},
                 {k: [e for e in v if e.start >= lo and e.end <= hi]
                  for k, v in modules.items()},
                 [e for e in host if e.name != WINDOW_SPAN])


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace) -> float:
    """Seconds in which some op ran, averaged over the device planes."""
    if not tr.devices:
        return 0.0
    tot = 0.0
    for evs in tr.devices.values():
        tot += sum(e - s for s, e in union((e.start, e.end) for e in evs))
    return tot / len(tr.devices) * 1e-9


def idle_share(tr: Trace):
    """1 − busy / window, as a percentage; None without device events."""
    if not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(tr) / tr.window_s)


def kernel_time(tr: Trace, kernel: str) -> Tuple[float, int]:
    """(seconds, events) of the ops of ``kernel``, over all devices."""
    secs, n = 0.0, 0
    for evs in tr.devices.values():
        for e in evs:
            if kernel in op_name(e.name):
                secs += (e.end - e.start) * 1e-9
                n += 1
    return secs, n


def program_time(tr: Trace, kernel: str) -> Tuple[float, int]:
    """(seconds, executions) of the programs in whose executions an op of
    ``kernel`` runs (``ttq_paged_decode_attention``: the decode program)."""
    secs, n = 0.0, 0
    for plane, mods in tr.modules.items():
        starts = sorted(e.start for e in tr.devices.get(plane, ())
                        if kernel in op_name(e.name))
        for m in mods:
            i = bisect.bisect_left(starts, m.start)
            if i < len(starts) and starts[i] < m.end:
                secs += (m.end - m.start) * 1e-9
                n += 1
    return secs, n


def top_ops(tr: Trace, n: int = 10):
    """[[op, seconds], ...]: device time by short op name (kernels by their
    entry), control-flow containers left out, largest first, averaged over
    the device planes."""
    tot = collections.Counter()
    for evs in tr.devices.values():
        for e in evs:
            name = short_name(e.name)
            if name not in CONTAINERS:
                tot[name] += (e.end - e.start) * 1e-9
    k = max(len(tr.devices), 1)
    return [[name, secs / k] for name, secs in tot.most_common(n)]


def idle_gaps(tr: Trace, n: int = 10):
    """[[label, seconds], ...]: the longest idle gaps of the first device
    in the window, each labelled by the innermost harness span open on the
    host at the gap's midpoint (``host.idle`` when none was)."""
    if not tr.devices:
        return []
    evs = tr.devices[sorted(tr.devices)[0]]
    busy = union((e.start, e.end) for e in evs)
    lo, hi = tr.window
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        open_ = [h for h in tr.host if h.start <= mid < h.end]
        label = (min(open_, key=lambda h: h.end - h.start).name
                 if open_ else "host.idle")
        out.append([label, (e - s) * 1e-9])
    return out
