"""Telemetry — the spans and counters the serving loop records about itself.

One :class:`Telemetry` per engine (``TTQEngine.telemetry``), shared by its
scheduler, runner, quant model and the ``TTQServer`` that drives it.  Each
layer opens a span where its work happens and counts events at the same
boundary, so ratios come from where the work is done:

* ``span(name, **meta)`` accumulates, on ``time.perf_counter``, the call
  count, total seconds and self seconds (total minus the child spans opened
  inside it) per name.  While a profiler session records, it is also a
  ``jax.profiler.TraceAnnotation`` of the same name — on the profiler's
  clock, the timeline of the device's op lines, where it labels what the
  host was doing in each device gap.  ``meta`` (request ids) is for
  per-request spans only: it goes to the annotation, not to the sums.
* ``count(name, n)`` adds to a counter.

All names are fixed here (``SPANS``, ``COUNTERS``); an unknown name raises.
One thread writes: the one that drives the engine.  ``snapshot()`` may be
called from any other thread: it copies the cumulative values (a reader
takes differences over its window), and each update it can see is whole
— a span's count and times, or a first token's four sums, are published
in one store, so no lock is taken on the hot path.

There is no switch: a span costs about a microsecond of host time, and a
serving round holds about a dozen (PERF.md gives the measured cost).
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

_now = time.perf_counter
_profiling = TraceAnnotation.is_enabled

# layer boundaries (PERF.md §3 lists the metric each one is for)
SPANS = (
    "server.drain",     # TTQServer: commands handed to the engine
    "server.park",      # TTQServer: worker waiting for work
    "engine.round",     # TTQEngine.step
    "sched.plan",       # deadlines, admission planning, chunk planning
    "sched.record",     # a decode block folded into requests (emit callbacks)
    "sched.release",    # freed slots deactivated on the device
    "runner.prefill",   # one admission group's prefill dispatch
    "runner.chunk",     # one chunked-prefill dispatch
    "runner.decode",    # one fused decode dispatch
    "runner.sync",      # a blocking device→host read of tokens
    "quant.calibrate",  # prefill statistics folded into the session
    "requant",          # one requantization, as the engine calls it
    "requant.run",      # the fused plan's family programs dispatched
    "requant.health",   # the health gate's host read of the candidate tree
)
COUNTERS = (
    "rounds",               # engine rounds
    "rounds_at_rung_0", "rounds_at_rung_1",   # degradation-ladder level
    "rounds_at_rung_2", "rounds_at_rung_3",   # at each round
    "decode_dispatches",    # fused decode dispatches
    "decode_steps",         # decode steps they ran (K of the program chosen)
    "decode_slot_steps",    # steps × slots
    "decode_tokens",        # decode tokens landed in requests
    "attn_pages_live",      # paged KV: pages the Pallas attention reads
    "attn_pages_table",     # paged KV: steps × slots × block-table width
    "host_syncs",           # blocking device→host transfers
    "first_tokens",         # requests that emitted their first token
    "intake_wait_s",        # Σ submit − receipt (server intake)
    "queue_wait_s",         # Σ slot claimed − submit (scheduler queue)
    "ingest_s",             # Σ first token − slot claimed (prefill, chunks)
)


def rid_meta(rids) -> str:
    """Request ids as a span's metadata: space-separated, because the
    profiler splits a value at commas."""
    return " ".join(map(str, rids))


class _Span:
    """A span name's context manager.  The state of each entry (start,
    children's seconds) lives on the telemetry's stack of open spans, so
    one instance per name serves every call, nested ones included."""
    __slots__ = ("open", "spans", "name", "ann")

    def __init__(self, tm: "Telemetry", name: str, ann=None):
        self.open, self.spans, self.name, self.ann = \
            tm._open, tm._spans, name, ann

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        self.open.append([_now(), 0.0])
        return self

    def __exit__(self, *exc):
        open_ = self.open
        t0, child = open_.pop()
        dt = _now() - t0
        if open_:
            open_[-1][1] += dt
        spans, name = self.spans, self.name
        n, total, self_ = spans[name]
        # one store: a snapshot sees the three values of a call or none
        spans[name] = (n + 1, total + dt, self_ + dt - child)
        if self.ann is not None:
            self.ann.__exit__(*exc)


class Telemetry:
    """Cumulative span times and counters of one engine (module docstring)."""

    def __init__(self):
        self._spans = {s: (0, 0.0, 0.0) for s in SPANS}  # n, total, self
        self._counts = {c: 0.0 if c.endswith("_s") else 0 for c in COUNTERS}
        self._open: list = []            # [start, children's s] per open span
        self._plain = {s: _Span(self, s) for s in SPANS}

    def span(self, name: str, **meta) -> _Span:
        plain = self._plain[name]        # KeyError for an unknown name
        if not _profiling():             # an annotation records nothing then
            return plain
        return _Span(self, name, TraceAnnotation(name, **meta))

    def count(self, name: str, n=1):
        c = self._counts
        c[name] = c[name] + n            # KeyError for an unknown name

    def first_token(self, recv_t: float, submit_t: float, admit_t: float,
                    first_t: float):
        """A request's first token: its receipt-to-first-token time split
        into intake, queue and ingestion, published in one store."""
        c = self._counts
        c.update(first_tokens=c["first_tokens"] + 1,
                 intake_wait_s=c["intake_wait_s"] + submit_t - recv_t,
                 queue_wait_s=c["queue_wait_s"] + admit_t - submit_t,
                 ingest_s=c["ingest_s"] + first_t - admit_t)

    def get(self, key: str):
        """One cumulative value: a counter, or ``<span>.n`` /
        ``<span>.total_s`` / ``<span>.self_s``."""
        if key in self._counts:
            return self._counts[key]
        name, field = key.rsplit(".", 1)
        return self._spans[name][("n", "total_s", "self_s").index(field)]

    def snapshot(self) -> dict:
        spans, out = dict(self._spans), dict(self._counts)
        for name, vals in spans.items():
            out.update(zip((f"{name}.n", f"{name}.total_s",
                            f"{name}.self_s"), vals))
        return out
