"""Window arithmetic over what the clients saw: rates, tails, counts.

Every time here is the client's: a token's time is when its stream handed
it to the client coroutine, a request's ``due`` is when it was due to be
sent (open loop) or was sent (closed loop).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional


@dataclasses.dataclass
class Record:
    index: int
    plen: int
    max_new: int
    due: float
    prompt: list = dataclasses.field(default_factory=list)
    sent: Optional[float] = None     # when the client called the server
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    error: str = ""
    done: bool = False               # the stream ended with all its tokens


def pct(xs, q: float) -> float:
    """The ``q``-quantile (0..1) of ``xs`` by linear interpolation between
    the closest ranks (numpy's default); nan for no samples."""
    s = sorted(xs)
    if not s:
        return math.nan
    h = (len(s) - 1) * q
    lo = math.floor(h)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (h - lo)


def summarize(records: List[Record], t0: float, t1: float) -> dict:
    """What the window [t0, t1) holds.

    * ``attempted``: requests due before t1 that were still open at t0 —
      for an open loop, the requests due in the window;
    * ``failed``: those that ended in an error;
    * ``tokens``: tokens that reached a client in the window, of requests
      finished or still in flight;
    * ``itl``: every gap between two consecutive tokens of one stream that
      both arrived in the window;
    * ``ttft``: per request due in the window, first token − due, or
      t1 − due if no first token came by t1 (a stall cannot hide);
    * ``decode_ctx``: the context length each decode token (every token
      but a request's first) of the window was produced at;
    * ``prefill_plens``: prompt lengths of requests whose first token came
      in the window (their prefill ran then).
    """
    out = dict(attempted=0, failed=0, tokens=0, itl=[], ttft=[],
               decode_ctx=[], prefill_plens=[])
    for r in records:
        end = r.times[-1] if (r.done or r.error) and r.times else math.inf
        if r.due >= t1 or end < t0:
            continue
        out["attempted"] += 1
        out["failed"] += bool(r.error)
        if r.due >= t0:
            first = r.times[0] if r.times else math.inf
            out["ttft"].append(min(first, t1) - r.due)
        prev = None
        for i, t in enumerate(r.times):
            if t0 <= t < t1:
                out["tokens"] += 1
                if i == 0:
                    out["prefill_plens"].append(r.plen)
                else:
                    out["decode_ctx"].append(r.plen + i)
                if prev is not None:
                    out["itl"].append(t - prev)
                prev = t
    return out
