"""The one traffic generator: a mix's parameters + a seed → requests.

A mix (``traffic/<name>.json``) is data only.  It names a loop, an
arrival law (open loop), two length laws, and optionally shared prefixes:

    {"loop": "closed", "clients": 8,                  # or
     "loop": "open", "arrivals": {"law": "poisson", "rate_per_s": 1.2},
     "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                "min": 32, "max": 768},
     "output": {"dist": "uniform", "min": 16, "max": 64},
     "pool": 60, "order": "fixed"}

Arrival laws (``arrivals.law``):

* ``poisson``: exponential gaps at ``rate_per_s``;
* ``onoff``: bursts, Poisson at ``rate_per_s`` for ``on_s`` seconds, then
  ``off_s`` seconds with no arrival (mean rate × on / (on + off));
* ``table``: the rows of ``traffic/<file>`` (CSV, header ``gap_s,prompt,
  output``), one request each, in order: any recorded trace, as data.

Length laws (``prompt``, ``output``): ``uniform`` and ``lognormal`` clipped
to [min, max], ``fixed`` (``value``), and ``mixture`` (``parts``: laws each
with a ``weight``).  ``prefix`` (``{"tokens": 1536, "count": 4}``) starts
every prompt with one of ``count`` shared system prompts of ``tokens``
tokens, request i taking prefix i mod count; the prompt law then sizes
what follows it.

The sizes of the ``pool`` requests and their gaps are fixed quantiles of
the laws, the same for every seed.  ``order`` says what the seed does with
them: ``fixed`` (the seed draws only the tokens: every seed offers the same
arrivals, sizes and order) or ``seeded`` (the default: the seed also
shuffles the order).  An open-loop pool holds the window's requests
(rate × seconds), so each window sends the whole pool; no prompt is sent
twice.
"""
from __future__ import annotations

import csv
import dataclasses
import json
import os
import statistics

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    prompt: list            # token ids
    max_new: int
    gap_s: float = 0.0      # open loop: wait after the previous arrival


def load(path: str) -> dict:
    """The mix in ``path``; a ``table`` law's rows are read from the file
    it names beside it."""
    with open(path) as f:
        mix = json.load(f)
    arr = mix.get("arrivals", {})
    if arr.get("law") == "table":
        with open(os.path.join(os.path.dirname(path), arr["file"])) as f:
            rows = [(float(r["gap_s"]), int(r["prompt"]), int(r["output"]))
                    for r in csv.DictReader(f)]
        mix = dict(mix, arrivals=dict(arr, rows=rows), pool=len(rows))
    return mix


def _quantiles(law: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-points of ``n`` equal slices of the law,
    rounded and clipped to [min, max]."""
    kind = law["dist"]
    if kind == "mixture":
        w = np.asarray([float(p["weight"]) for p in law["parts"]])
        k = np.floor(n * np.cumsum(w) / w.sum() + 0.5).astype(int)
        counts = np.diff(np.concatenate([[0], k]))
        return np.concatenate([_quantiles(p, int(c))
                               for p, c in zip(law["parts"], counts)])
    if kind == "fixed":
        return np.full(n, int(law["value"]), np.int64)
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(law["min"]), int(law["max"])
    if kind == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    elif kind == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(v) for v in u])
        x = float(law["median"]) * np.exp(float(law["sigma"]) * z)
    else:
        raise ValueError(f"unknown length law {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _gaps(arr: dict, n: int) -> np.ndarray:
    """Open-loop gaps before each of ``n`` arrivals (seed-free)."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / float(arr["rate_per_s"])
    gaps = gaps[np.random.default_rng(1).permutation(n)]
    if arr["law"] == "onoff":
        per_on = max(1, int(round(float(arr["rate_per_s"])
                                  * float(arr["on_s"]))))
        gaps[per_on::per_on] += float(arr["off_s"])
    elif arr["law"] != "poisson":
        raise ValueError(f"unknown arrival law {arr['law']!r}")
    return gaps


def sizes(mix: dict) -> list:
    """The pool's (prompt_len, max_new, gap_s) triples, seed-free, in the
    pool's own order.  ``prompt_len`` counts a shared prefix.  The output
    quantiles are paired with the prompt quantiles by a fixed permutation
    so the two lengths are independent."""
    arr = mix.get("arrivals", {})
    if arr.get("law") == "table":
        return [(p + _prefix_len(mix), o, g) for g, p, o in arr["rows"]]
    n = int(mix["pool"])
    plen = _quantiles(mix["prompt"], n) + _prefix_len(mix)
    onew = _quantiles(mix["output"], n)[
        np.random.default_rng(0).permutation(n)]
    gaps = _gaps(arr, n) if mix["loop"] == "open" else np.zeros(n)
    return list(zip(plen.tolist(), onew.tolist(), gaps.tolist()))


def _prefix_len(mix: dict) -> int:
    return int(mix["prefix"]["tokens"]) if "prefix" in mix else 0


def order(mix: dict, n: int, rng) -> np.ndarray:
    """The order in which the pool is sent: a table's own, a fixed
    permutation (``fixed``), or one drawn from ``rng`` (``seeded``)."""
    if mix.get("arrivals", {}).get("law") == "table":
        return np.arange(n)
    how = mix.get("order", "seeded")
    if how == "fixed":
        return np.random.default_rng(2).permutation(n)
    if how == "seeded":
        return rng.permutation(n)
    raise ValueError(f"unknown order {how!r}")


def requests(mix: dict, seed: int, vocab: int) -> list:
    """The pool's requests in the mix's order, prompt tokens (and shared
    prefixes) drawn from [1, vocab) by the seed."""
    rng = np.random.default_rng(seed)
    pool = sizes(mix)
    idx = order(mix, len(pool), rng)
    pre = mix.get("prefix")
    heads = [rng.integers(1, vocab, size=int(pre["tokens"])).tolist()
             for _ in range(int(pre["count"]))] if pre else [[]]
    out = []
    for i, j in enumerate(idx):
        plen, max_new, gap = pool[j]
        head = heads[i % len(heads)]
        toks = head + rng.integers(1, vocab, size=plen - len(head)).tolist()
        out.append(Request(i, toks, int(max_new), float(gap)))
    return out


def bounds(mix: dict) -> tuple:
    """(shortest prompt, longest prompt, longest prompt + output) of the
    pool."""
    pool = sizes(mix)
    return (min(p for p, _, _ in pool), max(p for p, _, _ in pool),
            max(p + o for p, o, _ in pool))


def prefill_shapes(mix: dict, buckets, chunk: int) -> dict:
    """What admission can dispatch for this mix: ``{"buckets": [...],
    "chunk_starts": [...]}`` — the prompt buckets of the pool's prompts no
    longer than ``chunk`` (or all of them without chunking) and the chunk
    offsets of longer prompts."""
    lo, hi, _ = bounds(mix)
    top = min(hi, chunk) if chunk else hi
    used = sorted({next(b for b in buckets if b >= n)
                   for n in range(lo, top + 1) if n <= max(buckets)})
    starts = list(range(0, hi, chunk)) if chunk and hi > chunk else []
    return {"buckets": used, "chunk_starts": starts}
