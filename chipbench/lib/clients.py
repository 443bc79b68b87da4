"""Clients that drive a started ``TTQServer`` and time what they see.

Open loop: each request is due ``gap_s`` after the one before it and is
sent then, whether or not earlier ones have finished; its time to first
token counts from when it was due, so a request held back by a stall
(or by the server's admission semaphore) is charged the wait.  Closed
loop: each client sends its next request as soon as its last one ends.
At the window's close no new request is sent and the streams still open
are cancelled; a cancelled request keeps the tokens it was served.
"""
from __future__ import annotations

import asyncio
import time

from repro.serving.server import RequestFailed

from .stats import Record

now = time.perf_counter


async def _stream(server, req, rec: Record, on_first=None):
    rec.sent = now()
    try:
        async for tok in server.generate(req.prompt, max_new=req.max_new):
            rec.times.append(now())
            rec.tokens.append(tok)
            if on_first is not None and len(rec.times) == 1:
                on_first()
        rec.done = True
    except RequestFailed as e:
        rec.error = str(e) or "failed"
    finally:
        if on_first is not None:
            on_first()


def _record(req, due: float) -> Record:
    return Record(req.index, len(req.prompt), req.max_new, due,
                  prompt=req.prompt)


async def _cancel(tasks):
    for t in tasks:
        t.cancel()
    for t in tasks:
        try:
            await t
        except asyncio.CancelledError:
            pass


async def open_loop(server, reqs, seconds: float, on_open=None):
    """Send ``reqs`` on their schedule from now for ``seconds``; returns
    (records, t0, t1, lag_s) where ``lag_s`` is the largest delay between
    a request's due time and its send (the generator's own lateness)."""
    t0 = now()
    if on_open is not None:
        on_open()
    t1 = t0 + seconds
    recs, tasks, due = [], [], t0
    for req in reqs:
        if due >= t1:
            break
        rec = _record(req, due)
        recs.append(rec)
        delay = due - now()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(_stream(server, req, rec)))
        due += req.gap_s
    if now() < t1:
        await asyncio.sleep(t1 - now())
    lag = max((r.sent - r.due for r in recs if r.sent is not None),
              default=0.0)
    await _cancel(tasks)
    return recs, t0, t1, lag


async def closed_loop(server, reqs, clients: int, seconds: float,
                      on_open=None, stagger_s: float = 0.0):
    """``clients`` clients each send their next request of ``reqs`` (in
    order, until the pool runs out) as soon as the last one ends; client c
    starts ``c * stagger_s`` after the first.  The window opens once every
    client has its first token and lasts ``seconds``.  Returns (records,
    t0, t1, fill_s)."""
    recs = []
    state = {"next": 0, "stop": False}
    first = [asyncio.Event() for _ in range(clients)]

    async def client(c):
        await asyncio.sleep(c * stagger_s)
        while not state["stop"] and state["next"] < len(reqs):
            req = reqs[state["next"]]
            state["next"] += 1
            rec = _record(req, now())
            recs.append(rec)
            await _stream(server, req, rec, on_first=first[c].set)

    t_fill = now()
    tasks = [asyncio.create_task(client(c)) for c in range(clients)]
    for ev in first:
        await ev.wait()
    t0 = now()
    if on_open is not None:
        on_open()
    await asyncio.sleep(seconds)
    t1 = now()
    state["stop"] = True
    await _cancel(tasks)
    return recs, t0, t1, t0 - t_fill
