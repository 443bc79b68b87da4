"""TTQServer — asyncio streaming front end over :class:`TTQEngine`.

Turns the batch-driven engine into a live service (DESIGN.md §13): clients
``await server.generate(...)`` and receive tokens as the engine emits them,
instead of waiting for ``run_all`` to return.

Threading contract (tracecheck TC407): the engine is single-threaded device
code — every engine call (``submit``, ``step``, ``cancel``) happens on ONE
dedicated worker thread that this server owns.  The asyncio side only
touches queues, futures and semaphores:

* **submit** — a coroutine enqueues a command and awaits a future; the
  worker performs the actual ``engine.submit`` and resolves the future with
  the rid (or the typed rejection).  The request's receipt is stamped on
  the engine clock before backpressure, so the engine's telemetry tells
  the wait for the worker apart from the wait in its queue.
* **stream** — the engine's ``on_token`` / ``on_finish`` callbacks (fired
  on the worker thread inside ``step``) forward events into the consumer's
  ``asyncio.Queue`` via ``loop.call_soon_threadsafe`` — the one documented
  thread-safe entry point into a running event loop.
* **backpressure** — an ``asyncio.Semaphore`` sized to the engine's
  ``max_queue`` (held from submit to completion) makes coroutines *await*
  at capacity instead of seeing :class:`QueueFull`; the engine-level bound
  stays armed underneath as the hard stop for non-server submitters.
* **disconnect** — a consumer that abandons ``generate`` (task cancelled,
  generator closed) triggers ``cancel(rid)`` on the worker thread; the
  scheduler releases the slot and any partially chunk-ingested blocks
  immediately (mid-prefill cancellation, DESIGN.md §13).

Fault-retried lanes (DESIGN.md §12) restart their stream from scratch —
``on_token`` re-emits from the first token; consumers that need exactly-
once delivery should key on (rid, index).
"""
from __future__ import annotations

import asyncio
import queue as _queue
import threading
from typing import Optional

from .scheduler import GenResult, QueueFull  # noqa: F401  (re-export)


class RequestFailed(RuntimeError):
    """A streamed request landed with a terminal error (deadline, lane
    fault past the retry budget, admission retries exhausted).  Carries the
    partial :class:`GenResult` as ``.result``."""

    def __init__(self, rid: int, result: GenResult):
        super().__init__(f"request {rid} failed: {result.error}")
        self.rid = rid
        self.result = result


class TTQServer:
    """Async streaming wrapper over one :class:`TTQEngine`.

    Usage::

        async with TTQServer(engine) as server:
            async for tok in server.generate(prompt, max_new=32):
                ...

    The server owns the engine for its lifetime: it installs the streaming
    callbacks and drives ``engine.step()`` from its worker thread whenever
    work is pending.  ``stop()`` (or leaving the ``async with``) drains
    in-flight work, then parks the worker.
    """

    def __init__(self, engine, max_concurrent: int = 0,
                 poll_s: float = 0.005):
        self.engine = engine
        # hold-to-completion semaphore: never lets more requests coexist
        # than the engine queue bound admits, so server submits cannot
        # bounce off QueueFull
        self._limit = max_concurrent or getattr(engine.ecfg, "max_queue", 0) \
            or 16
        self.poll_s = poll_s
        self.error: Optional[BaseException] = None   # worker crash, if any
        self._running = False
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._sem: Optional[asyncio.Semaphore] = None
        self._cmds: _queue.Queue = _queue.Queue()
        self._streams: dict = {}        # rid → consumer asyncio.Queue
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._wake = threading.Event()

    # ------------------------------------------------------------ lifecycle

    async def start(self):
        if self._running:
            raise RuntimeError("server already started")
        self._loop = asyncio.get_running_loop()
        self._sem = asyncio.Semaphore(self._limit)
        self._stop_evt.clear()
        self.error = None
        self._thread = threading.Thread(target=self._run, name="ttq-engine",
                                        daemon=True)
        self._running = True
        self._thread.start()

    async def stop(self):
        """Drain in-flight work, then stop the worker thread."""
        if not self._running:
            return
        self._stop_evt.set()
        self._wake.set()
        await self._loop.run_in_executor(None, self._thread.join)
        self._running = False

    async def __aenter__(self):
        await self.start()
        return self

    async def __aexit__(self, *exc):
        await self.stop()

    # -------------------------------------------------------------- serving

    async def generate(self, prompt, max_new: int = 16, priority: int = 0,
                       deadline_s=None):
        """Async generator of tokens, yielded as the engine emits them.

        Awaits at the server's concurrency bound (backpressure) before
        submitting.  Abandoning the generator cancels the request on the
        engine — slot and partially written KV blocks free immediately.
        Raises :class:`RequestFailed` if the request lands with a terminal
        error; a cancellation just ends the stream."""
        rid, q, done = None, None, False
        recv_t = self.engine._clock()    # receipt, before any backpressure
        await self._acquire()
        try:
            rid, q = await self._open(prompt, max_new, priority, deadline_s,
                                      recv_t)
            while True:
                ev = await q.get()
                if isinstance(ev, GenResult):
                    done = True
                    if ev.error:
                        raise RequestFailed(rid, ev)
                    return
                yield ev
        finally:
            self._close(rid, done)

    async def complete(self, prompt, max_new: int = 16, priority: int = 0,
                       deadline_s=None) -> GenResult:
        """Await a whole generation; returns its :class:`GenResult` (error
        results return rather than raise — inspect ``.error``)."""
        rid, done = None, False
        recv_t = self.engine._clock()    # receipt, before any backpressure
        await self._acquire()
        try:
            rid, q = await self._open(prompt, max_new, priority, deadline_s,
                                      recv_t)
            while True:
                ev = await q.get()
                if isinstance(ev, GenResult):
                    done = True
                    return ev
        finally:
            self._close(rid, done)

    # ----------------------------------------------------- stream plumbing

    async def _acquire(self):
        if not self._running:
            raise RuntimeError("server not started")
        await self._sem.acquire()

    async def _open(self, prompt, max_new, priority, deadline_s, recv_t):
        """Hand the submit to the worker; await the rid."""
        fut = self._loop.create_future()
        q: asyncio.Queue = asyncio.Queue()
        self._cmds.put(("submit", list(prompt),
                        dict(max_new=max_new, priority=priority,
                             deadline_s=deadline_s, recv_t=recv_t), fut, q))
        self._wake.set()
        return await fut, q

    def _close(self, rid, done: bool):
        """Stream teardown: cancel on the worker if the consumer left
        early, release the admission slot either way."""
        if rid is not None and not done:
            self._cmds.put(("cancel", rid))
            self._wake.set()
        self._sem.release()

    # -------------------------------------------- worker thread (TC407 side)

    def _run(self):
        """The engine-driving loop: drain commands, step while work is
        pending, park on the wake event otherwise.  The ONLY thread that
        touches the engine after ``start()``."""
        eng = self.engine
        tm = eng.telemetry
        eng.set_stream_callbacks(self._on_token, self._on_finish)
        try:
            while True:
                with tm.span("server.drain"):
                    self._drain_cmds()
                sched = eng.scheduler
                if sched.has_work() or sched.has_deferred_work():
                    eng.step()
                elif self._stop_evt.is_set():
                    break
                else:
                    with tm.span("server.park"):
                        self._wake.wait(self.poll_s)
                    self._wake.clear()
        except BaseException as e:   # tracecheck: ok[TC406] worker crash
            #   boundary: land the failure in every open stream instead of
            #   killing a daemon thread silently
            self._crash(e)
        finally:
            eng.set_stream_callbacks(None, None)

    def _drain_cmds(self):
        while True:
            try:
                cmd = self._cmds.get_nowait()
            except _queue.Empty:
                return
            if cmd[0] == "submit":
                _, prompt, kw, fut, q = cmd
                try:
                    rid = self.engine.submit(prompt, **kw)
                except (QueueFull, ValueError) as e:
                    self._call_soon(self._resolve, fut, None, e)
                    continue
                self._streams[rid] = q
                self._call_soon(self._resolve, fut, rid, None)
            elif cmd[0] == "cancel":
                self.engine.cancel(cmd[1])

    def _on_token(self, rid, tok, t):
        q = self._streams.get(rid)
        if q is not None:
            self._call_soon(q.put_nowait, int(tok))

    def _on_finish(self, rid, req):
        q = self._streams.pop(rid, None)
        if q is not None:
            res = GenResult(req.out,
                            unfinished=req.cancelled or bool(req.error),
                            cancelled=req.cancelled, error=req.error)
            self._call_soon(q.put_nowait, res)

    def _crash(self, e: BaseException):
        self.error = e
        for rid in list(self._streams):
            q = self._streams.pop(rid, None)
            if q is not None:
                res = GenResult((), unfinished=True,
                                error=f"engine worker crashed: {e!r}")
                self._call_soon(q.put_nowait, res)

    def _call_soon(self, fn, *args):
        try:
            self._loop.call_soon_threadsafe(fn, *args)
        except RuntimeError:        # loop already closed (shutdown race)
            pass

    def _resolve(self, fut, val, err):
        if fut.done():              # consumer gave up while we submitted
            if err is None and val is not None:
                # the submit won the race — don't orphan a running request
                self._streams.pop(val, None)
                self._cmds.put(("cancel", val))
                self._wake.set()
            return
        if err is not None:
            fut.set_exception(err)
        else:
            fut.set_result(val)
