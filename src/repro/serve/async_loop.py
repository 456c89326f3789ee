"""Async continuous-batching serve loop: dispatch → plan-ahead → commit.

The synchronous ``Scheduler.tick`` serializes host work with the device:
fill slots, block on the decode step, account, repeat — and clients see
tokens only when their request completes. This module restructures the
replica loop around JAX async dispatch so both costs disappear:

- **Pipeline overlap.** ``engine.dispatch_step()`` does all host-side
  planning and *launches* the jitted step; the call returns while the
  device still computes. The loop uses that window for tick N+1's host
  work — admitting late arrivals to the queue and precomputing
  admission costs via ``scheduler.plan_ahead()`` (one prefix-match walk
  per candidate, cached against ``BlockPool.version``) — then blocks in
  ``tick.commit()`` only when the result is actually needed. Host
  planning time hides behind the device step instead of adding to it.

- **Chained decode steps.** Where the engine knows the next step from
  its host state already (every slot decoding, none ending on this
  step by its length), ``engine.dispatch_next(tick)`` launches it
  before this step commits, fed by this step's sampled tokens on the
  device. The device then runs decode steps back to back while the
  host commits, streams and plans the one before; slots hold still
  (no cancel, no admission) until the launched step commits.

- **Per-token streaming.** Every request may carry an ``on_token``
  callback; after each commit the loop emits the tokens that appeared
  since the last tick, in order. Token values are **bit-identical** to
  the synchronous drain: the engine's streams are deterministic per
  request regardless of batch composition (mixed-length bit-exact
  decode + counter-based sampling), so overlap changes *when* tokens
  arrive, never *what* they are — ``tests/test_streaming.py`` enforces
  this across the full engine grid.

- **Cancellation.** ``StreamHandle.cancel()`` (or a callback raising —
  treated as a client disconnect) retires the slot and frees its
  refcounted KV blocks at the next loop boundary; cancels are never
  applied between dispatch and commit, when slot state must not move.

The loop is *driven*, not threaded, by default: ``run_once()`` pumps one
tick, ``wait(handle)`` pumps until a reply is ready — so tests drive it
under a :class:`~repro.serve.clock.VirtualClock` with scripted arrival
traces and zero wall-clock sleeps. ``start()`` runs the same pump on a
daemon thread (event-woken, no polling sleeps) for live replicas, and
``stream()`` is an ``async`` front-end yielding ``(token, logprob)``
pairs for asyncio servers.

Error taxonomy matches the service layer: queue-full and replica aborts
are retryable ``ServiceError``; sheds and client disconnects are the
client's fault (``RequestError``) and must not poison balancer health.
The balancer additionally refuses to retry a request once its first
token has streamed (the client already observed output).
"""
from __future__ import annotations

import asyncio
import threading
from collections import deque

from repro.core.services import RequestError, ServiceError
from repro.serve.engine import Request
from repro.serve.scheduler import Scheduler
from repro.serve.telemetry import phase


class StreamHandle:
    """A submitted request's streaming future.

    ``on_token(token, logprob)`` fires per generated token, in order;
    ``cancel()`` abandons the request at the next loop boundary (the
    reply then carries the tokens generated so far); ``result()`` blocks
    (pumping the loop when it isn't threaded) until the reply dict is
    ready, raising the request's error if it failed.
    """

    def __init__(self, loop: "AsyncServeLoop", req: Request,
                 on_token=None):
        self._loop = loop
        self.request = req
        self.rid = req.rid
        self.on_token = on_token
        self.streamed = 0               # tokens already emitted
        self.cancelled = False
        self.error: Exception | None = None
        self.reply: dict | None = None
        self._done = threading.Event()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def _finish(self) -> None:
        self._done.set()

    def cancel(self) -> None:
        self._loop.cancel(self)

    def result(self) -> dict:
        return self._loop.wait(self)


class AsyncServeLoop:
    """Continuous-batching pump over one Scheduler/ServingEngine pair."""

    def __init__(self, scheduler: Scheduler, *, name: str = "replica",
                 plan_limit: int = 32):
        self.scheduler = scheduler
        self.engine = scheduler.engine
        self.name = name
        self.plan_limit = plan_limit
        self.clock = scheduler.clock
        self._intake: deque[StreamHandle] = deque()
        self._cancels: deque[StreamHandle] = deque()
        self._live: dict[int, StreamHandle] = {}
        # one lock serializes pumping and intake: the engine is not
        # thread-safe, and callbacks fire with the lock held (reentrant
        # so a callback may cancel its own handle)
        self._lock = threading.RLock()
        self._wake = threading.Event()
        self._stopping = threading.Event()
        self._thread: threading.Thread | None = None
        self.metrics = {
            "ticks": 0,                 # committed device steps
            "planned_ahead_ticks": 0,   # ticks that planned >=1 candidate
            "planned": 0,               # total candidates planned in-flight
            # host seconds per phase of run_once (telemetry.phase)
            "cancel_s": 0.0,            # applying cancels
            "fill_s": 0.0,              # intake, admission, sheds
            "dispatch_s": 0.0,          # planning + launching the step
            "dispatch_next_s": 0.0,     # launching the step after it
            "plan_time_s": 0.0,         # host time inside the overlap window
            "commit_wait_s": 0.0,       # device wait + commit bookkeeping
            "account_s": 0.0,           # scheduler stats of the finished
            "emit_s": 0.0,              # streaming tokens, resolving replies
            "outside_s": 0.0,           # the caller's time between calls
        }
        self._left = None               # clock when run_once last returned
        self._ahead = None              # a launched step not yet committed

    # ------------------------------------------------------------ intake
    def submit(self, req: Request, on_token=None) -> StreamHandle:
        """Hand a request to the loop; returns its stream handle."""
        handle = StreamHandle(self, req, on_token)
        with self._lock:
            self._intake.append(handle)
        self._wake.set()
        return handle

    def cancel(self, handle: StreamHandle) -> None:
        with self._lock:
            if not handle.done:
                self._cancels.append(handle)
        self._wake.set()

    def load(self) -> int:
        """Queued + active + not-yet-admitted work, for least-loaded
        balancing."""
        with self._lock:
            return (len(self._intake) + len(self.scheduler.queue)
                    + self.engine.active)

    # ----------------------------------------------------------- pumping
    def _admit(self) -> None:
        """Move intake handles into the scheduler queue. Queue-only (no
        engine-slot mutation), so this is safe inside the plan-ahead
        window too — late arrivals join tick N+1's plan."""
        while self._intake:
            handle = self._intake.popleft()
            if handle.done:             # cancelled before admission
                continue
            if handle.rid in self._live:
                handle.error = ServiceError(
                    f"{self.name}: duplicate rid {handle.rid}")
                handle._finish()
                continue
            if not self.scheduler.submit(handle.request):
                handle.error = ServiceError(f"{self.name}: queue full")
                handle._finish()
                continue
            self._live[handle.rid] = handle

    def _apply_cancels(self) -> None:
        """Retire cancelled requests (frees slots + refcounted blocks).
        Only called at loop boundaries — never between dispatch and
        commit."""
        while self._cancels:
            handle = self._cancels.popleft()
            if handle.done:
                continue
            handle.cancelled = True
            self.scheduler.cancel(handle.rid)
            self._live.pop(handle.rid, None)
            handle.reply = self._reply(handle.request)
            handle._finish()

    def _collect_shed(self) -> None:
        """Sheds (expired deadline / memory pressure) surface on their
        handles as RequestError — the client's SLO lapsed; retrying
        elsewhere would waste another replica's slots."""
        if not self.scheduler.shed_requests:
            return
        keep = []
        for r in self.scheduler.shed_requests:
            handle = self._live.pop(r.rid, None)
            if handle is None:
                keep.append(r)          # a direct scheduler user's shed
                continue
            handle.error = RequestError(
                f"{self.name}: request {r.rid} shed past its deadline")
            handle._finish()
        self.scheduler.shed_requests[:] = keep

    def _reply(self, r: Request) -> dict:
        return {"tokens": list(r.out_tokens),
                "logprobs": list(r.out_logprobs),
                "latency_s": r.latency_s,
                "replica": self.name}

    def _emit(self) -> None:
        """Stream the tokens each live request gained since last tick. A
        callback that raises is a disconnected client: the request is
        cancelled (slot + blocks recycled) and surfaces RequestError."""
        dead = []
        for rid, handle in self._live.items():
            r = handle.request
            n = len(r.out_tokens)
            if handle.on_token is None:
                handle.streamed = n
                continue
            while handle.streamed < n:
                i = handle.streamed
                try:
                    handle.on_token(r.out_tokens[i], r.out_logprobs[i])
                except Exception as e:
                    handle.error = RequestError(
                        f"{self.name}: client disconnected mid-stream "
                        f"after {i} tokens: {e!r}")
                    dead.append(rid)
                    break
                handle.streamed += 1
        for rid in dead:
            handle = self._live.pop(rid)
            self.scheduler.cancel(rid)
            handle._finish()

    def run_once(self) -> bool:
        """One pipelined tick: admit/cancel → fill → dispatch → launch
        the next step where the engine can chain it → (plan-ahead
        window) → commit → account → emit → resolve. A tick that picks
        up a chained step skips cancels and admission: its step is
        already in flight. Returns False when there was nothing to do.

        Every phase runs under the telemetry phase timer: it adds to its
        counter in ``self.metrics``, lands on the engine tracer's
        serve-loop track as a span, and on a profiler trace as a
        ``serve.<phase>`` host event. The plan-window and commit-wait
        phases measure the dispatch/commit overlap directly (host work
        hidden vs. time blocked on the device), and ``outside_s`` the
        caller's time between two calls. Timestamps come from the
        loop's clock, so a VirtualClock-driven pump emits a
        deterministic timeline."""
        eng, m, clock, tr = self.engine, self.metrics, self.clock, \
            self.engine.tracer
        with self._lock:
            # a step the last call launched before its own step committed:
            # slots hold still until it commits (no cancel, no admission)
            ahead, self._ahead = self._ahead, None
            with phase("apply-cancels", clock, m, "cancel_s", tr) as p:
                if ahead is None:
                    self._apply_cancels()
            if self._left is not None:
                m["outside_s"] += p.start - self._left
            with phase("fill", clock, m, "fill_s", tr) as p:
                self._admit()
                if ahead is None:
                    self.scheduler.fill()
                    self._collect_shed()
            self._left = p.end
            if ahead is None and not (eng.active or eng.waiting
                                      or eng._finished_at_admit):
                eng.end_launch_chain()
                return False
            with phase("dispatch", clock, m, "dispatch_s", tr) as p:
                tick = eng.dispatch_step() if ahead is None else ahead
                p.args = {"active": eng.active}
            # the step after this one, launched before this one commits
            # where the engine knows it already; a waiting cancel keeps
            # the next call at the loop boundary
            with phase("dispatch-next", clock, m, "dispatch_next_s",
                       tr) as p:
                if not self._cancels:
                    self._ahead = eng.dispatch_next(tick)
                p.args = {"launched": self._ahead is not None}
            # ---- overlap window: the device step is in flight --------
            with phase("plan-window", clock, m, "plan_time_s", tr) as p:
                self._admit()           # late arrivals reach this plan
                planned = self.scheduler.plan_ahead(self.plan_limit)
                p.args = {"planned": planned}
            # ----------------------------------------------------------
            with phase("commit-wait", clock, m, "commit_wait_s", tr):
                done = tick.commit()
            with phase("account", clock, m, "account_s", tr):
                self.scheduler.account(done)
            m["ticks"] += 1
            m["planned"] += planned
            if planned:
                m["planned_ahead_ticks"] += 1
            with phase("emit", clock, m, "emit_s", tr,
                       args={"finished": len(done)}) as p:
                self._emit()
                for r in done:
                    handle = self._live.pop(r.rid, None)
                    if handle is not None and not handle.done:
                        handle.reply = self._reply(r)
                        handle._finish()
            self._left = p.end
            return True

    def wait(self, handle: StreamHandle) -> dict:
        """Block until the handle resolves — by pumping the loop inline
        when it isn't threaded — then return the reply or raise the
        request's error."""
        if self._thread is not None:
            handle._done.wait()
        else:
            while not handle.done:
                self.run_once()
        if handle.error is not None:
            raise handle.error
        return handle.reply

    def abort(self, error: Exception | None = None) -> int:
        """Fail every in-flight and queued request (replica died / is
        restarting): handles resolve with a retryable ServiceError, and
        scheduler + engine state is torn down so a restart starts clean.
        Returns the number of handles failed."""
        with self._lock:
            if self._ahead is not None:
                ahead, self._ahead = self._ahead, None
                ahead.commit()
            err = error if error is not None else ServiceError(
                f"{self.name}: replica aborted mid-stream")
            handles = list(self._live.values()) + list(self._intake) \
                + list(self._cancels)
            self._live.clear()
            self._intake.clear()
            self._cancels.clear()
            n = 0
            for handle in handles:
                if handle.done:
                    continue
                handle.error = err
                handle._finish()
                n += 1
            for req in list(self.scheduler.queue):
                self.scheduler.cancel(req.rid)
            for r in list(self.engine.slot_req):
                if r is not None:
                    self.engine.cancel(r.rid)
            self.engine._waiting.clear()
            self.engine._finished_at_admit.clear()
            return n

    # ---------------------------------------------------------- threaded
    def start(self) -> None:
        """Run the pump on a daemon thread. Event-woken: the thread
        sleeps only when there is no work and wakes on submit/cancel —
        no polling sleeps."""
        if self._thread is not None:
            return
        self._stopping.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"serve-loop:{self.name}")
        self._thread.start()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stopping.set()
        self._wake.set()
        self._thread.join()
        self._thread = None

    def _run(self) -> None:
        while not self._stopping.is_set():
            self._wake.clear()
            if not self.run_once():
                with self._lock:
                    idle = not (self._intake or self._cancels
                                or self.scheduler.queue
                                or self.engine.active
                                or self.engine.waiting)
                if idle and not self._stopping.is_set():
                    self._wake.wait()

    # ----------------------------------------------------------- asyncio
    async def stream(self, req: Request):
        """Async generator yielding ``(token, logprob)`` pairs as they
        materialize, for asyncio front-ends. Pumps the loop inline when
        it isn't threaded; yields control to the event loop between
        ticks so concurrent streams interleave."""
        buf: deque = deque()
        handle = self.submit(req, lambda t, lp: buf.append((t, lp)))
        try:
            while not handle.done:
                if self._thread is None:
                    self.run_once()
                while buf:
                    yield buf.popleft()
                await asyncio.sleep(0)
            while buf:
                yield buf.popleft()
            if handle.error is not None:
                raise handle.error
        finally:
            if not handle.done:
                handle.cancel()
