"""Serve-loop telemetry: span tracing + a unified metrics registry.

The paper's headline claim is a latency budget ("a normal CV in under
700 ms for a sequential flow of requests"); before this module the
reproduction could only *state* latencies, through counters scattered
across ``engine.metrics``, ``pool.stats()``, ``SchedulerStats`` and
``balancer.stats`` — it could not show *where* a request's time went or
whether the async loop's plan window actually overlapped device
compute. This module is the measurement layer under every serving PR:

* :class:`phase` — the timer every host phase of the serve loop and
  the engine runs under (a tick's fill/dispatch/plan/commit/emit, the
  engine's launch, device wait and admission). Always on: two reads of
  the caller's clock add the phase's time to a counter in the caller's
  metrics dict, the same reads stamp the phase's span in the
  :class:`Tracer` when one records, and the phase runs inside a
  ``jax.profiler.TraceAnnotation`` named ``serve.<phase>``, so under a
  profiler trace it sits on the host plane on the device's clock.
* :class:`Tracer` — a clock-injectable event recorder. Components emit
  **spans** (named intervals: a request's queued/prefill/decode phases,
  the phases above) and **instants** (admit, park, preempt,
  copy-on-write, shed, cancel) into a bounded ring buffer;
  :meth:`Tracer.chrome_trace` renders the buffer as Chrome trace-event
  JSON that Perfetto (https://ui.perfetto.dev) loads directly —
  requests as one named track each, the serve loop's tick phases and
  the engine's as two more, pool occupancy as a counter track. The
  clock is injectable, so traces recorded under a
  :class:`~repro.serve.clock.VirtualClock` are **deterministic**: the
  same scripted workload emits byte-identical JSON, which is what lets
  tests assert on traces at all.
* :class:`NoopTracer` — the default everywhere. Every emitter is an
  empty method and every call site is also guarded on ``.enabled``, so
  an untraced engine pays a handful of no-op attribute checks per tick
  (< 0.5 % of a step; ``bench_serving`` gates it) and the hot path
  allocates nothing.
* :class:`MetricsRegistry` — Prometheus text exposition
  (:meth:`MetricsRegistry.prometheus_text`) of the existing stats
  dicts (``engine.metrics``, ``pool.stats()``, scheduler/loop/balancer
  counters), plugged in as **sources** — callables polled at collection
  time — so the registry unifies them without forking their storage;
  :func:`prometheus_text` merges many registries (one per replica,
  labelled) into one exposition, which is how ``service.py`` and
  ``Supervisor.snapshot`` aggregate across replicas.

Overhead contract (docs/observability.md): tracing is **opt-in**, the
ring buffer bounds memory (oldest events drop first, ``dropped``
counts them), span emission is O(1) appends with no I/O, and exporters
only walk the buffer when asked. The enabled tracer must cost < 2 % on
the closed-loop serving benchmark; the no-op default < 0.5 %.
"""
from __future__ import annotations

import json
import time
from collections import deque
from contextlib import contextmanager

import jax

# Trace "process" ids: Perfetto groups tracks by pid, so the serve
# loop's tick phases, the per-request lifecycles, the pool's occupancy
# counters and the engine's phases land in separately-collapsible groups.
PID_LOOP = 0        # serve-loop tick phases (one thread track)
PID_REQUESTS = 1    # one thread track per request (tid = rid)
PID_POOL = 2        # block-pool counters + events
PID_ENGINE = 3      # engine phases inside a tick: launch, waits, admit


class NoopTracer:
    """Default tracer: every emitter is a no-op, ``enabled`` is False so
    call sites can skip even argument construction. Exporters render an
    empty trace rather than raising, so ``--trace-out`` on an untraced
    run fails loudly at the *flag* level, not deep in a serve loop."""

    enabled = False

    def instant(self, name, *, pid=0, tid=0, args=None, ts=None):
        pass

    def complete(self, name, start, duration, *, pid=0, tid=0,
                 args=None):
        pass

    def counter(self, name, values, *, pid=0, tid=0, ts=None):
        pass

    @contextmanager
    def span(self, name, *, pid=0, tid=0, args=None):
        yield

    def chrome_trace(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path) -> int:
        raise RuntimeError("no-op tracer records nothing; construct a "
                           "Tracer and pass it to the engine")


NOOP = NoopTracer()


class Tracer(NoopTracer):
    """Bounded in-memory trace recorder with Chrome trace-event export.

    ``clock`` is any zero-argument callable returning seconds
    (``time.perf_counter`` by default, a ``VirtualClock`` in tests);
    every event is stamped with it at emission, so trace timelines and
    the serving stack's latency stats live on one time base when both
    share a clock. ``capacity`` bounds the ring buffer — the hot path
    never grows without bound; the oldest events are evicted first and
    counted in ``dropped``.
    """

    enabled = True

    def __init__(self, clock=time.perf_counter, capacity: int = 65536):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.clock = clock
        self.capacity = capacity
        self._events: deque = deque(maxlen=capacity)
        self.dropped = 0

    def __len__(self) -> int:
        return len(self._events)

    # ------------------------------------------------------------- emit
    def _emit(self, ev: dict) -> None:
        if len(self._events) == self.capacity:
            self.dropped += 1
        self._events.append(ev)

    def instant(self, name, *, pid=0, tid=0, args=None, ts=None):
        """A point event (``ph: "i"``): admit / park / preempt / shed /
        first-token markers."""
        self._emit({"name": name, "ph": "i", "s": "t",
                    "ts": self._us(self.clock() if ts is None else ts),
                    "pid": pid, "tid": tid,
                    **({"args": args} if args else {})})

    def complete(self, name, start, duration, *, pid=0, tid=0,
                 args=None):
        """A closed interval (``ph: "X"``) stamped by the caller —
        lifecycle phases reconstructed at retire time, tick phases
        measured around the work they cover."""
        self._emit({"name": name, "ph": "X", "ts": self._us(start),
                    "dur": self._us(max(duration, 0.0)),
                    "pid": pid, "tid": tid,
                    **({"args": args} if args else {})})

    def counter(self, name, values, *, pid=0, tid=0, ts=None):
        """A counter sample (``ph: "C"``): Perfetto renders each key of
        ``values`` as a stacked series (pool occupancy, spec accepts)."""
        self._emit({"name": name, "ph": "C",
                    "ts": self._us(self.clock() if ts is None else ts),
                    "pid": pid, "tid": tid, "args": dict(values)})

    @contextmanager
    def span(self, name, *, pid=0, tid=0, args=None):
        """Context-manager form of :meth:`complete` for host-side work
        measured in place."""
        t0 = self.clock()
        try:
            yield
        finally:
            self.complete(name, t0, self.clock() - t0, pid=pid, tid=tid,
                          args=args)

    @staticmethod
    def _us(t: float) -> float:
        # Chrome trace timestamps are microseconds; rounding to 0.1 us
        # keeps the JSON stable against float-repr noise without losing
        # anything a serve loop can resolve
        return round(t * 1e6, 1)

    # ----------------------------------------------------------- export
    def chrome_trace(self) -> dict:
        """The ring buffer as a Chrome trace-event object (Perfetto /
        chrome://tracing loadable). Process/thread metadata names the
        tracks; request tracks are labelled by rid. Deterministic for a
        deterministic clock: events render in emission order with
        sorted keys, so two identical scripted runs serialize to
        byte-identical JSON."""
        events = [{"name": "process_name", "ph": "M", "pid": pid,
                   "tid": 0, "args": {"name": label}}
                  for pid, label in ((PID_LOOP, "serve-loop"),
                                     (PID_REQUESTS, "requests"),
                                     (PID_POOL, "kv-block-pool"),
                                     (PID_ENGINE, "serve-engine"))]
        rids = sorted({e["tid"] for e in self._events
                       if e["pid"] == PID_REQUESTS})
        events.extend({"name": "thread_name", "ph": "M",
                       "pid": PID_REQUESTS, "tid": rid,
                       "args": {"name": f"request {rid}"}}
                      for rid in rids)
        events.extend(self._events)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def write_chrome_trace(self, path) -> int:
        """Serialize to ``path``; returns the number of trace events
        written (metadata included)."""
        trace = self.chrome_trace()
        with open(path, "w", encoding="utf-8") as f:
            json.dump(trace, f, sort_keys=True, separators=(",", ":"))
            f.write("\n")
        return len(trace["traceEvents"])


class phase:
    """Time one host phase: ``with phase("fill", clock, metrics,
    "fill_s", tracer):``. Runs the block inside a
    ``jax.profiler.TraceAnnotation`` named ``serve.<name>``, reads
    ``clock`` once on entry (``start``) and once on exit (``end``), adds
    ``end - start`` to ``metrics[key]`` and, when ``tracer`` records,
    emits the span ``name`` on track ``pid`` from the same two reads —
    so the counter, the ring-buffer span and the profiler's host event
    cover the same work. ``args`` (settable inside the block) go on the
    span. Costs two clock reads, a dict add and an inactive profiler
    annotation when nothing records: time phases, never per-slot or
    per-token work."""

    __slots__ = ("name", "clock", "metrics", "key", "tracer", "pid",
                 "args", "start", "end", "_annotation")

    def __init__(self, name: str, clock, metrics: dict, key: str,
                 tracer=NOOP, *, pid: int = PID_LOOP, args=None):
        self.name, self.clock = name, clock
        self.metrics, self.key = metrics, key
        self.tracer, self.pid, self.args = tracer, pid, args
        self._annotation = jax.profiler.TraceAnnotation("serve." + name)

    def __enter__(self) -> "phase":
        self._annotation.__enter__()
        self.start = self.clock()
        return self

    def __exit__(self, *exc) -> None:
        self.end = self.clock()
        self.metrics[self.key] += self.end - self.start
        if self.tracer.enabled:
            self.tracer.complete(self.name, self.start,
                                 self.end - self.start, pid=self.pid,
                                 args=self.args)
        self._annotation.__exit__(*exc)


# =========================================================== metrics
def _sanitize(name: str) -> str:
    """Prometheus metric names allow [a-zA-Z0-9_:]; everything else
    (the dots of ``serving.open_loop.ttft``-style row names, slashes of
    replica names) maps to ``_``."""
    return "".join(c if c.isalnum() or c in "_:" else "_" for c in name)


class MetricsRegistry:
    """Polled sources with Prometheus text exposition.

    ``labels`` stamp every sample (e.g. ``{"replica": "lm/0"}``) so
    per-replica registries merge into one exposition without name
    collisions. ``source(prefix, fn)`` registers a zero-arg callable
    returning a flat dict of numbers — the bridge that puts
    ``engine.metrics`` / ``pool.stats()`` / scheduler / loop / balancer
    counters behind this one registry instead of five ad-hoc dicts:
    sources are polled at :meth:`collect` time and rendered as gauges
    (their dict semantics: current value, resettable by the owner).
    Non-numeric source values are skipped."""

    def __init__(self, labels: dict | None = None):
        self.labels = dict(labels or {})
        self._sources: list[tuple[str, object]] = []

    def source(self, prefix: str, fn) -> None:
        """Poll ``fn()`` (a flat ``{name: number}`` dict) at collect
        time, exposing each key as gauge ``{prefix}_{key}``."""
        self._sources.append((prefix, fn))

    def collect(self) -> list:
        """``(name, labels, value)`` for every numeric source key."""
        out = []
        for prefix, fn in self._sources:
            vals = fn()
            for key in sorted(vals):
                v = vals[key]
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                out.append((_sanitize(f"{prefix}_{key}"), self.labels,
                            float(v)))
        return out

    def prometheus_text(self) -> str:
        return prometheus_text([self])


def _render_labels(labels: dict) -> str:
    if not labels:
        return ""
    body = ",".join(f'{_sanitize(k)}="{v}"'
                    for k, v in sorted(labels.items()))
    return "{" + body + "}"


def prometheus_text(registries) -> str:
    """Merge many registries (one per replica, each with distinguishing
    labels) into one Prometheus text exposition: ``# TYPE`` emitted once
    per metric name, samples from every registry under it."""
    by_name: dict[str, list] = {}
    for reg in registries:
        for name, labels, value in reg.collect():
            by_name.setdefault(name, []).append((labels, value))
    lines = []
    for name in sorted(by_name):
        lines.append(f"# TYPE {name} gauge")
        lines.extend(f"{name}{_render_labels(labels)} {_fmt(value)}"
                     for labels, value in by_name[name])
    return "\n".join(lines) + "\n"


def _fmt(v) -> str:
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)
