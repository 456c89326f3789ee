"""Telemetry: tracer determinism, trace/stats reconstruction, the phase
timer's counters and spans, metrics registry + Prometheus exposition,
and bit-identity with tracing on.

The load-bearing claims: (1) a scripted workload under a VirtualClock
emits **byte-identical** trace JSON run to run, (2) the trace's queued
span and TTFT are the *same numbers* the scheduler/engine report (same
clock reads, not a re-measurement), and (3) turning tracing on changes
no token stream anywhere on the engine grid.
"""
import dataclasses
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models.model import build_model
from repro.serve.async_loop import AsyncServeLoop
from repro.serve.clock import VirtualClock
from repro.serve.engine import Request, ServingEngine
from repro.serve.scheduler import Scheduler
from repro.serve.telemetry import (NOOP, PID_ENGINE, PID_LOOP, PID_POOL,
                                   PID_REQUESTS, MetricsRegistry, Tracer,
                                   phase, prometheus_text)

MAX_SEQ = 64


# ===================================================== tracer unit tests
def test_ring_buffer_bounds_and_counts_drops():
    vc = VirtualClock()
    tr = Tracer(clock=vc, capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert tr.dropped == 6
    names = [e["name"] for e in tr.chrome_trace()["traceEvents"]
             if e["ph"] == "i"]
    assert names == ["e6", "e7", "e8", "e9"]     # oldest evicted first
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 6


def test_capacity_must_be_positive():
    with pytest.raises(ValueError, match="capacity"):
        Tracer(capacity=0)


def test_noop_is_default_and_inert(tmp_path):
    assert NOOP.enabled is False
    NOOP.instant("x")
    NOOP.complete("x", 0.0, 1.0)
    NOOP.counter("x", {"v": 1})
    with NOOP.span("x"):
        pass
    assert NOOP.chrome_trace()["traceEvents"] == []
    with pytest.raises(RuntimeError, match="no-op tracer"):
        NOOP.write_chrome_trace(tmp_path / "t.json")


def test_span_context_manager_measures_clock():
    vc = VirtualClock()
    tr = Tracer(clock=vc)
    with tr.span("work", pid=PID_LOOP, args={"k": 1}):
        vc.advance(0.5)
    (ev,) = [e for e in tr.chrome_trace()["traceEvents"]
             if e["ph"] == "X"]
    assert ev["name"] == "work"
    assert ev["ts"] == 0.0 and ev["dur"] == 500000.0
    assert ev["args"] == {"k": 1}


def test_negative_duration_clamped():
    tr = Tracer(clock=VirtualClock())
    tr.complete("x", 1.0, -0.5)
    (ev,) = [e for e in tr.chrome_trace()["traceEvents"]
             if e["ph"] == "X"]
    assert ev["dur"] == 0.0


# ================================================ phase timer unit tests
def test_phase_adds_interval_and_emits_identical_span():
    vc = VirtualClock(start=2.0)
    tr = Tracer(clock=vc)
    m = {"fill_s": 0.5}
    with phase("fill", vc, m, "fill_s", tr, pid=PID_ENGINE) as p:
        vc.advance(0.25)
        p.args = {"k": 1}
    assert (p.start, p.end) == (2.0, 2.25)
    assert m["fill_s"] == 0.75                  # added, not overwritten
    (ev,) = [e for e in tr.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert ev == {"name": "fill", "ph": "X", "ts": 2000000.0,
                  "dur": 250000.0, "pid": PID_ENGINE, "tid": 0,
                  "args": {"k": 1}}


def test_phase_without_a_recording_tracer_still_counts():
    vc = VirtualClock()
    m = {"emit_s": 0.0}
    for dt in (0.125, 0.5):
        with phase("emit", vc, m, "emit_s"):    # NOOP by default
            vc.advance(dt)
    assert m["emit_s"] == 0.625
    with pytest.raises(KeyError):               # the block's error passes
        with phase("emit", vc, m, "emit_s", NOOP):
            vc.advance(1.0)
            raise KeyError("client")
    assert m["emit_s"] == 1.625                 # and its time is counted
    assert NOOP.chrome_trace()["traceEvents"] == []


def test_registry_source_polls_and_skips_non_numeric():
    state = {"completed": 1, "label": "text", "flag": True, "ratio": 0.5}
    reg = MetricsRegistry(labels={"replica": "lm/0"})
    reg.source("engine", lambda: state)
    names = {name for name, *_ in reg.collect()}
    assert "engine_completed" in names and "engine_ratio" in names
    assert "engine_label" not in names     # non-numeric skipped
    assert "engine_flag" not in names      # bools are not metrics
    state["completed"] = 7                 # polled, not copied
    text = reg.prometheus_text()
    assert 'engine_completed{replica="lm/0"} 7' in text


def test_prometheus_merge_across_registries():
    regs = []
    for i in range(2):
        reg = MetricsRegistry(labels={"replica": f"lm/{i}"})
        reg.source("engine", lambda i=i: {"completed": i + 1,
                                          "launch_gap_s": 0.5 * i})
        regs.append(reg)
    text = prometheus_text(regs)
    # TYPE once per name, samples from both registries under it
    assert text.count("# TYPE engine_completed gauge") == 1
    assert 'engine_completed{replica="lm/0"} 1' in text
    assert 'engine_completed{replica="lm/1"} 2' in text
    assert 'engine_launch_gap_s{replica="lm/0"} 0' in text
    assert 'engine_launch_gap_s{replica="lm/1"} 0.5' in text
    assert "# HELP" not in text


def test_metric_names_sanitized():
    reg = MetricsRegistry()
    reg.source("serving", lambda: {"open_loop.ttft/p50": 3})
    text = reg.prometheus_text()
    assert "serving_open_loop_ttft_p50 3" in text


# ================================================== engine integration
@pytest.fixture(scope="module")
def stack():
    cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                              dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _prompts(cfg, lens, seed=1):
    rng = jax.random.key(seed)
    out = []
    for L in lens:
        rng, k = jax.random.split(rng)
        out.append(jax.random.randint(k, (L,), 2, cfg.vocab_size).tolist())
    return out


def _scripted_serve(model, params, prompts, **kw):
    """One deterministic serve: all requests submitted at t=0, the loop
    pumped on a virtual 10 ms tick with the tracer on the same clock.
    Returns (tracer, scheduler, requests)."""
    vc = VirtualClock()
    tracer = Tracer(clock=vc)
    eng = ServingEngine(model, params, batch_size=4, max_seq=MAX_SEQ,
                        clock=vc, tracer=tracer, **kw)
    sched = Scheduler(eng, clock=vc)
    loop = AsyncServeLoop(sched)
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=4)
            for i, p in enumerate(prompts)]
    handles = []
    for r in reqs:
        r.submitted_s = vc()            # scheduler timeline, not wall
        handles.append(loop.submit(r))
    t = 0
    while not all(h.done for h in handles):
        loop.run_once()
        vc.advance(0.01)
        t += 1
        assert t < 500, "serve did not converge"
    return tracer, sched, reqs


def test_trace_byte_identical_under_virtual_clock(stack, tmp_path):
    """Acceptance: two runs of the same scripted workload emit
    byte-identical trace JSON."""
    cfg, model, params = stack
    lens = [5, 9, 7, 12, 6]
    paths = []
    for run in range(2):
        tracer, _, _ = _scripted_serve(model, params,
                                       _prompts(cfg, lens, seed=2))
        p = tmp_path / f"run{run}.json"
        tracer.write_chrome_trace(p)
        paths.append(p)
    assert paths[0].read_bytes() == paths[1].read_bytes()


def test_trace_validates_and_covers_all_tracks(stack, tmp_path):
    cfg, model, params = stack
    tracer, _, reqs = _scripted_serve(model, params,
                                      _prompts(cfg, [5, 9, 7], seed=3))
    p = tmp_path / "t.json"
    tracer.write_chrome_trace(p)
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                           / "scripts"))
    try:
        from check_trace import validate
    finally:
        sys.path.pop(0)
    assert validate(p) == []
    events = json.loads(p.read_text())["traceEvents"]
    pids = {e["pid"] for e in events}
    assert {PID_LOOP, PID_REQUESTS, PID_POOL} <= pids
    names = {e["name"] for e in events}
    assert {"submit", "queued", "admitted", "first_token", "request",
            "prefill", "decode", "plan-window", "commit-wait",
            "pool"} <= names
    # one lifecycle span per request, every one completed
    lifecycle = [e for e in events
                 if e["name"] == "request" and e["ph"] == "X"]
    assert sorted(e["tid"] for e in lifecycle) \
        == sorted(r.rid for r in reqs)
    assert all(e["args"]["status"] == "completed" for e in lifecycle)


def test_trace_reconstructs_ttft_and_queue_wait(stack):
    """Acceptance: per-request spans reconstruct TTFT and queue wait
    equal to the engine's/scheduler's own reported values."""
    cfg, model, params = stack
    tracer, sched, reqs = _scripted_serve(
        model, params, _prompts(cfg, [5, 9, 7, 12, 6], seed=4))
    events = tracer.chrome_trace()["traceEvents"]

    def us(x):
        return round(x * 1e6, 1)

    # queued spans carry the exact same durations the stats recorded
    queued = sorted(e["dur"] for e in events if e["name"] == "queued")
    assert queued == sorted(us(w) for w in sched.stats.queue_wait_s)

    by_rid = {}
    for e in events:
        if e["name"] in ("submit", "first_token", "request"):
            by_rid.setdefault(e["tid"], {})[e["name"]] = e
    for r in reqs:
        ev = by_rid[r.rid]
        # TTFT from the trace == TTFT from the engine's stamps
        assert ev["first_token"]["ts"] - ev["submit"]["ts"] \
            == pytest.approx(us(r.first_token_s - r.submitted_s))
        # lifecycle span == the request's reported latency
        assert ev["request"]["dur"] == pytest.approx(us(r.latency_s))
        assert ev["request"]["args"]["tokens"] == len(r.out_tokens)


def test_tick_phases_cover_the_pipeline(stack):
    cfg, model, params = stack
    tracer, sched, _ = _scripted_serve(model, params,
                                       _prompts(cfg, [5, 7], seed=5))
    loop_spans = [e for e in tracer.chrome_trace()["traceEvents"]
                  if e["pid"] == PID_LOOP and e["ph"] == "X"]
    phases = {e["name"] for e in loop_spans}
    assert {"apply-cancels", "fill", "dispatch", "plan-window",
            "commit-wait", "emit"} <= phases
    # committed ticks all carry the full dispatch->commit split
    n_commit = sum(1 for e in loop_spans if e["name"] == "commit-wait")
    assert n_commit == sched.stats.ticks
    for e in loop_spans:
        assert e["dur"] >= 0.0


def test_pool_track_alloc_free_and_occupancy(stack):
    cfg, model, params = stack
    tracer, _, _ = _scripted_serve(model, params,
                                   _prompts(cfg, [5, 9, 7], seed=6))
    events = tracer.chrome_trace()["traceEvents"]
    pool = [e for e in events if e["pid"] == PID_POOL]
    assert any(e["name"] == "alloc" for e in pool)
    assert any(e["name"] == "free" for e in pool)
    counters = [e for e in pool if e["ph"] == "C" and e["name"] == "pool"]
    assert counters
    assert all(set(e["args"]) == {"used", "shared", "cached"}
               for e in counters)
    # everything retired: the last occupancy sample (emitted on the
    # final free) shows no held blocks
    assert counters[-1]["args"]["used"] == 0


# ------------------------------------------------- phase counters
# scripted host time per phase (s): distinct, so time booked to the
# wrong phase breaks the identities below
SLOW = {"cancel": 1e-3, "fill": 2e-3, "plan": 3e-3, "account": 5e-3,
        "emit": 7e-3, "grow": 11e-3, "launch": 13e-3, "wait": 17e-3,
        "admit_wait": 19e-3, "done_check": 23e-4, "outside": 29e-3}
HOOK_CONFIGS = {"decode": ({}, [5, 9, 7, 12, 6]),
                "chunked": ({"prefill_chunk": 8}, [21, 30, 17, 26, 19])}


class _SlowFetch:
    """A step output whose transfer to the host takes scripted time."""

    def __init__(self, x, vc, dt):
        self.x, self.vc, self.dt = x, vc, dt

    def __array__(self, dtype=None, copy=None):
        self.vc.advance(self.dt)
        return np.asarray(self.x, dtype)


def _slow(vc, fn, dt, fetch=0.0):
    """``fn`` taking ``dt`` of the clock; with ``fetch``, its first two
    outputs take that long each to reach the host (an output fed to the
    next step on the device reaches no host)."""
    def run(*a, **k):
        vc.advance(dt)
        a = [x.x if isinstance(x, _SlowFetch) else x for x in a]
        out = fn(*a, **k)
        if fetch:
            out = (_SlowFetch(out[0], vc, fetch),
                   _SlowFetch(out[1], vc, fetch)) + tuple(out[2:])
        return out
    return run


@pytest.fixture(scope="module", params=list(HOOK_CONFIGS))
def hooked(stack, request):
    """A scripted serve on a VirtualClock in which every host phase of
    the loop and the engine takes a known time, pumped with no idle
    tick. Returns (loop, tracer, per-tick counter snapshots)."""
    cfg, model, params = stack
    kw, lens = HOOK_CONFIGS[request.param]
    vc = VirtualClock()
    tracer = Tracer(clock=vc)
    eng = ServingEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                        clock=vc, tracer=tracer, **kw)
    sched = Scheduler(eng, clock=vc)
    loop = AsyncServeLoop(sched)
    loop._apply_cancels = _slow(vc, loop._apply_cancels, SLOW["cancel"])
    sched.fill = _slow(vc, sched.fill, SLOW["fill"])
    sched.plan_ahead = _slow(vc, sched.plan_ahead, SLOW["plan"])
    sched.account = _slow(vc, sched.account, SLOW["account"])
    loop._emit = _slow(vc, loop._emit, SLOW["emit"])
    eng._grow_or_park = _slow(vc, eng._grow_or_park, SLOW["grow"])
    eng._is_done = _slow(vc, eng._is_done, SLOW["done_check"])
    eng._decode = _slow(vc, eng._decode, SLOW["launch"], SLOW["wait"])
    eng._chunk_fn = _slow(vc, eng._chunk_fn, SLOW["launch"], SLOW["wait"])
    eng._prefill_paged = _slow(vc, eng._prefill_paged, 0.0,
                               SLOW["admit_wait"])
    handles = [loop.submit(Request(rid=i, prompt=list(p), max_new_tokens=4))
               for i, p in enumerate(_prompts(cfg, lens, seed=31))]
    snaps = []
    while not all(h.done for h in handles):
        assert loop.run_once(), "the scripted serve has no idle tick"
        snaps.append((dict(loop.metrics), dict(eng.metrics)))
        vc.advance(SLOW["outside"])
        assert len(snaps) < 200, "serve did not converge"
    return loop, tracer, snaps


def test_launch_gap_is_the_host_phases_between_steps(hooked):
    """From one step's result on the host to the next step's launch
    returning: the commit bookkeeping after the device wait, account,
    emit, the caller's time, cancels, fill (admissions included) and the
    dispatch up to the launch. Nothing after the launch takes scripted
    time, so ``dispatch_s`` is the dispatch up to the launch; the plan
    window overlaps the device and is no part of the gap."""
    _, _, snaps = hooked
    zero = ({k: 0 for k in snaps[0][0]}, {k: 0 for k in snaps[0][1]})
    prev = zero
    ticks = []
    for lm, em in snaps:
        ticks.append(({k: lm[k] - prev[0][k] for k in lm},
                      {k: em[k] - prev[1][k] for k in em}))
        prev = (lm, em)
    # a step launched before the step before it committed (chained) is
    # queued behind it on the device: a gap of 0
    first = ticks[0][1]
    assert first["launch_gaps"] == first["chained_steps"]   # none before
    for (pl, pe), (lm, em) in zip(ticks, ticks[1:]):
        launched = em["decode_steps"] - em["chained_steps"]
        assert launched in (0, 1)       # 0: the last tick launched it
        assert em["launch_gaps"] == launched + em["chained_steps"]
        expect = launched * (
            pl["commit_wait_s"] - pe["device_wait_s"]
            + pl["account_s"] + pl["emit_s"] + lm["outside_s"]
            + lm["cancel_s"] + lm["fill_s"] + lm["dispatch_s"])
        assert em["launch_gap_s"] == pytest.approx(expect, abs=1e-9)
    last_l, last_e = snaps[-1]
    assert last_e["chained_steps"] > 0
    n = len(snaps)
    assert last_e["launch_gaps"] == n - 1
    assert last_e["launch_s"] == pytest.approx(n * SLOW["launch"])
    assert last_e["device_wait_s"] == pytest.approx(2 * n * SLOW["wait"])
    assert last_l["outside_s"] == pytest.approx((n - 1) * SLOW["outside"])
    assert last_l["plan_time_s"] == pytest.approx(n * SLOW["plan"])
    # admissions ran inside fill, each blocked on its admit program
    assert 0 < last_e["admit_wait_s"] <= last_e["admit_s"] \
        <= last_l["fill_s"]
    assert last_e["write_blocks"] >= last_e["prefills"]


PHASE_COUNTERS = [
    ("apply-cancels", PID_LOOP, "loop", "cancel_s"),
    ("fill", PID_LOOP, "loop", "fill_s"),
    ("dispatch", PID_LOOP, "loop", "dispatch_s"),
    ("dispatch-next", PID_LOOP, "loop", "dispatch_next_s"),
    ("plan-window", PID_LOOP, "loop", "plan_time_s"),
    ("commit-wait", PID_LOOP, "loop", "commit_wait_s"),
    ("account", PID_LOOP, "loop", "account_s"),
    ("emit", PID_LOOP, "loop", "emit_s"),
    ("launch", PID_ENGINE, "engine", "launch_s"),
    ("device-wait", PID_ENGINE, "engine", "device_wait_s"),
    ("admit", PID_ENGINE, "engine", "admit_s"),
    ("admit-wait", PID_ENGINE, "engine", "admit_wait_s"),
]


@pytest.mark.parametrize("name,pid,owner,key", PHASE_COUNTERS,
                         ids=[c[0] for c in PHASE_COUNTERS])
def test_phase_spans_sum_to_their_counter(hooked, name, pid, owner, key):
    """The trace shows exactly what the counters sum: same clock reads."""
    loop, tracer, _ = hooked
    metrics = loop.metrics if owner == "loop" else loop.engine.metrics
    spans = [e for e in tracer.chrome_trace()["traceEvents"]
             if e["ph"] == "X" and e["name"] == name and e["pid"] == pid]
    assert spans
    assert sum(e["dur"] for e in spans) == pytest.approx(
        metrics[key] * 1e6, abs=0.1 * len(spans))
    assert metrics[key] > 0


def test_idle_tick_breaks_the_launch_chain(stack):
    cfg, model, params = stack
    vc = VirtualClock()
    eng = ServingEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                        clock=vc)
    loop = AsyncServeLoop(Scheduler(eng, clock=vc))
    p1, p2 = _prompts(cfg, [6, 7], seed=33)
    for rid, p in ((0, p1), (1, p2)):
        h = loop.submit(Request(rid=rid, prompt=list(p), max_new_tokens=3))
        while not h.done:
            loop.run_once()
            vc.advance(0.01)
        gaps = dict(eng.metrics)
        for _ in range(3):              # idle: nothing queued or active
            assert not loop.run_once()
            vc.advance(5.0)
    # each request: admission + 2 decode steps, one gap between them;
    # the 15 s idle spell is no launch gap
    assert gaps["launch_gaps"] == 2 and gaps["decode_steps"] == 4
    assert gaps["launch_gap_s"] == pytest.approx(0.02)
    # the caller's time: every advance but the last falls between calls
    assert loop.metrics["outside_s"] == pytest.approx(
        0.01 * loop.metrics["ticks"] + 5.0 * 5)


def test_admit_to_first_token_sums_the_request_stamps(stack):
    cfg, model, params = stack
    vc = VirtualClock()
    eng = ServingEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                        clock=vc, prefill_chunk=8)
    loop = AsyncServeLoop(Scheduler(eng, clock=vc))
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=3)
            for i, p in enumerate(_prompts(cfg, [21, 30, 5, 17], seed=35))]
    handles = [loop.submit(r) for r in reqs]
    while not all(h.done for h in handles):
        loop.run_once()
        vc.advance(0.01)
    m = eng.metrics
    assert m["first_tokens"] == len(reqs)
    waits = [r.first_token_s - r.admitted_s for r in reqs]
    assert m["admit_to_first_s"] == pytest.approx(sum(waits))
    # the 30-token prompt: 8 tokens at admission, then windows of 8, 8
    # and 6, the first in the admission's own tick; the 5-token one's
    # first token comes from its admission
    assert max(waits) == pytest.approx(0.02)
    assert min(waits) == 0.0


def test_profiler_trace_holds_phases_nested_in_the_tick(stack, tmp_path):
    """Under a profiler trace the phases are host events on the
    profiler's clock, nested in the caller's span around ``run_once``
    and, for the engine's, in the loop phase that runs them."""
    cfg, model, params = stack
    eng = ServingEngine(model, params, batch_size=2, max_seq=MAX_SEQ)
    loop = AsyncServeLoop(Scheduler(eng))
    handles = [loop.submit(Request(rid=i, prompt=list(p), max_new_tokens=3))
               for i, p in enumerate(_prompts(cfg, [6, 9, 7], seed=37))]
    loop.run_once()                     # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        while not all(h.done for h in handles):
            with jax.profiler.TraceAnnotation("tick"):
                loop.run_once()
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))

    def inside(child, parent):
        return events[child] and all(
            any(ps <= s and e <= pe for ps, pe in events[parent])
            for s, e in events[child])

    ticks = len(events["tick"])
    assert ticks >= 3
    for name in ("serve.fill", "serve.dispatch", "serve.commit-wait",
                 "serve.emit"):
        assert len(events[name]) == ticks and inside(name, "tick"), name
    assert inside("serve.launch", "serve.dispatch")
    assert inside("serve.device-wait", "serve.commit-wait")
    assert inside("serve.admit", "serve.fill")


# ------------------------------------------ tracing-on bit-identity grid
GRID = {
    "paged": ({}, [5, 9, 7, 12, 6]),
    "kernel": ({"use_kernel": True}, [5, 9, 7, 12, 6]),
    "shared_prefix": ({}, None),
    "chunked": ({"prefill_chunk": 8}, [21, 30, 17, 26, 19]),
    "speculative": ("SPEC", [5, 9, 7, 12, 6]),
}


@pytest.mark.parametrize("config", list(GRID))
def test_streams_bit_identical_with_tracing_enabled(stack, config):
    """Acceptance: async streams stay bit-identical to the sync drain
    with tracing ENABLED, across the engine grid — observation must not
    perturb the system."""
    cfg, model, params = stack
    kw, lens = GRID[config]
    if kw == "SPEC":
        kw = {"draft_model": model, "draft_params": params,
              "speculation": 3}
    if config == "shared_prefix":
        stem = _prompts(cfg, [20], seed=7)[0]
        tails = _prompts(cfg, [3, 5, 2], seed=8)
        prompts = [list(stem)] + [stem + tl for tl in tails]
    else:
        prompts = _prompts(cfg, lens, seed=9)

    vc = VirtualClock()
    tracer = Tracer(clock=vc)
    eng = ServingEngine(model, params, batch_size=4, max_seq=MAX_SEQ,
                        clock=vc, tracer=tracer, **kw)
    loop = AsyncServeLoop(Scheduler(eng, clock=vc))
    streams = {i: [] for i in range(len(prompts))}
    handles = {}
    t = 0
    while len(handles) < len(prompts) \
            or not all(h.done for h in handles.values()):
        # arrivals staggered 2 ticks apart: mid-decode admissions
        for i, p in enumerate(prompts):
            if i not in handles and 2 * i <= t:
                handles[i] = loop.submit(
                    Request(rid=i, prompt=list(p), max_new_tokens=4),
                    lambda tok, lp, rid=i: streams[rid].append(tok))
        loop.run_once()
        vc.advance(0.01)
        t += 1
        assert t < 500, "serve did not converge"
    assert len(tracer) > 0              # tracing actually recorded

    ref = ServingEngine(model, params, batch_size=4, max_seq=MAX_SEQ,
                        **kw)           # untraced synchronous reference
    ref_done = ref.run([Request(rid=100 + i, prompt=list(p),
                                max_new_tokens=4)
                        for i, p in enumerate(prompts)])
    assert streams == {r.rid - 100: r.out_tokens for r in ref_done}
    if config == "speculative":
        spec = [e for e in tracer.chrome_trace()["traceEvents"]
                if e["name"] == "speculation"]
        assert spec, "speculative serve emitted no window counters"
        assert all(0 <= e["args"]["accepted"] <= e["args"]["proposed"]
                   for e in spec)


# ---------------------------------------------- kernel dispatch counters
def test_kernel_dispatch_counters_reach_prometheus(stack):
    """`kernel_windows` counts fused multi-token launches (verify +
    chunk ticks) and `kernel_positions` the total real query positions
    through the paged kernel — so a Prometheus scrape tells fused-window
    launches from single-token decode launches. Gather-path engines
    must leave both at zero."""
    cfg, model, params = stack
    prompts = _prompts(cfg, [20, 9], seed=21)
    eng = ServingEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                        block_size=8, use_kernel=True, prefill_chunk=8)
    eng.run([Request(rid=i, prompt=list(p), max_new_tokens=4)
             for i, p in enumerate(prompts)])
    # chunk ticks ran fused windows; decode ticks added 1 position per
    # active row with no window launch
    assert eng.metrics["kernel_windows"] > 0
    assert eng.metrics["chunk_steps"] >= eng.metrics["kernel_windows"]
    assert eng.metrics["kernel_positions"] > eng.metrics["kernel_windows"]
    reg = MetricsRegistry(labels={"replica": "lm/0"})
    reg.source("engine", lambda: eng.metrics)
    text = reg.prometheus_text()
    assert 'engine_kernel_windows{replica="lm/0"}' in text
    assert 'engine_kernel_positions{replica="lm/0"}' in text
    gather = ServingEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                           block_size=8, use_kernel=False, prefill_chunk=8)
    gather.run([Request(rid=10 + i, prompt=list(p), max_new_tokens=4)
                for i, p in enumerate(prompts)])
    assert gather.metrics["kernel_windows"] == 0
    assert gather.metrics["kernel_positions"] == 0


def test_kernel_page_counters_match_hand_count(stack):
    """`kernel_pages_held` adds, per paged step program, the table pages
    each row's window reads (positions below its length + window; an
    idle row at length 0 reads one), `kernel_pages_table` the whole
    table (2 slots x 8 pages of 8 tokens). Prompts 20 and 9, chunk 8:
    admission writes 8 tokens of each, then

    * chunk window 8 at lengths [8, 8]: 2 + 2 = 4 pages
    * chunk window 8 (a 4-token chunk rides the smallest width) at
      [16, 9]: ceil(24/8) + ceil(17/8) = 6
    * decode at [20, 10]: ceil(21/8) + ceil(11/8) = 5
    * decode at [0, 11], row 0 done after 2 tokens: 1 + 2 = 3

    18 held of 4 x 16. The gather path counts nothing."""
    cfg, model, params = stack
    prompts = _prompts(cfg, [20, 9], seed=21)
    counts = []
    for use_kernel in (True, False):
        eng = ServingEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                            block_size=8, use_kernel=use_kernel,
                            prefill_chunk=8)
        eng.run([Request(rid=i, prompt=list(p), max_new_tokens=n)
                 for i, (p, n) in enumerate(zip(prompts, (2, 4)))])
        assert eng.metrics["decode_steps"] == 4
        counts.append((eng.metrics["kernel_pages_held"],
                       eng.metrics["kernel_pages_table"]))
    assert counts == [(18, 64), (0, 0)]


# ------------------------------------------------- service-level scrape
def test_service_and_supervisor_prometheus_exposition(stack):
    from repro.core.supervisor import Supervisor
    from repro.serve.service import (make_lm_service,
                                     service_prometheus_text)
    cfg, model, params = stack
    sup = Supervisor()
    svc = make_lm_service("lm", model, params, n_replicas=1,
                          batch_size=2, max_seq=MAX_SEQ, supervisor=sup)
    sup.start_all()
    prompt = _prompts(cfg, [5], seed=10)[0]
    out = svc.balancer({"prompt": prompt, "max_new_tokens": 3})
    assert len(out["tokens"]) == 3
    text = service_prometheus_text(svc)
    assert 'engine_completed{replica="lm/0"} 1' in text
    assert 'scheduler_completed{replica="lm/0"} 1' in text
    assert 'balancer_served{service="lm"} 1' in text
    assert "# TYPE engine_completed gauge" in text
    # fleet-level scrape: replica + balancer + supervisor accounting
    fleet = sup.prometheus_text()
    assert 'engine_completed{replica="lm/0"} 1' in fleet
    assert 'supervisor_up{service="lm"} 1' in fleet
    assert 'supervisor_restart_attempts{service="lm"} 0' in fleet
