"""Async continuous-batching serve loop: deterministic concurrency
harness (virtual clock + scripted arrival traces, zero wall-clock
sleeps).

The load-bearing guarantee: per-token streams produced by the async
dispatch → plan-ahead → commit loop are **bit-identical** to the
synchronous tick drain, across the full engine grid (paged / kernel /
shared-prefix / chunked / speculative). The engine's determinism story
makes this provable rather than flaky: a request's tokens do not depend
on batch composition or admission timing (mixed-length bit-exact decode
+ counter-based sampling), so concurrency changes *when* tokens stream,
never *what* they are.

Everything here is driven, not slept: the harness pumps
``AsyncServeLoop.run_once()`` against scripted traces and advances a
``VirtualClock`` by hand, so a loaded CI host can't turn a live request
into a shed one or hide a lost wakeup behind a generous sleep.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic local shim, see requirements-dev
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs.base import get_config
from repro.core.balancer import deploy
from repro.core.services import (Replica, RequestError, Service,
                                 ServiceError)
from repro.models.model import build_model
from repro.serve.async_loop import AsyncServeLoop
from repro.serve.clock import VirtualClock
from repro.serve.engine import Request, ServingEngine
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import Scheduler
from repro.serve.service import make_lm_service

MAX_SEQ = 64


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


def _build_loop(model, params, *, batch_size=4, vc=None, **kw):
    vc = vc or VirtualClock()
    eng = ServingEngine(model, params, batch_size=batch_size,
                        max_seq=MAX_SEQ, clock=vc, **kw)
    sched = Scheduler(eng, clock=vc)
    return eng, sched, AsyncServeLoop(sched), vc


def _pump(loop, vc, *, until, limit=2000):
    """Drive the loop tick by tick (virtual 10 ms each) until the
    predicate holds."""
    t = 0
    while not until():
        loop.run_once()
        vc.advance(0.01)
        t += 1
        assert t < limit, "serve loop did not converge"
    return t


# --------------------------------------------------- grid bit-identity
# engine kwargs + prompt lengths per config; "SPEC" is resolved to a
# self-draft speculative engine in the test body (needs model/params)
GRID = {
    "paged": ({}, [5, 9, 7, 12, 6]),
    "kernel": ({"use_kernel": True}, [5, 9, 7, 12, 6]),
    "shared_prefix": ({}, None),          # prompts share a long stem
    "chunked": ({"prefill_chunk": 8}, [21, 30, 17, 26, 19]),
    "speculative": ("SPEC", [5, 9, 7, 12, 6]),
}


@pytest.mark.parametrize("config", list(GRID))
def test_async_streams_bit_identical_to_sync_drain(stack, config):
    """Staggered open-loop arrivals through the async loop emit, per
    request, exactly the token stream a synchronous closed-loop drain
    emits, with logprobs equal to float tolerance — on every engine
    config, greedy and sampled. What the loop streams equals its own
    reply bit for bit."""
    cfg, model, params = stack
    kw, lens = GRID[config]
    if kw == "SPEC":
        kw = {"draft_model": model, "draft_params": params,
              "speculation": 3}
    if config == "shared_prefix":
        stem = _prompts(cfg, [20], seed=7)[0]
        tails = _prompts(cfg, [3, 5, 2, 4], seed=8)
        prompts = [list(stem)] + [stem + tl for tl in tails]
    else:
        prompts = _prompts(cfg, lens, seed=2)

    def mk(base):
        reqs = []
        for i, p in enumerate(prompts):
            samp = SamplingParams(temperature=0.8, top_k=8, seed=3) \
                if i == 1 else SamplingParams()
            reqs.append(Request(rid=base + i, prompt=list(p),
                                max_new_tokens=4, sampling=samp))
        return reqs

    eng, sched, loop, vc = _build_loop(model, params, **kw)
    reqs = mk(0)
    streams = {r.rid: [] for r in reqs}
    handles = {}

    def drive():
        # arrivals staggered 2 ticks apart: request i lands mid-decode
        # of its predecessors, exercising continuous batching
        for i, r in enumerate(reqs):
            if r.rid not in handles and 2 * i <= drive.t:
                handles[r.rid] = loop.submit(
                    r, lambda tok, lp, rid=r.rid:
                        streams[rid].append((tok, lp)))
        drive.t += 1
        return len(handles) == len(reqs) \
            and all(h.done for h in handles.values())
    drive.t = 0
    _pump(loop, vc, until=drive)

    ref = ServingEngine(model, params, batch_size=4, max_seq=MAX_SEQ,
                        **kw)
    ref_done = {r.rid - 100: r for r in ref.run(mk(100))}
    assert len(ref_done) == len(reqs)
    for r in reqs:
        reply = handles[r.rid].reply
        toks = [t for t, _ in streams[r.rid]]
        lps = [lp for _, lp in streams[r.rid]]
        assert toks == reply["tokens"] == ref_done[r.rid].out_tokens, \
            (config, r.rid)
        assert lps == reply["logprobs"], (config, r.rid)
        # Staggered arrivals change which compiled program computes a
        # request's logits (admission bucket, co-batched rows, chunk
        # window vs prefill): XLA compiles each shape on its own and may
        # fuse and order float reductions differently, so the last
        # digits of a logprob can move (observed: 1.4e-6 on jax 0.9.0).
        # Tokens stay bit-exact on every config; logprobs hold to the
        # float tolerance of the test_chunked.py contract.
        np.testing.assert_allclose(lps, ref_done[r.rid].out_logprobs,
                                   rtol=2e-5, atol=2e-5)
        assert len(toks) == 4
    if eng.paged:
        eng.pool.check()                   # raises on invariant breach
        assert eng.pool.available == eng.pool.total


def test_tokens_stream_incrementally_not_at_completion(stack):
    """TTFT < completion: tokens surface while the request is still
    decoding, across multiple loop ticks."""
    cfg, model, params = stack
    eng, sched, loop, vc = _build_loop(model, params)
    (p,) = _prompts(cfg, [6], seed=3)
    seen_ticks = []
    tick = [0]
    h = loop.submit(Request(rid=1, prompt=p, max_new_tokens=6),
                    lambda t, lp: seen_ticks.append(tick[0]))

    def drive():
        tick[0] += 1
        return h.done
    _pump(loop, vc, until=drive)
    assert len(seen_ticks) == 6
    assert seen_ticks[0] < seen_ticks[-1]      # not one burst at the end
    assert seen_ticks == sorted(seen_ticks)
    assert h.reply["tokens"] == h.request.out_tokens


def test_cancel_mid_stream_recycles_slot_and_blocks(stack):
    """Cancel frees the slot and its refcounted blocks mid-generation;
    the reply carries the partial stream; co-resident requests are
    untouched and the pool drains clean."""
    cfg, model, params = stack
    eng, sched, loop, vc = _build_loop(model, params, batch_size=2)
    pa, pb, pc = _prompts(cfg, [5, 8, 6], seed=4)
    got_a = []
    ha = loop.submit(Request(rid=1, prompt=pa, max_new_tokens=30),
                     lambda t, lp: got_a.append(t))
    hb = loop.submit(Request(rid=2, prompt=pb, max_new_tokens=4))
    hc = loop.submit(Request(rid=3, prompt=pc, max_new_tokens=4))  # queued
    _pump(loop, vc, until=lambda: len(got_a) >= 3)
    ha.cancel()
    _pump(loop, vc, until=lambda: ha.done)
    assert ha.cancelled
    assert ha.reply["tokens"] == got_a            # partial stream kept
    assert 3 <= len(got_a) < 30
    assert eng.metrics["cancelled"] == 1
    _pump(loop, vc, until=lambda: hb.done and hc.done)
    assert len(hb.reply["tokens"]) == len(hc.reply["tokens"]) == 4
    # the freed slot was actually recycled for the queued request
    assert sched.stats.completed == 2
    eng.pool.check()
    assert eng.pool.available == eng.pool.total


def test_cancel_while_queued_never_occupies_a_slot(stack):
    cfg, model, params = stack
    eng, sched, loop, vc = _build_loop(model, params, batch_size=1)
    pa, pb = _prompts(cfg, [5, 7], seed=5)
    ha = loop.submit(Request(rid=1, prompt=pa, max_new_tokens=6))
    hb = loop.submit(Request(rid=2, prompt=pb, max_new_tokens=2))
    _pump(loop, vc, until=lambda: len(ha.request.out_tokens) >= 1)
    hb.cancel()                                   # still in the queue
    _pump(loop, vc, until=lambda: hb.done)
    assert hb.cancelled and hb.reply["tokens"] == []
    _pump(loop, vc, until=lambda: ha.done)
    assert len(ha.reply["tokens"]) == 6
    assert hb.request.out_tokens == []            # never decoded
    assert eng.pool.available == eng.pool.total


# ------------------------------------------------------- property test
@pytest.fixture(scope="module")
def prop_stack(stack):
    """One engine/loop pair reused across hypothesis examples (each
    ServingEngine owns fresh jitted closures — rebuilding per example
    would recompile)."""
    cfg, model, params = stack
    eng, sched, loop, vc = _build_loop(model, params, batch_size=3)
    return cfg, eng, sched, loop, vc


@settings(max_examples=8, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["arrive", "cancel",
                                           "disconnect"]),
                          st.integers(min_value=0, max_value=7),
                          st.integers(min_value=0, max_value=3)),
                min_size=3, max_size=12))
def test_random_arrival_cancel_disconnect_traces(prop_stack, trace):
    """Random traces against the loop: token order per request is
    preserved (the streamed list is always a prefix of the engine's
    stream), cancelled/disconnected slots recycle, and the pool's
    refcount invariants hold after every tick, then drain clean."""
    cfg, eng, sched, loop, vc = prop_stack
    prompts = _prompts(cfg, [4, 6, 5, 7, 5, 6, 4, 8], seed=6)
    handles, streams, poisoned = {}, {}, set()
    rid = [0]

    def arrive(_):
        rid[0] += 1
        r = rid[0]
        streams[r] = []

        def tap(tok, lp, r=r):
            if r in poisoned:
                raise ConnectionResetError("client went away")
            streams[r].append(tok)
        handles[r] = loop.submit(
            Request(rid=r, prompt=list(prompts[r % len(prompts)]),
                    max_new_tokens=5), tap)

    def live():
        return [h for h in handles.values() if not h.done]

    def cancel(i):
        alive = live()
        if alive:
            alive[i % len(alive)].cancel()

    def disconnect(i):
        alive = live()
        if alive:
            poisoned.add(alive[i % len(alive)].rid)

    for op, i, gap in trace:
        {"arrive": arrive, "cancel": cancel, "disconnect": disconnect}[op](i)
        for _ in range(gap):
            loop.run_once()
            vc.advance(0.01)
            eng.pool.check()               # allocator invariants hold
            for r, h in handles.items():
                # streamed tokens are always an in-order prefix of the
                # engine's stream for that request
                assert streams[r] == h.request.out_tokens[:len(streams[r])]
    _pump(loop, vc, until=lambda: all(h.done for h in handles.values()))
    for r, h in handles.items():
        if h.cancelled:
            assert h.reply is not None
        elif r in poisoned and h.error is not None:
            assert isinstance(h.error, RequestError)
        else:
            assert h.reply["tokens"] == h.request.out_tokens
    # every slot and block recycled for the next example
    assert eng.active == 0 and eng.waiting == 0
    eng.pool.check()
    assert eng.pool.available == eng.pool.total
    assert not loop._live and not loop._intake and not loop._cancels


# ------------------------------------------------- robustness / service
def test_replica_kill_mid_stream_is_service_error(stack):
    """Supervisor-style kill (set_up(False)) mid-stream: the open stream
    surfaces a retryable ServiceError, fresh requests fail over to the
    healthy replica, and the service stays up."""
    cfg, model, params = stack
    svc = make_lm_service("lm_kill", model, params, n_replicas=2,
                          batch_size=2, max_seq=MAX_SEQ,
                          with_backup=False)
    svc.start()
    rep0 = svc.replicas[0]
    got = []
    handle = rep0.handler.submit({"prompt": [5, 6, 7],
                                  "max_new_tokens": 8,
                                  "on_token": lambda t, lp: got.append(t)})
    loop = rep0.handler.loop
    while len(got) < 2:
        loop.run_once()
    rep0.set_up(False)                    # kill → abort in-flight streams
    with pytest.raises(ServiceError, match="abort"):
        loop.wait(handle)
    assert 2 <= len(got) < 8              # stream stopped mid-flight
    # untouched requests route around the dead replica
    out = svc({"prompt": [5, 6, 7], "max_new_tokens": 2})
    assert out["replica"] == "lm_kill/1"
    assert len(out["tokens"]) == 2


def test_balancer_does_not_retry_after_first_streamed_token():
    """Once a token reached the client, a replica failure must NOT
    replay the request elsewhere (the client would see a duplicated
    prefix) — but it still counts against the replica's health."""
    calls = []

    def flaky(payload):
        calls.append("flaky")
        payload["on_token"](7, -0.5)
        raise ServiceError("died mid-stream")

    def healthy(payload):
        calls.append("healthy")
        return {"tokens": [1]}

    svc = Service("s", replicas=[Replica("a", flaky),
                                 Replica("b", healthy)])
    deploy(svc)
    svc.start()
    got = []
    with pytest.raises(ServiceError, match="not retrying"):
        svc({"on_token": lambda t, lp: got.append(t)})
    assert got == [7]
    assert calls == ["flaky"]             # no replay on the healthy one
    assert svc.balancer.stats["failovers"] == 1   # health still charged
    # a failure BEFORE any token still fails over as always
    assert svc({"on_token": lambda t, lp: None}) == {"tokens": [1]}
    assert calls[-1] == "healthy"


def test_client_disconnect_mid_stream_never_poisons_health(stack):
    """A callback that raises is the CLIENT hanging up: RequestError,
    zero failovers, and the replica keeps serving."""
    cfg, model, params = stack
    svc = make_lm_service("lm_disc", model, params, n_replicas=1,
                          batch_size=2, max_seq=MAX_SEQ)
    svc.start()

    def hangup(tok, lp):
        raise BrokenPipeError("peer reset")

    with pytest.raises(RequestError, match="disconnected"):
        svc({"prompt": [5, 6, 7], "max_new_tokens": 4,
             "on_token": hangup})
    assert svc.balancer.stats["failovers"] == 0
    rep = svc.replicas[0].handler
    assert rep.scheduler.engine.metrics["cancelled"] == 1
    out = svc({"prompt": [5, 6, 7], "max_new_tokens": 2})
    assert len(out["tokens"]) == 2        # slot recycled, replica healthy


def test_streaming_through_service_matches_reply(stack):
    """The on_token payload path through Service → balancer → replica
    delivers exactly the reply's tokens, in order."""
    cfg, model, params = stack
    svc = make_lm_service("lm_stream", model, params, n_replicas=1,
                          batch_size=2, max_seq=MAX_SEQ)
    svc.start()
    got = []
    out = svc({"prompt": [5, 6, 7], "max_new_tokens": 5,
               "on_token": lambda t, lp: got.append((t, lp))})
    assert [t for t, _ in got] == out["tokens"]
    assert [lp for _, lp in got] == out["logprobs"]
    assert len(got) == 5


# ------------------------------------------------------------- asyncio
def test_asyncio_stream_front_end_interleaves(stack):
    """Two concurrent asyncio streams over one loop interleave token
    delivery and both match the engine's streams (runs under
    PYTHONASYNCIODEBUG=1 in CI to catch un-awaited coroutines)."""
    import asyncio

    cfg, model, params = stack
    eng, sched, loop, vc = _build_loop(model, params, batch_size=2)
    pa, pb = _prompts(cfg, [5, 7], seed=9)
    order = []

    async def consume(rid, prompt):
        toks = []
        async for tok, lp in loop.stream(
                Request(rid=rid, prompt=list(prompt), max_new_tokens=4)):
            toks.append(tok)
            order.append(rid)
        return toks

    ta, tb = asyncio.run(_gather_two(consume(1, pa), consume(2, pb)))
    reqs = {1: ta, 2: tb}
    for rid, toks in reqs.items():
        assert len(toks) == 4
    # delivery interleaved rather than one stream fully first
    assert order != sorted(order)


async def _gather_two(a, b):
    import asyncio
    return await asyncio.gather(a, b)


def test_threaded_loop_serves_without_polling_sleeps(stack):
    """The daemon-thread pump is event-woken: submit → wait round-trips
    without the test (or the loop) ever sleeping on a timer."""
    cfg, model, params = stack
    eng, sched, loop, vc = _build_loop(model, params, batch_size=2)
    loop.start()
    try:
        (p,) = _prompts(cfg, [6], seed=10)
        h = loop.submit(Request(rid=1, prompt=p, max_new_tokens=3))
        reply = loop.wait(h)
        assert len(reply["tokens"]) == 3
    finally:
        loop.stop()
    assert eng.pool.available == eng.pool.total


def test_dispatched_tick_commits_exactly_once(stack):
    cfg, model, params = stack
    eng = ServingEngine(model, params, batch_size=2, max_seq=MAX_SEQ)
    (p,) = _prompts(cfg, [5], seed=11)
    assert eng.add_requests([Request(rid=1, prompt=p,
                                     max_new_tokens=1)]) == 1
    tick = eng.dispatch_step()
    done = tick.commit()
    assert [r.rid for r in done] == [1]
    with pytest.raises(RuntimeError, match="already committed"):
        tick.commit()
