"""Speculative draft-and-verify decode: the multi-token verify path's
differential property (verify(k) == k+1 sequential decode steps), the
engine-level greedy bit-identity grid, rollback safety under churn
(including the co-holder-KV hypothesis property), and the serving knobs."""
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
from repro.models.model import build_model
from repro.serve.engine import Request, ServingEngine
from repro.serve.sampling import SamplingParams


@pytest.fixture(scope="module")
def stack():
    cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                              dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _reqs(cfg, lens, max_new=6, seed=1, **kw):
    rng = jax.random.key(seed)
    out = []
    for i, L in enumerate(lens):
        rng, k = jax.random.split(rng)
        out.append(Request(rid=i, max_new_tokens=max_new,
                           prompt=jax.random.randint(
                               k, (L,), 2, cfg.vocab_size).tolist(), **kw))
    return out


def _shared_reqs(cfg, n, prefix_len=20, suffix_len=3, max_new=6, seed=5,
                 **kw):
    rng = jax.random.key(seed)
    rng, k = jax.random.split(rng)
    common = jax.random.randint(k, (prefix_len,), 2, cfg.vocab_size).tolist()
    out = []
    for i in range(n):
        rng, k = jax.random.split(rng)
        sfx = jax.random.randint(k, (suffix_len,), 2,
                                 cfg.vocab_size).tolist()
        out.append(Request(rid=i, prompt=common + sfx, max_new_tokens=max_new,
                           **kw))
    return out


# =============================================== verify-path differential
def _prefill_stripe(model, params, toks, capacity):
    cache = model.init_cache(toks.shape[0], capacity)
    _, pref = model.prefill(params, {"tokens": toks})
    for key in cache:
        cache[key] = jax.lax.dynamic_update_slice(
            cache[key], pref[key].astype(cache[key].dtype), (0,) * 5)
    return cache


def _seq_logits(model, params, win, cache, lens, **kw):
    """k+1 sequential decode_steps — the oracle the verify step must
    reproduce."""
    outs = []
    for j in range(win.shape[1]):
        lg, cache = model.decode_step(params, win[:, j:j + 1], cache,
                                      lens + j, **kw)
        outs.append(lg[:, 0])
    return jnp.stack(outs, axis=1), cache


def test_verify_matches_sequential_decode_stripe(stack):
    """f32-tight: one q_len=k+1 verify == k+1 single-token steps, logits
    AND resulting cache, every row at its own length."""
    cfg, model, params = stack
    B, P, S = 3, 9, 4
    toks = jax.random.randint(jax.random.key(1), (B, P), 2, cfg.vocab_size)
    win = jax.random.randint(jax.random.key(2), (B, S), 2, cfg.vocab_size)
    lens = jnp.full((B,), P, jnp.int32)
    seq, c_seq = _seq_logits(model, params, win,
                             _prefill_stripe(model, params, toks, 32), lens)
    ver, c_ver = model.verify_step(params, win,
                                   _prefill_stripe(model, params, toks, 32),
                                   lens)
    np.testing.assert_allclose(np.asarray(ver), np.asarray(seq),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(jnp.argmax(ver, -1))
                  == np.asarray(jnp.argmax(seq, -1)))
    for key in c_seq:
        np.testing.assert_allclose(np.asarray(c_ver[key]),
                                   np.asarray(c_seq[key]),
                                   rtol=2e-5, atol=2e-5)


def test_verify_matches_sequential_decode_bf16(stack):
    """Same property at bf16 storage precision, looser tolerance."""
    cfg, _, _ = stack
    cfg16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    model = build_model(cfg16)
    params = model.init(jax.random.key(0))
    B, P, S = 2, 7, 3
    toks = jax.random.randint(jax.random.key(3), (B, P), 2, cfg.vocab_size)
    win = jax.random.randint(jax.random.key(4), (B, S), 2, cfg.vocab_size)
    lens = jnp.full((B,), P, jnp.int32)
    seq, _ = _seq_logits(model, params, win,
                         _prefill_stripe(model, params, toks, 32), lens)
    ver, _ = model.verify_step(params, win,
                               _prefill_stripe(model, params, toks, 32),
                               lens)
    np.testing.assert_allclose(np.asarray(ver, np.float32),
                               np.asarray(seq, np.float32),
                               rtol=2e-2, atol=2e-2)


def _paged_setup(model, params, toks, bs, num_blocks):
    """Prefill into a block pool; returns (cache, table, lens)."""
    B, P = toks.shape
    cache = model.init_paged_cache(num_blocks, bs)
    _, pref = model.prefill(params, {"tokens": toks})
    n_blk = -(-P // bs)
    table = np.zeros((B, num_blocks), np.int32)
    nxt = 1
    for b in range(B):
        for i in range(n_blk):
            table[b, i] = nxt
            lo, hi = i * bs, min((i + 1) * bs, P)
            for key in cache:
                # token-major prefill rows into the head-major pool
                # block (L, Hkv / f, bs, hd * f), f heads to a row
                blk = pref[key][:, b, lo:hi]
                blk = blk.reshape(blk.shape[:2] + cache[key].shape[2::2])
                cache[key] = cache[key].at[:, nxt, :, : hi - lo].set(
                    jnp.swapaxes(blk, 1, 2).astype(cache[key].dtype))
            nxt += 1
    return cache, jnp.asarray(table), jnp.full((B,), P, jnp.int32)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel"])
def test_verify_matches_sequential_decode_paged(stack, use_kernel):
    """The paged verify (jnp gather AND the fused multi-token Pallas
    window kernel — ONE launch for the whole verify window, interpret
    mode on CPU) against sequential paged decode."""
    cfg, model, params = stack
    B, P, S, bs = 2, 10, 3, 4
    toks = jax.random.randint(jax.random.key(5), (B, P), 2, cfg.vocab_size)
    win = jax.random.randint(jax.random.key(6), (B, S), 2, cfg.vocab_size)
    cache, table, lens = _paged_setup(model, params, toks, bs,
                                      num_blocks=16)
    seq, _ = _seq_logits(model, params, win, cache, lens,
                         block_table=table, paged_kernel=use_kernel)
    cache2, table2, _ = _paged_setup(model, params, toks, bs,
                                     num_blocks=16)
    ver, _ = model.verify_step(params, win, cache2, lens,
                               block_table=table2, paged_kernel=use_kernel)
    np.testing.assert_allclose(np.asarray(ver), np.asarray(seq),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(jnp.argmax(ver, -1))
                  == np.asarray(jnp.argmax(seq, -1)))


def test_verify_shared_prefix_blocks_and_scratch_diversion(stack):
    """Two rows whose tables alias the SAME physical prefix blocks: the
    verify window must read through the shared blocks correctly, and a
    row with a zero n_write (a rider) must leave every owned block
    byte-identical — its scatter is diverted to scratch."""
    cfg, model, params = stack
    P, S, bs = 8, 3, 4
    tok_row = jax.random.randint(jax.random.key(7), (1, P), 2,
                                 cfg.vocab_size)
    toks = jnp.concatenate([tok_row, tok_row], axis=0)     # same prompt
    win = jax.random.randint(jax.random.key(8), (2, S), 2, cfg.vocab_size)
    cache, _, lens = _paged_setup(model, params, tok_row, bs, num_blocks=16)
    # both rows read blocks 1..2 (the shared prefix); each owns one tail
    table = np.zeros((2, 16), np.int32)
    table[0, :2] = [1, 2]
    table[1, :2] = [1, 2]
    table[0, 2] = 3                                        # row 0's tail
    table[1, 2] = 4                                        # row 1's tail
    ver, cache2 = model.verify_step(
        params, win, {k: v for k, v in cache.items()}, lens,
        block_table=jnp.asarray(table),
        n_write=jnp.asarray([S, 0], jnp.int32))            # row 1 rides
    # row 1's "owned" block 4 untouched; shared prefix blocks untouched
    for key in cache:
        np.testing.assert_array_equal(np.asarray(cache2[key][:, 4]),
                                      np.asarray(cache[key][:, 4]))
        np.testing.assert_array_equal(np.asarray(cache2[key][:, 1:3]),
                                      np.asarray(cache[key][:, 1:3]))
    # row 0 (writing) equals its sequential oracle at every position;
    # row 1's outputs are only valid at position 0 (rider semantics)
    cache3, table3, _ = _paged_setup(model, params, tok_row, bs,
                                     num_blocks=16)
    seq, _ = _seq_logits(model, params, win[:1], cache3,
                         jnp.full((1,), P, jnp.int32), block_table=table3)
    np.testing.assert_allclose(np.asarray(ver[0]), np.asarray(seq[0]),
                               rtol=2e-5, atol=2e-5)


def test_verify_rejects_recurrent_families(stack):
    cfg = dataclasses.replace(get_config("rwkv6-1.6b").reduced(),
                              dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache = model.init_cache(1, 16)
    with pytest.raises(ValueError, match="unsupported for family"):
        model.verify_step(params, jnp.ones((1, 3), jnp.int32), cache,
                          jnp.asarray([4], jnp.int32))


# ========================================== engine greedy bit-identity grid
GRID = [
    dict(paged=True, block_size=8),
    dict(paged=True, block_size=8, use_kernel=True),
    dict(paged=True, block_size=4, num_blocks=12),      # tight pool
    dict(paged=False),
]
GRID_IDS = ["paged", "kernel", "tight-pool", "stripe"]


@pytest.mark.parametrize("cfg_kw", GRID, ids=GRID_IDS)
def test_greedy_spec_streams_bit_identical(stack, cfg_kw):
    """THE acceptance regression: greedy speculative decode emits
    bit-identical streams to non-speculative decode — mixed lengths,
    every engine config, a self-draft (high acceptance) AND a
    different-weights draft (near-zero acceptance)."""
    cfg, model, params = stack
    lens = [5, 11, 7, 14]
    base = _reqs(cfg, lens)
    e0 = ServingEngine(model, params, batch_size=4, max_seq=64, **cfg_kw)
    e0.run(list(base))
    for tag, dparams in (("self", params),
                         ("cold", model.init(jax.random.key(9)))):
        spec = _reqs(cfg, lens)
        e1 = ServingEngine(model, params, batch_size=4, max_seq=64,
                           draft_model=model, draft_params=dparams,
                           speculation=3, **cfg_kw)
        e1.run(list(spec))
        for a, b in zip(base, spec):
            assert a.out_tokens == b.out_tokens, (tag, a.rid)
            np.testing.assert_allclose(a.out_logprobs, b.out_logprobs,
                                       rtol=1e-5, atol=1e-5)
        assert e1.metrics["verify_steps"] > 0
        if e1.paged:
            assert e1.pool.available == e1.pool.total
            e1.pool.check()
    # the self-draft actually speculates: >1 token per target step
    assert e1.metrics["spec_proposed"] > 0


def test_greedy_spec_shared_prefix_streams(stack):
    """Greedy bit-identity through prefix sharing: shared admissions,
    catch-up riders, and CoW all compose with speculation."""
    cfg, model, params = stack
    a = _shared_reqs(cfg, 4)
    b = _shared_reqs(cfg, 4)
    e0 = ServingEngine(model, params, batch_size=4, max_seq=64,
                       paged=True, block_size=8, prefix_sharing=True)
    e1 = ServingEngine(model, params, batch_size=4, max_seq=64,
                       paged=True, block_size=8, prefix_sharing=True,
                       draft_model=model, draft_params=params,
                       speculation=3)
    e0.run(list(a))
    e1.run(list(b))
    for x, y in zip(a, b):
        assert x.out_tokens == y.out_tokens, x.rid
    assert e1.metrics["shared_admissions"] >= 1
    assert e1.metrics["spec_accepted"] > 0
    assert e1.pool.available == e1.pool.total
    e1.pool.check()


def test_self_draft_accepts_everything_and_multiplies_tokens(stack):
    """A draft with the target's own weights proposes the target argmax:
    greedy acceptance is total, so tokens per target step ~ k+1."""
    cfg, model, params = stack
    eng = ServingEngine(model, params, batch_size=2, max_seq=64,
                        paged=True, block_size=8, draft_model=model,
                        draft_params=params, speculation=3)
    reqs = _reqs(cfg, [6, 9], max_new=8)
    eng.run(list(reqs))
    m = eng.metrics
    assert m["spec_accepted"] == m["spec_proposed"] > 0
    emitted = sum(len(r.out_tokens) for r in reqs)
    # prefill emits one per request; every verify step nets > 1 token
    assert (emitted - len(reqs)) / m["decode_steps"] > 1.0


def test_spec_rollback_returns_watermark_blocks(stack):
    """A rejecting draft makes the engine allocate window blocks and
    roll them back: the pool never leaks and streams stay correct."""
    cfg, model, params = stack
    cold = model.init(jax.random.key(11))
    eng = ServingEngine(model, params, batch_size=1, max_seq=64,
                        paged=True, block_size=4, draft_model=model,
                        draft_params=cold, speculation=3)
    (req,) = _reqs(cfg, [7], max_new=10)
    eng.run([req])
    m = eng.metrics
    assert m["spec_blocks_rolled_back"] > 0
    assert eng.pool.available == eng.pool.total
    eng.pool.check()
    base = ServingEngine(model, params, batch_size=1, max_seq=64,
                         paged=True, block_size=4)
    (d,) = base.run([Request(rid=100, prompt=list(req.prompt),
                             max_new_tokens=10)])
    assert d.out_tokens == req.out_tokens


def test_per_request_speculation_opt_out(stack):
    """Request.speculation=0 rides every verify batch non-speculatively;
    its stream is still the plain greedy stream."""
    cfg, model, params = stack
    lens = [6, 8]
    base = _reqs(cfg, lens)
    ServingEngine(model, params, batch_size=2, max_seq=64).run(list(base))
    spec = _reqs(cfg, lens)
    spec[1].speculation = 0
    eng = ServingEngine(model, params, batch_size=2, max_seq=64,
                        draft_model=model, draft_params=params,
                        speculation=3)
    eng.run(list(spec))
    for a, b in zip(base, spec):
        assert a.out_tokens == b.out_tokens, a.rid
    # the opted-out request emitted one token per step: its stream is as
    # long as the opted-in one but took proportionally more steps
    assert eng.metrics["spec_proposed"] > 0


def test_speculation_validation(stack):
    cfg, model, params = stack
    with pytest.raises(ValueError, match="draft model"):
        ServingEngine(model, params, batch_size=1, max_seq=32,
                      speculation=2)
    mo_cfg = dataclasses.replace(get_config("grok-1-314b").reduced(),
                                 dtype=jnp.float32)
    mo = build_model(mo_cfg)
    mo_params = mo.init(jax.random.key(0))
    with pytest.raises(ValueError, match="MoE"):
        ServingEngine(mo, mo_params, batch_size=1, max_seq=32,
                      draft_model=mo, draft_params=mo_params, speculation=2)
    r_cfg = dataclasses.replace(get_config("rwkv6-1.6b").reduced(),
                                dtype=jnp.float32)
    rm = build_model(r_cfg)
    rp = rm.init(jax.random.key(0))
    with pytest.raises(ValueError, match="pure-attention"):
        ServingEngine(rm, rp, batch_size=1, max_seq=32, draft_model=model,
                      draft_params=params, speculation=2)
    # a recurrent DRAFT is rejected too: the runner's rollback is
    # truncate-only stripe semantics, which recurrent state cannot obey
    cfg_ok = dataclasses.replace(get_config("qwen3-4b").reduced(),
                                 dtype=jnp.float32)
    tm = build_model(cfg_ok)
    with pytest.raises(ValueError, match="draft model"):
        ServingEngine(tm, tm.init(jax.random.key(0)), batch_size=1,
                      max_seq=32, draft_model=rm, draft_params=rp,
                      speculation=2)


def test_blocks_needed_charges_spec_watermark(stack):
    """The scheduler's block gate must include the speculative window,
    or a fill batch admits and instantly mass-parks."""
    cfg, model, params = stack
    plain = ServingEngine(model, params, batch_size=2, max_seq=64,
                          paged=True, block_size=4)
    spec = ServingEngine(model, params, batch_size=2, max_seq=64,
                         paged=True, block_size=4, draft_model=model,
                         draft_params=params, speculation=3)
    (r,) = _reqs(cfg, [8], max_new=8)
    # 8 tokens = 2 blocks; the k+1=4-token window adds one more
    assert plain.blocks_needed(r) == 2
    assert spec.blocks_needed(r) == 3
    r2 = Request(rid=9, prompt=[3] * 8, max_new_tokens=8, speculation=0)
    assert spec.blocks_needed(r2) == 2       # opted out: no watermark


def test_scheduler_drains_speculative_engine(stack):
    from repro.serve.scheduler import Scheduler
    cfg, model, params = stack
    eng = ServingEngine(model, params, batch_size=2, max_seq=64,
                        paged=True, block_size=8, draft_model=model,
                        draft_params=params, speculation=2)
    sched = Scheduler(eng, policy="fifo")
    reqs = _reqs(cfg, [5, 9, 7], max_new=5)
    for r in reqs:
        assert sched.submit(r)
    done = sched.drain()
    assert len(done) == 3
    assert eng.metrics["verify_steps"] > 0
    assert eng.pool.available == eng.pool.total


def test_sampled_spec_reproducible_and_exhaustive(stack):
    """Sampled speculative decode: streams reproduce run-to-run (counter
    keys), logprobs ride along, and every request completes."""
    cfg, model, params = stack
    sp = SamplingParams(temperature=0.8, top_k=12, seed=17)
    outs = []
    for _ in range(2):
        reqs = _reqs(cfg, [6, 9], max_new=7, sampling=sp)
        eng = ServingEngine(model, params, batch_size=2, max_seq=64,
                            paged=True, block_size=8, draft_model=model,
                            draft_params=params, speculation=3)
        eng.run(list(reqs))
        outs.append([r.out_tokens for r in reqs])
        for r in reqs:
            assert len(r.out_tokens) == 7
            assert len(r.out_logprobs) == 7
            assert all(np.isfinite(r.out_logprobs))
    assert outs[0] == outs[1]


# =========================================== rollback churn property
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10 ** 6),
       block_size=st.sampled_from([4, 8]),
       spec_k=st.integers(min_value=1, max_value=4))
def test_property_spec_rollback_never_corrupts_coholder(stack, seed,
                                                        block_size, spec_k):
    """Hypothesis churn: shared-prefix requests under a TIGHT pool with
    speculation on — CoW, parking, preemption, watermark growth and
    rollback all interleave. Whatever happens, every request's stream
    must equal its uncontended solo run (no co-holder's KV was ever
    touched) and the pool must drain clean."""
    cfg, model, params = stack
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 4))
    reqs = _shared_reqs(cfg, n, prefix_len=int(rng.integers(6, 14)),
                        suffix_len=int(rng.integers(1, 4)),
                        max_new=int(rng.integers(4, 10)),
                        seed=int(rng.integers(0, 2 ** 31)))
    num_blocks = int(rng.integers(7, 13))
    eng = ServingEngine(model, params, batch_size=n, max_seq=64,
                        paged=True, block_size=block_size,
                        num_blocks=num_blocks, prefix_sharing=True,
                        draft_model=model, draft_params=params,
                        speculation=spec_k)
    done = eng.run(list(reqs))
    assert len(done) == len(reqs)
    assert eng.pool.available == eng.pool.total
    eng.pool.check()
    for r in reqs:
        solo = ServingEngine(model, params, batch_size=1, max_seq=64,
                             paged=True, block_size=block_size,
                             prefix_sharing=False)
        (d,) = solo.run([Request(rid=100 + r.rid, prompt=list(r.prompt),
                                 max_new_tokens=r.max_new_tokens)])
        assert d.out_tokens == r.out_tokens, r.rid


# ================================================= logprobs + cached reuse
def test_logprobs_match_manual_log_softmax(stack):
    """The streamed logprob of a greedy token is the raw log-softmax at
    that token — checked against a hand prefill."""
    cfg, model, params = stack
    (req,) = _reqs(cfg, [6], max_new=3)
    eng = ServingEngine(model, params, batch_size=1, max_seq=64)
    eng.run([req])
    toks = jnp.asarray([req.prompt], jnp.int32)
    logits, _ = model.prefill(params, {"tokens": toks})
    ref = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
    assert len(req.out_logprobs) == len(req.out_tokens) == 3
    assert req.out_logprobs[0] == pytest.approx(
        float(ref[req.out_tokens[0]]), rel=1e-5)
    assert int(jnp.argmax(logits[0, -1])) == req.out_tokens[0]


def test_sequential_identical_prompts_reuse_cached_blocks(stack):
    """Back-to-back identical prompts (the second submitted AFTER the
    first completed and freed its blocks) still share: the freed chain's
    index entries survive until the memory is recycled, so the second
    admission revives the blocks instead of recomputing the prefill."""
    cfg, model, params = stack
    eng = ServingEngine(model, params, batch_size=2, max_seq=64,
                        paged=True, block_size=8, prefix_sharing=True)
    (first,) = _reqs(cfg, [20], max_new=4, seed=9)
    eng.run([first])
    assert eng.pool.available == eng.pool.total      # all freed...
    assert eng.pool.cached > 0                       # ...but still indexed
    second = Request(rid=10, prompt=list(first.prompt), max_new_tokens=4)
    eng.run([second])
    assert eng.metrics["shared_admissions"] == 1
    assert eng.metrics["prefill_tokens_shared"] >= 16
    assert second.out_tokens == first.out_tokens
    eng.pool.check()
    # sanity: sharing-off never matches across retirement
    off = ServingEngine(model, params, batch_size=2, max_seq=64,
                        paged=True, block_size=8, prefix_sharing=False)
    (a,) = _reqs(cfg, [20], max_new=4, seed=9)
    off.run([a])
    b = Request(rid=11, prompt=list(a.prompt), max_new_tokens=4)
    off.run([b])
    assert off.metrics["shared_admissions"] == 0
    assert b.out_tokens == a.out_tokens


def test_revived_blocks_not_double_counted_in_batch_planning(stack):
    """Admission planning charges a cached-block revival once: after the
    planning-time acquire moves the block off the free list, `planned`
    must drop it, or a same-batch follower is gated out of a pool that
    actually has room."""
    cfg, model, params = stack
    # pool of exactly 7: request A uses 3 blocks (20 tokens / bs 8),
    # retires, leaves them cached. Then one batch: A' (revives 2 shared
    # + needs ~2) and B (2 blocks + reserve) — fits ONLY if the revived
    # blocks are not counted both in planned and out of available.
    eng = ServingEngine(model, params, batch_size=4, max_seq=64,
                        paged=True, block_size=8, num_blocks=8,
                        prefix_sharing=True)
    (first,) = _reqs(cfg, [20], max_new=2, seed=13)
    eng.run([first])
    assert eng.pool.cached == 3
    again = Request(rid=20, prompt=list(first.prompt), max_new_tokens=2)
    (other,) = _reqs(cfg, [12], max_new=2, seed=14)
    other.rid = 21
    assert eng.add_requests([again, other]) == 2   # both admitted together
    done = eng.run([])
    assert len(done) == 2
    assert eng.metrics["shared_admissions"] == 1
    eng.pool.check()


def test_sampled_opt_out_stream_independent_of_neighbors(stack):
    """A sampled request that opts out of speculation must emit the same
    stream whether its co-batched neighbor speculates or not: riders
    draw from the TOKEN stream at the plain-step counter, never from the
    verify batch's accept stream."""
    cfg, model, params = stack
    sp = SamplingParams(temperature=0.8, top_k=10, seed=31)
    (plain,) = _reqs(cfg, [6], max_new=6, seed=2, sampling=sp)
    ServingEngine(model, params, batch_size=2, max_seq=64,
                  paged=True, block_size=8).run([plain])
    (rider,) = _reqs(cfg, [6], max_new=6, seed=2, sampling=sp)
    rider.speculation = 0
    (neighbor,) = _reqs(cfg, [9], max_new=6, seed=3)
    neighbor.rid = 50
    eng = ServingEngine(model, params, batch_size=2, max_seq=64,
                        paged=True, block_size=8, draft_model=model,
                        draft_params=params, speculation=3)
    eng.run([rider, neighbor])
    assert eng.metrics["spec_proposed"] > 0      # the neighbor speculated
    assert rider.out_tokens == plain.out_tokens
