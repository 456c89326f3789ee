"""Chained decode steps: where the engine knows the next decode step from
its host state already (every slot decoding, none ending on the step in
flight by its length), the async loop launches that step before the one
in flight commits, fed by its sampled tokens on the device
(``ServingEngine.dispatch_next``). What it launches must be exactly the
step the host would have launched after the commit: the streams equal
the synchronous drain's, on dense and on layer-pattern (state-row)
models, with the kernel and without, with stop tokens that end a row
inside a chain and with cancels that wait for it.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.drivers.hybrid_serve import program_config  # noqa: E402
from bench.reference import granite_hybrid  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serve.async_loop import AsyncServeLoop  # noqa: E402
from repro.serve.clock import VirtualClock  # noqa: E402
from repro.serve.engine import Request, ServingEngine  # noqa: E402
from repro.serve.sampling import SamplingParams  # noqa: E402
from repro.serve.scheduler import Scheduler  # noqa: E402

MAX_SEQ = 64
# a small Granite-style hybrid: two periods of [m, m, a]
HYBRID = {
    "name": "granite-small", "source": "test", "program_arch":
    "granite-4.0-h-micro", "torch_dtype": "float32",
    "num_hidden_layers": 6,
    "layer_types": ["mamba", "mamba", "attention"] * 2,
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 256, "shared_intermediate_size": 256,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "position_embedding_type": "nope",
    "mamba_n_heads": 8, "mamba_d_head": 64, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 8,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.015625, "logits_scaling": 8,
}


@pytest.fixture(scope="module")
def dense():
    cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                              dtype=jnp.float32)
    model = build_model(cfg)
    return model, model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def hybrid():
    model = build_model(program_config(HYBRID))
    return model, granite_hybrid.weights(HYBRID, 11)


def _requests(vocab, n=6, stop=None):
    rng = np.random.default_rng(5)
    out = []
    for i in range(n):
        samp = SamplingParams() if i % 2 == 0 else SamplingParams(
            temperature=0.8, top_k=20, seed=i)
        out.append(Request(rid=i, prompt=rng.integers(
            2, vocab, int(rng.integers(4, 20))).tolist(),
            max_new_tokens=int(rng.integers(9, 16)), sampling=samp,
            stop_tokens=(stop or {}).get(i, ())))
    return out


def _engine(model, params, **kw):
    return ServingEngine(model, params, batch_size=3, max_seq=MAX_SEQ,
                         block_size=8, prefill_chunk=8, **kw)


def _serve(eng, reqs, cancel_after=None):
    """Every request submitted at once (more than the slots), the loop
    pumped to the end. ``cancel_after``: (rid, n) cancels ``rid`` once
    it has streamed ``n`` tokens. Returns (replies, streams, loop)."""
    vc = VirtualClock()
    loop = AsyncServeLoop(Scheduler(eng, clock=vc))
    streams = {r.rid: [] for r in reqs}
    handles = {r.rid: loop.submit(
        r, lambda t, lp, rid=r.rid: streams[rid].append((t, lp)))
        for r in reqs}
    for _ in range(2000):
        if all(h.done for h in handles.values()):
            break
        if cancel_after:
            rid, n = cancel_after
            if len(streams[rid]) >= n and not handles[rid].done:
                handles[rid].cancel()
        loop.run_once()
        vc.advance(0.01)
    assert all(h.done for h in handles.values())
    return {k: h.reply for k, h in handles.items()}, streams, loop


CASES = [("dense", False), ("dense", True), ("hybrid", False),
         ("hybrid", True)]


@pytest.mark.parametrize("family,use_kernel", CASES,
                         ids=[f"{f}-{'kernel' if k else 'gather'}"
                              for f, k in CASES])
def test_chained_streams_equal_the_sync_drain(dense, hybrid, family,
                                              use_kernel):
    """Six requests on three slots, half sampled, prompts chunked: the
    loop chains most decode steps, and every request streams exactly
    the synchronous drain's tokens (logprobs to 2e-5, the streaming
    contract's float tolerance: admissions may batch differently)."""
    model, params = dense if family == "dense" else hybrid
    vocab = model.cfg.vocab_size
    eng = _engine(model, params, use_kernel=use_kernel)
    replies, streams, _ = _serve(eng, _requests(vocab))
    ref = {r.rid: r for r in _engine(model, params, use_kernel=use_kernel)
           .run(_requests(vocab))}
    assert eng.metrics["chained_steps"] > eng.metrics["decode_steps"] // 3
    for rid, reply in replies.items():
        toks = [t for t, _ in streams[rid]]
        assert toks == reply["tokens"] == ref[rid].out_tokens, rid
        np.testing.assert_allclose([lp for _, lp in streams[rid]],
                                   ref[rid].out_logprobs, rtol=2e-5,
                                   atol=2e-5)
    eng.pool.check()
    assert eng.pool.available == eng.pool.total
    if eng.state_keys:
        assert eng.metrics["state_rows_stepped"] <= \
            eng.B * eng.metrics["decode_steps"]


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_stop_token_ends_a_row_inside_a_chain(dense, hybrid, family):
    """A stop token that the engine cannot see coming: the row rides the
    step launched after it, whose token for it is dropped; its slot is
    reused afterwards and every stream equals the synchronous drain's
    with the same stop token."""
    model, params = dense if family == "dense" else hybrid
    vocab = model.cfg.vocab_size
    free = {r.rid: r.out_tokens for r in _engine(model, params)
            .run(_requests(vocab))}
    toks = free[0]
    j = next(j for j in range(3, len(toks) - 2)
             if toks[j] not in toks[:j])
    stop = {0: (toks[j],)}
    eng = _engine(model, params)
    replies, streams, _ = _serve(eng, _requests(vocab, stop=stop))
    ref = {r.rid: r.out_tokens for r in _engine(model, params)
           .run(_requests(vocab, stop=stop))}
    assert replies[0]["tokens"] == toks[:j + 1] == ref[0]
    for rid, reply in replies.items():
        assert [t for t, _ in streams[rid]] == reply["tokens"] == ref[rid]
    assert eng.metrics["stop_token_exits"] == 1
    assert eng.metrics["chained_steps"] > 0
    eng.pool.check()
    assert eng.pool.available == eng.pool.total


def test_dispatch_next_refuses_what_it_cannot_know(dense):
    """No chained step while a slot is free (an admission may come), or
    when a row ends on the step in flight by its length; otherwise the
    chained step's tokens are the next step's."""
    model, params = dense

    def admitted(n_new):
        eng = ServingEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                            block_size=8)
        reqs = [Request(rid=i, prompt=list(range(3 + i, 9 + i)),
                        max_new_tokens=k) for i, k in enumerate(n_new)]
        assert eng.add_requests(reqs) == len(reqs)
        return eng, reqs

    eng, _ = admitted([6])                       # one slot free
    tick = eng.dispatch_step()
    assert eng.dispatch_next(tick) is None
    tick.commit()
    eng, _ = admitted([6, 2])       # row 1 ends on the step in flight
    tick = eng.dispatch_step()
    assert eng.dispatch_next(tick) is None
    tick.commit()
    eng, reqs = admitted([6, 6])
    first = eng.dispatch_step()
    second = eng.dispatch_next(first)
    assert second is not None and eng.metrics["chained_steps"] == 1
    first.commit()
    second.commit()
    ref, ref_reqs = admitted([6, 6])
    ref.step()
    ref.step()
    assert [r.out_tokens for r in reqs] == \
        [r.out_tokens for r in ref_reqs]
    assert len(reqs[0].out_tokens) == 3


def test_cancel_waits_for_the_chained_step(dense):
    """A cancel that arrives while a chained step is in flight is applied
    at the next loop boundary: the cancelled stream is a prefix of the
    drain's, the others equal it, and the pool drains clean."""
    model, params = dense
    vocab = model.cfg.vocab_size
    eng = _engine(model, params)
    replies, streams, loop = _serve(eng, _requests(vocab),
                                    cancel_after=(1, 4))
    ref = {r.rid: r.out_tokens for r in _engine(model, params)
           .run(_requests(vocab))}
    got = replies[1]["tokens"]
    assert 4 <= len(got) < len(ref[1]) and got == ref[1][:len(got)]
    for rid in (0, 2, 3, 4, 5):
        assert replies[rid]["tokens"] == ref[rid]
    assert eng.metrics["cancelled"] == 1 and loop._ahead is None
    eng.pool.check()
    assert eng.pool.available == eng.pool.total
