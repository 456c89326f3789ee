"""Granite-4.0-H-style hybrid (Mamba-2 + GQA attention, Granite's
multipliers) at a small size on the CPU, with seeded weights: the
chunked SSD scan against the sequential recurrence, right-padding that
leaves a row's state exactly at its true length, the serving engine's
prefill + chunk windows with carried state + paged decode against the
benchmark's plain float32 reference (``bench/reference/granite_hybrid``),
and the pattern stack against the one-kind stack it generalises.
"""
import copy
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the benchmark's reference and cell module live in bench/ at the repo root
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.drivers.hybrid_serve import program_config  # noqa: E402
from bench.reference import granite_hybrid  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.models import mamba2  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serve.engine import Request, ServingEngine  # noqa: E402
from repro.serve.sampling import SamplingParams  # noqa: E402

# two periods of [m, m, a]; 4 Mamba-2 heads x 16, state 16, chunk 8;
# attention heads of 64 on 2 KV heads, so the paged pool lane-packs two
# heads to a 128-lane row
HF = {
    "name": "granite-small", "source": "test", "program_arch":
    "granite-4.0-h-micro", "torch_dtype": "float32",
    "num_hidden_layers": 6,
    "layer_types": ["mamba", "mamba", "attention"] * 2,
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "shared_intermediate_size": 96,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "position_embedding_type": "nope",
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 8,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.015625, "logits_scaling": 8,
}
HF_ENGINE = dict(HF, hidden_size=256, mamba_n_heads=8, mamba_d_head=64,
                 intermediate_size=256, shared_intermediate_size=256)


@pytest.fixture(scope="module")
def small():
    cfg = program_config(HF)
    return cfg, build_model(cfg), granite_hybrid.weights(HF, 7)


def _sequential(x, dt, A, Bm, Cm, h):
    """The Mamba-2 recurrence one token at a time: x (B,T,G,Hg,P), dt
    (B,T,G,Hg), A (G,Hg), Bm/Cm (B,T,G,N), h (B,G,Hg,P,N)."""
    ys = []
    for t in range(x.shape[1]):
        h = jnp.exp(dt[:, t] * A)[..., None, None] * h \
            + (dt[:, t, ..., None] * x[:, t])[..., None] \
            * Bm[:, t, :, None, None, :]
        ys.append(jnp.einsum("bghpn,bgn->bghp", h, Cm[:, t],
                             precision="highest"))
    return jnp.stack(ys, 1), h


@pytest.mark.parametrize("T", [5, 13, 21])
@pytest.mark.parametrize("carried", [False, True])
def test_chunked_ssd_matches_sequential_recurrence(T, carried):
    """The SSD form over chunks of 8 (T not a multiple of the chunk),
    from zeros or a carried state, against the token-at-a-time
    recurrence. Tolerance 2e-5: both are float32 at highest precision
    and differ only in summation order (within-chunk products against
    the sequential decay)."""
    B, G, Hg, P, N = 2, 1, 4, 16, 16
    ks = jax.random.split(jax.random.key(T), 6)
    x = jax.random.normal(ks[0], (B, T, G, Hg, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, G, Hg)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (G, Hg), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    h0 = jax.random.normal(ks[5], (B, G, Hg, P, N)) if carried \
        else jnp.zeros((B, G, Hg, P, N))
    y, h = mamba2.ssd(x, dt, A, Bm, Cm, h0, chunk=8)
    y_ref, h_ref = _sequential(x, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, h_ref, rtol=2e-5, atol=2e-5)


def test_window_step_and_right_padding_agree(small):
    """A right-padded row ends in the state of its exact length: pad
    positions get dt = 0 and leave the conv state, so the state leaves
    are equal, and so are the logits at the last real token. The
    window's state then steps on like a decode from it. Tolerance 1e-5:
    float32, the same operations over a longer (padded) sequence."""
    cfg, model, params = small
    n = 11
    toks = jax.random.randint(jax.random.key(1), (1, n), 0, 256)
    padded = jnp.pad(toks, ((0, 0), (0, 21 - n)), constant_values=5)
    lg_exact, c_exact = model.prefill(params, {"tokens": toks})
    lg_pad, c_pad = model.prefill(params, {"tokens": padded},
                                  last_idx=jnp.asarray([n - 1]))
    np.testing.assert_allclose(lg_pad, lg_exact, rtol=1e-5, atol=1e-5)
    for key in ("ssm", "conv"):
        np.testing.assert_allclose(c_pad[key], c_exact[key], rtol=1e-5,
                                   atol=1e-5)
    # one more token through the decode step from that state, against
    # the prefill of n + 1 tokens
    nxt = jnp.asarray([[17]])
    cache = model.init_cache(1, 32)
    cache = dict(cache, ssm=c_pad["ssm"], conv=c_pad["conv"])
    for key in ("k", "v"):
        cache[key] = cache[key].at[:, :, :n].set(c_exact[key])
    lg_step, _ = model.decode_step(params, nxt, cache, jnp.int32(n))
    lg_next, _ = model.prefill(params, {"tokens": jnp.concatenate(
        [toks, nxt], 1)})
    np.testing.assert_allclose(lg_step, lg_next, rtol=1e-5, atol=1e-5)


def test_program_prefill_matches_reference(small):
    """The program's prefill logits against the plain reference, which
    runs the recurrence token by token. Tolerance 1e-4 on logits of unit
    spread: float32 on both sides, different summation orders over 6
    layers."""
    cfg, model, params = small
    toks = jax.random.randint(jax.random.key(2), (1, 19), 0, 256)
    ref = granite_hybrid.logits(params, HF, toks[0].tolist())
    for n in (1, 8, 19):
        got, _ = model.prefill(params, {"tokens": toks[:, :n]})
        np.testing.assert_allclose(got[0, 0], ref[n - 1], rtol=1e-4,
                                   atol=1e-4)


@pytest.fixture(scope="module")
def engine_stack():
    cfg = program_config(HF_ENGINE)
    return cfg, build_model(cfg), granite_hybrid.weights(HF_ENGINE, 11)


def _requests(n_req):
    rng = np.random.default_rng(0)
    lens = [(5, 9), (37, 6), (12, 14), (26, 5), (3, 8), (19, 7)][:n_req]
    out = []
    for i, (p, k) in enumerate(lens):
        samp = SamplingParams() if i % 2 == 0 else SamplingParams(
            temperature=0.8, top_k=20, seed=i)
        out.append(Request(rid=i, prompt=rng.integers(0, 256, p).tolist(),
                           max_new_tokens=k, sampling=samp))
    return out


@pytest.mark.parametrize("use_kernel,num_blocks",
                         [(False, None), (True, None), (False, 9)],
                         ids=["gather", "kernel", "gather-tight-pool"])
def test_engine_matches_reference(engine_stack, use_kernel, num_blocks):
    """Engine admission (first chunk of 8), chunk windows from the rows'
    carried state, then paged decode, 3 slots for 6 requests (slots are
    reused, so each admission must reset its slot's state); with a tight
    pool, rows park (their state must stay) and are preempted (their
    state rebuilt on re-admission). Every emitted token's logprob equals
    the reference's log-softmax at that position, and greedy tokens are
    the reference's argmax within the tolerance. Tolerance 2e-4: float32
    on both sides; the program sums the SSD in chunks and the paged
    attention in kernel tiles, the reference token by token."""
    cfg, model, params = engine_stack
    eng = ServingEngine(model, params, batch_size=3, max_seq=64,
                        block_size=8, prefill_chunk=8, use_kernel=use_kernel,
                        num_blocks=num_blocks)
    assert eng.paged and eng.state_keys == ("conv", "ssm")
    assert not eng.prefix_sharing
    done = eng.run(copy.deepcopy(_requests(6)))
    assert len(done) == 6
    m = eng.metrics
    assert m["chunk_steps"] > 0 and m["state_resets"] >= 6
    if num_blocks:
        assert m["parked_slot_steps"] > 0
    assert 0 < m["state_rows_stepped"] <= 3 * m["decode_steps"]
    for r in done:
        seq = r.prompt + r.out_tokens[:-1]
        lg = granite_hybrid.logits(params, HF_ENGINE, seq)[:len(seq), :256]
        lsm = jax.nn.log_softmax(lg, axis=-1)
        first = len(r.prompt) - 1
        pos = np.arange(first, first + len(r.out_tokens))
        ref_lp = np.asarray(lsm[pos, np.asarray(r.out_tokens)])
        np.testing.assert_allclose(r.out_logprobs, ref_lp, rtol=2e-4,
                                   atol=2e-4)
        if r.sampling.temperature == 0:
            gap = np.asarray(lg[pos].max(-1)
                             - lg[pos, np.asarray(r.out_tokens)])
            assert gap.max() <= 2e-4, (r.rid, gap)


def test_state_models_refuse_speculation(engine_stack):
    """Speculation needs a rollback recurrent state does not have; the
    cache spec decides it, with no option."""
    cfg, model, params = engine_stack
    with pytest.raises(ValueError, match="pure-attention"):
        ServingEngine(model, params, batch_size=2, max_seq=32,
                      draft_model=model, draft_params=params, speculation=2)


@pytest.mark.parametrize("mode", ["prefill", "decode", "window"])
def test_one_kind_pattern_is_bit_identical_to_dense_stack(mode):
    """A dense config run as a one-kind layer pattern (every layer
    "attention", multipliers 1) through the pattern stack gives the same
    logits, bit for bit, as the one-kind scanned stack."""
    cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                              dtype=jnp.float32)
    dense = build_model(cfg)
    pat = build_model(dataclasses.replace(
        cfg, layer_types=("attention",) * cfg.n_layers))
    p = dense.init(jax.random.key(0))
    pp = dict(p, blocks={"attention": p["blocks"]})
    toks = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab_size)
    if mode == "prefill":
        a, _ = dense.prefill(p, {"tokens": toks})
        b, _ = pat.prefill(pp, {"tokens": toks})
    else:
        def run(m, params):
            c = m.init_cache(2, 32)
            _, c = m.prefill(params, {"tokens": toks[:, :8]}, cache=c,
                             cache_len=jnp.zeros(2, jnp.int32))
            if mode == "window":
                return m.prefill(params, {"tokens": toks[:, 8:]}, cache=c,
                                 cache_len=jnp.full(2, 8, jnp.int32))[0]
            return m.decode_step(params, toks[:, 8:9], c,
                                 jnp.full(2, 8, jnp.int32))[0]
        a, b = run(dense, p), run(pat, pp)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_bytes_count_the_programs_weights_and_state():
    """``bench/work_hybrid.py``'s floor counts exactly the program's
    weights and one slot's state rows at the cell's published size
    (shapes only, nothing allocated), so the roofline's numerator is the
    program's own bytes, never more."""
    from bench import spec
    from bench.work_hybrid import Hybrid
    cfg = spec.config("granite-4.0-h-micro")
    h = Hybrid.from_config(cfg)
    model = build_model(program_config(cfg))
    nbytes = lambda tree: sum(x.size * x.dtype.itemsize  # noqa: E731
                              for x in jax.tree.leaves(tree))
    assert h.weight_bytes == nbytes(jax.eval_shape(model.init,
                                                   jax.random.key(0)))
    cache = jax.eval_shape(lambda: model.init_paged_cache(2, 16, slots=1))
    assert h.state_bytes_per_row == nbytes(
        {k: v for k, v in cache.items() if k not in ("k", "v")})
    assert (h.n_attn, h.n_layers) == (4, 40)
