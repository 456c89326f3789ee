"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU).

Deliverable c: for each kernel, sweep shapes/dtypes and assert_allclose
against the ref.py oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import (decode_attention,
                                                sharded_decode_attention)
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.rwkv_scan.ops import wkv
from repro.kernels.rwkv_scan.ref import wkv_ref

RNG = jax.random.PRNGKey(0)


def tol(dt):
    return 3e-2 if dt == jnp.bfloat16 else 3e-5


# ------------------------------------------------------------------- flash
@pytest.mark.parametrize("B,Hq,Hkv,S,T,hd,win,dt", [
    (2, 4, 2, 256, 256, 64, 0, jnp.float32),
    (1, 4, 4, 128, 384, 64, 0, jnp.bfloat16),     # MHA, q shorter than kv
    (2, 8, 2, 256, 256, 128, 128, jnp.float32),   # sliding window
    (1, 2, 1, 512, 512, 192, 0, jnp.float32),     # nemotron head_dim
    (1, 6, 6, 128, 128, 64, 0, jnp.bfloat16),     # whisper-ish
])
def test_flash_attention_matches_ref(B, Hq, Hkv, S, T, hd, win, dt):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, Hq, S, hd), dt)
    k = jax.random.normal(ks[1], (B, Hkv, T, hd), dt)
    v = jax.random.normal(ks[2], (B, Hkv, T, hd), dt)
    out = flash_attention(q, k, v, sliding_window=win)
    ref = flash_attention_ref(q, k, v, sliding_window=win)
    np.testing.assert_allclose(np.float32(out), np.float32(ref),
                               atol=tol(dt), rtol=tol(dt))


def test_flash_attention_non_square_blocks():
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    out = flash_attention(q, k, v, bq=64, bk=128)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.float32(out), np.float32(ref), atol=3e-5,
                               rtol=3e-5)


# ------------------------------------------------------------------ decode
@pytest.mark.parametrize("B,Hq,Hkv,T,hd,nv,win,dt", [
    (2, 8, 2, 512, 64, 300, 0, jnp.float32),
    (1, 4, 1, 1024, 128, 1000, 256, jnp.bfloat16),
    (2, 4, 4, 512, 64, 512, 0, jnp.float32),
    (1, 8, 8, 256, 112, 100, 0, jnp.float32),     # kimi head_dim
])
def test_decode_attention_matches_ref(B, Hq, Hkv, T, hd, nv, win, dt):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, Hq, hd), dt)
    k = jax.random.normal(ks[1], (B, Hkv, T, hd), dt)
    v = jax.random.normal(ks[2], (B, Hkv, T, hd), dt)
    out, lse = decode_attention(q, k, v, nv, sliding_window=win)
    ro, rl = decode_attention_ref(q, k, v, nv, sliding_window=win)
    np.testing.assert_allclose(np.float32(out), np.float32(ro),
                               atol=tol(dt), rtol=tol(dt))
    np.testing.assert_allclose(np.float32(lse), np.float32(rl),
                               atol=tol(dt), rtol=tol(dt))


@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_decode_lse_combine(n_shards):
    """Flash-decoding invariant: sequence-sharded partials + LSE merge ==
    unsharded attention."""
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (2, 4, 2 * 64)).reshape(2, 4, 128)
    k = jax.random.normal(ks[1], (2, 2, 512, 128))
    v = jax.random.normal(ks[2], (2, 2, 512, 128))
    ro, _ = decode_attention_ref(q, k, v, 400)
    so = sharded_decode_attention(q, jnp.split(k, n_shards, 2),
                                  jnp.split(v, n_shards, 2), 400)
    np.testing.assert_allclose(np.float32(so), np.float32(ro), atol=3e-5,
                               rtol=3e-5)


# --------------------------------------------------------------------- wkv
@pytest.mark.parametrize("B,T,H,hd,bt", [
    (2, 128, 2, 64, 64),
    (1, 96, 4, 32, 32),
    (1, 64, 1, 64, 16),
])
def test_wkv_scan_matches_ref(B, T, H, hd, bt):
    ks = jax.random.split(RNG, 5)
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd))
    v = jax.random.normal(ks[2], (B, T, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    s0 = jnp.zeros((B, H, hd, hd))
    out, sT = wkv(r, k, v, w, u, s0, bt=bt)
    ro, rs = wkv_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.float32(out), np.float32(ro), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.float32(sT), np.float32(rs), atol=2e-4,
                               rtol=2e-4)


def test_wkv_state_carry_equals_two_halves():
    """Running T then T (carrying state) == running 2T at once."""
    ks = jax.random.split(RNG, 5)
    B, T, H, hd = 1, 64, 2, 32
    r = jax.random.normal(ks[0], (B, 2 * T, H, hd))
    k = jax.random.normal(ks[1], (B, 2 * T, H, hd))
    v = jax.random.normal(ks[2], (B, 2 * T, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, 2 * T, H, hd))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    s0 = jnp.zeros((B, H, hd, hd))
    o_full, s_full = wkv(r, k, v, w, u, s0, bt=32)
    o1, s1 = wkv(r[:, :T], k[:, :T], v[:, :T], w[:, :T], u, s0, bt=32)
    o2, s2 = wkv(r[:, T:], k[:, T:], v[:, T:], w[:, T:], u, s1, bt=32)
    np.testing.assert_allclose(np.float32(jnp.concatenate([o1, o2], 1)),
                               np.float32(o_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.float32(s2), np.float32(s_full),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------- paged decode attention
from repro.kernels.paged_attention.ops import (  # noqa: E402
    paged_decode_attention as decode_op,
    paged_window_attention as window_op)
from repro.kernels.paged_attention.kernel import tile_plan  # noqa: E402
from repro.kernels.paged_attention.ref import (  # noqa: E402
    gathered_decode_ref, paged_decode_attention_ref)


def _held(pool, table, base, S):
    """What a one-layer pool holds at each row's positions ``base +
    [0, S)`` (B, S, Hkv, hd); a position below 0 reads position 0."""
    bs = pool.shape[2]
    pos = jnp.maximum(jnp.asarray(base)[:, None] + jnp.arange(S)[None], 0)
    phys = table[jnp.arange(table.shape[0])[:, None], pos // bs]
    return pool[phys, :, pos % bs]


def paged_decode(q, pk, pv, table, lens, **kw):
    """The kernel over a one-layer pool, writing at each row's last
    position the K/V the pool already holds there, so out and lse read
    the pool as given."""
    kn, vn = (_held(p, table, lens - 1, 1)[:, 0] for p in (pk, pv))
    out, lse, _, _ = decode_op(q, kn, vn, pk[None], pv[None], table, lens,
                               0, **kw)
    return out, lse


def paged_window(q, pk, pv, table, base, **kw):
    """The window kernel over a one-layer pool, every row writing the
    K/V the pool already holds at its window's positions."""
    S = q.shape[1]
    kn, vn = (_held(p, table, base, S) for p in (pk, pv))
    out, lse, _, _ = window_op(q, kn, vn, pk[None], pv[None], table, base,
                               0, jnp.full(q.shape[:1], S, jnp.int32), **kw)
    return out, lse


def _paged_case(B, Hq, Hkv, hd, bs, max_blocks, dt, *, seed=0, full=False,
                lens=None):
    """A pool + per-row disjoint block tables at ragged lengths, the
    shapes the serving engine hands the kernel: zeroed table tails point
    at the scratch block, row lengths land anywhere in [1, capacity]
    (or are the given ``lens``; a row of length 0 owns no block)."""
    nb = B * max_blocks + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Hq, hd), dt)
    pool_k = jax.random.normal(ks[1], (nb, Hkv, bs, hd), dt)
    pool_v = jax.random.normal(ks[2], (nb, Hkv, bs, hd), dt)
    rng = np.random.default_rng(seed + B * 1000 + hd)
    free = list(rng.permutation(np.arange(1, nb)))
    given = lens
    lens = np.zeros(B, np.int32)
    table = np.zeros((B, max_blocks), np.int32)
    for b in range(B):
        if given is not None:
            lens[b] = given[b]
        else:
            lens[b] = max_blocks * bs if full \
                else int(rng.integers(1, max_blocks * bs + 1))
        for i in range(-(-int(lens[b]) // bs)):
            table[b, i] = free.pop()
    return q, pool_k, pool_v, jnp.asarray(table), jnp.asarray(lens)


# num_heads x head_dim x block_size x active-slot count x window x dtype;
# every row also varies ragged per-row lengths via _paged_case
PAGED_GRID = [
    (1, 4, 1, 64, 16, 4, 0, jnp.float32),
    (2, 8, 2, 64, 16, 4, 0, jnp.float32),     # GQA
    (3, 4, 4, 32, 8, 6, 0, jnp.float32),      # MHA, small blocks
    (4, 2, 1, 128, 16, 5, 0, jnp.float32),    # wide heads
    (2, 8, 8, 64, 8, 4, 0, jnp.float32),
    (4, 4, 1, 64, 16, 5, 24, jnp.float32),    # sliding window
    (2, 8, 2, 64, 16, 4, 0, jnp.bfloat16),
    (3, 6, 6, 64, 8, 4, 0, jnp.bfloat16),
    (2, 4, 2, 32, 8, 6, 12, jnp.bfloat16),    # window + bf16
]


def _assert_ulp(a, b, nulp: int):
    """Elementwise |a - b| <= nulp float32 steps — the tightest portable
    contract between two separately-compiled XLA programs (the CPU
    backend deletes optimization barriers and keeps per-context codegen
    freedom in transcendentals, worth 1-3 ulp on some shapes; a real
    kernel bug is 3+ orders of magnitude larger)."""
    np.testing.assert_array_max_ulp(np.float32(a), np.float32(b),
                                    maxulp=nulp, dtype=np.float32)


@pytest.mark.parametrize("B,Hq,Hkv,hd,bs,mb,win,dt", PAGED_GRID)
def test_paged_decode_kernel_differential(B, Hq, Hkv, hd, bs, mb, win, dt):
    """The differential grid: the Pallas kernel (interpret mode) against
    the streaming jnp oracle — float32 within 4 ulp (bit-exact on
    nearly every shape; see ref.py for why universal bitwise equality
    between separately-compiled XLA programs is not contractable) and
    within dtype tolerance in bfloat16; kernel and oracle must both
    agree with the independent gather-then-softmax reference to
    dtype-tiered tolerance."""
    q, pk, pv, table, lens = _paged_case(B, Hq, Hkv, hd, bs, mb, dt)
    out, lse = paged_decode(q, pk, pv, table, lens, sliding_window=win)
    ro, rl = paged_decode_attention_ref(q, pk, pv, table, lens,
                                        sliding_window=win)
    go, gl = gathered_decode_ref(q, pk, pv, table, lens, sliding_window=win)
    if dt == jnp.float32:
        # out: bitwise on every audited (shape x seed) case — the 4-ulp
        # bound is slack for toolchain drift only. lse: jnp.log keeps
        # per-context codegen freedom (see ref.py), worth <= ~16 ulp.
        _assert_ulp(out, ro, 4)
        _assert_ulp(lse, rl, 32)
    else:
        np.testing.assert_allclose(np.float32(out), np.float32(ro),
                                   atol=tol(dt), rtol=tol(dt))
        np.testing.assert_allclose(np.float32(lse), np.float32(rl),
                                   atol=tol(dt), rtol=tol(dt))
    np.testing.assert_allclose(np.float32(out), np.float32(go),
                               atol=tol(dt), rtol=tol(dt))
    np.testing.assert_allclose(np.float32(lse), np.float32(gl),
                               atol=tol(dt), rtol=tol(dt))


def _block_tokens(Hq, Hkv, hd, bs, mb, dt, S):
    """Tokens per compute block the kernel plans for this shape."""
    P, _ = tile_plan(Hkv=Hkv, bs=bs, hd=hd, S=S, G=Hq // Hkv,
                     itemsize=jnp.dtype(dt).itemsize, max_blocks=mb)
    return P * bs


# query heads x KV heads x head_dim x block_size x table pages x dtype;
# every table spans several compute blocks
PAGED_SPAN_GRID = [
    (8, 2, 32, 16, 40, jnp.float32),     # GQA: blocks of 16, 16, 8 pages
    (4, 4, 32, 8, 40, jnp.float32),      # MHA: blocks of 32, 8 pages
    (8, 2, 32, 16, 40, jnp.bfloat16),
    (4, 4, 32, 8, 40, jnp.bfloat16),
]


@pytest.mark.parametrize("Hq,Hkv,hd,bs,mb,dt", PAGED_SPAN_GRID)
def test_paged_decode_kernel_spans_compute_blocks(Hq, Hkv, hd, bs, mb, dt):
    """Row lengths at the compute-block edges of a table several blocks
    long: one token, exactly one block, one token past it, the full
    table, and a row that holds nothing. The kernel loops over each
    row's held blocks only; it must match the streaming oracle (f32:
    out <= 4 ulp / lse <= 32 ulp) and the gather oracle, and the empty
    row must end as it always has: out 0, lse log(1e-30)."""
    T = _block_tokens(Hq, Hkv, hd, bs, mb, dt, 1)
    assert mb * bs > T
    lens = [1, T, T + 1, mb * bs, 0]
    q, pk, pv, table, lens = _paged_case(len(lens), Hq, Hkv, hd, bs, mb,
                                         dt, lens=lens)
    out, lse = paged_decode(q, pk, pv, table, lens)
    ro, rl = paged_decode_attention_ref(q, pk, pv, table, lens)
    go, gl = gathered_decode_ref(q, pk, pv, table, lens)
    if dt == jnp.float32:
        _assert_ulp(out, ro, 4)
        _assert_ulp(lse, rl, 32)
    else:
        np.testing.assert_allclose(np.float32(out), np.float32(ro),
                                   atol=tol(dt), rtol=tol(dt))
        np.testing.assert_allclose(np.float32(lse), np.float32(rl),
                                   atol=tol(dt), rtol=tol(dt))
    np.testing.assert_allclose(np.float32(out[:-1]), np.float32(go[:-1]),
                               atol=tol(dt), rtol=tol(dt))
    np.testing.assert_allclose(np.float32(lse[:-1]), np.float32(gl[:-1]),
                               atol=tol(dt), rtol=tol(dt))
    np.testing.assert_array_equal(np.float32(out[-1]), 0.0)
    np.testing.assert_array_equal(np.asarray(lse[-1]),
                                  np.log(np.float32(1e-30)))


def test_paged_decode_kernel_full_and_single_token_rows():
    """Length edges: a row at exactly full capacity and (via seed reroll)
    rows at 1 token keep the mask honest at both extremes."""
    q, pk, pv, table, lens = _paged_case(2, 4, 2, 64, 16, 3, jnp.float32,
                                         full=True)
    out, _ = paged_decode(q, pk, pv, table, lens)
    ro, _ = paged_decode_attention_ref(q, pk, pv, table, lens)
    _assert_ulp(out, ro, 4)
    lens1 = jnp.ones_like(lens)
    out1, _ = paged_decode(q, pk, pv, table, lens1)
    go1, _ = gathered_decode_ref(q, pk, pv, table, lens1)
    np.testing.assert_allclose(np.float32(out1), np.float32(go1), atol=3e-5,
                               rtol=3e-5)


def test_paged_decode_kernel_ignores_scratch_garbage():
    """Unowned table tails point at scratch block 0, whose contents are
    garbage by design: poisoning scratch must not change any output."""
    q, pk, pv, table, lens = _paged_case(3, 8, 2, 64, 16, 4, jnp.float32)
    out, lse = paged_decode(q, pk, pv, table, lens)
    pk2 = pk.at[0].set(1e9)
    pv2 = pv.at[0].set(-1e9)
    out2, lse2 = paged_decode(q, pk2, pv2, table, lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse2))


def test_paged_attention_serving_path_kernel_vs_gather():
    """Through the serving entry point (`attention.paged_decode_attention`
    with the scatter of the new token): use_kernel=True and the jnp
    gather path must return bitwise-identical updated pools and
    tolerance-close outputs."""
    from repro.models.attention import paged_decode_attention as serve_paged
    B, Hq, Hkv, hd, bs, mb = 3, 8, 2, 64, 8, 4
    q, pk, pv, table, lens = _paged_case(B, Hq, Hkv, hd, bs, mb, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    k_new = jax.random.normal(ks[0], (B, 1, Hkv, hd))
    v_new = jax.random.normal(ks[1], (B, 1, Hkv, hd))
    # cache_len = lens - 1 so the scatter stays inside owned blocks
    cache_len = lens - 1
    o_g, pk_g, pv_g = serve_paged(q[:, None], pk[None], pv[None], k_new,
                                  v_new, table, cache_len, 0,
                                  use_kernel=False)
    o_k, pk_k, pv_k = serve_paged(q[:, None], pk[None], pv[None], k_new,
                                  v_new, table, cache_len, 0,
                                  use_kernel=True)
    np.testing.assert_array_equal(np.asarray(pk_g), np.asarray(pk_k))
    np.testing.assert_array_equal(np.asarray(pv_g), np.asarray(pv_k))
    np.testing.assert_allclose(np.float32(o_g), np.float32(o_k), atol=3e-5,
                               rtol=3e-5)


# ------------------------------------------------- fused window attention
from repro.kernels.paged_attention.ref import (  # noqa: E402
    gathered_window_ref, paged_window_attention_ref)


def _window_case(B, S, Hq, Hkv, hd, bs, max_blocks, dt, *, seed=0,
                 base=None):
    """Window variant of ``_paged_case``: each row holds a ragged base
    length (including 0 — a chunked-prefill first chunk; or the given
    ``base``) and owns blocks covering ``base + S`` tokens, i.e. the
    window's K/V is already scattered into the pool; table tails stay
    at scratch."""
    nb = B * max_blocks + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), dt)
    pool_k = jax.random.normal(ks[1], (nb, Hkv, bs, hd), dt)
    pool_v = jax.random.normal(ks[2], (nb, Hkv, bs, hd), dt)
    rng = np.random.default_rng(seed + B * 1000 + S * 100 + hd)
    free = list(rng.permutation(np.arange(1, nb)))
    given = base
    base = np.zeros(B, np.int32)
    table = np.zeros((B, max_blocks), np.int32)
    for b in range(B):
        base[b] = int(rng.integers(0, max_blocks * bs - S + 1)) \
            if given is None else given[b]
        for i in range(-(-int(base[b] + S) // bs)):
            table[b, i] = free.pop()
    return q, pool_k, pool_v, jnp.asarray(table), jnp.asarray(base)


# q_len x active-slot count x heads x head_dim x block_size x window x
# dtype; ragged per-row base lengths (incl. mid-block boundaries and
# base = 0) come from _window_case's rng
WINDOW_GRID = [
    (1, 2, 8, 2, 64, 16, 4, 0, jnp.float32),   # degenerate decode shape
    (2, 3, 4, 4, 32, 8, 6, 0, jnp.float32),    # MHA, small blocks
    (2, 2, 8, 2, 64, 16, 4, 0, jnp.float32),   # GQA
    (4, 2, 8, 2, 64, 16, 4, 0, jnp.float32),
    (4, 3, 4, 1, 64, 8, 6, 0, jnp.float32),    # MQA
    (8, 2, 4, 2, 64, 16, 4, 0, jnp.float32),
    (8, 2, 4, 4, 32, 8, 8, 0, jnp.float32),
    (4, 2, 8, 2, 64, 16, 5, 24, jnp.float32),  # sliding window
    (4, 2, 8, 2, 64, 16, 4, 0, jnp.bfloat16),
    (8, 2, 4, 2, 32, 8, 8, 12, jnp.bfloat16),  # window + bf16
]


@pytest.mark.parametrize("S,B,Hq,Hkv,hd,bs,mb,win,dt", WINDOW_GRID)
def test_paged_window_kernel_differential(S, B, Hq, Hkv, hd, bs, mb, win,
                                          dt):
    """The fused multi-token grid: one kernel launch covering S window
    queries per row with causal-in-window masking and per-row base
    lengths, against the streaming oracle (f32: out <= 4 ulp / lse <=
    32 ulp, same contract as the decode grid) and the independent
    gather-then-softmax oracle (dtype-tiered tolerance)."""
    q, pk, pv, table, base = _window_case(B, S, Hq, Hkv, hd, bs, mb, dt)
    out, lse = paged_window(q, pk, pv, table, base, sliding_window=win)
    ro, rl = paged_window_attention_ref(q, pk, pv, table, base,
                                        sliding_window=win)
    go, gl = gathered_window_ref(q, pk, pv, table, base, sliding_window=win)
    if dt == jnp.float32:
        _assert_ulp(out, ro, 4)
        _assert_ulp(lse, rl, 32)
    else:
        np.testing.assert_allclose(np.float32(out), np.float32(ro),
                                   atol=tol(dt), rtol=tol(dt))
        np.testing.assert_allclose(np.float32(lse), np.float32(rl),
                                   atol=tol(dt), rtol=tol(dt))
    np.testing.assert_allclose(np.float32(out), np.float32(go),
                               atol=tol(dt), rtol=tol(dt))
    np.testing.assert_allclose(np.float32(lse), np.float32(gl),
                               atol=tol(dt), rtol=tol(dt))


# q_len x query heads x KV heads x head_dim x block_size x table pages
# x dtype; every table spans several compute blocks
WINDOW_SPAN_GRID = [
    (64, 16, 4, 32, 16, 40, jnp.float32),    # GQA, R 256: head groups
    (64, 4, 4, 32, 16, 40, jnp.bfloat16),    # MHA
]


@pytest.mark.parametrize("S,Hq,Hkv,hd,bs,mb,dt", WINDOW_SPAN_GRID)
def test_paged_window_kernel_spans_compute_blocks(S, Hq, Hkv, hd, bs, mb,
                                                  dt):
    """Chunk windows whose rows end at the compute-block edges of a
    table several blocks long: a first chunk (base 0), a window ending
    exactly on a block, one ending one token past it, and one ending at
    the table's end, against the streaming and gather oracles."""
    T = _block_tokens(Hq, Hkv, hd, bs, mb, dt, S)
    assert mb * bs > T
    base = [0, T - S, T - S + 1, mb * bs - S]
    q, pk, pv, table, base = _window_case(len(base), S, Hq, Hkv, hd, bs,
                                          mb, dt, base=base)
    out, lse = paged_window(q, pk, pv, table, base)
    ro, rl = paged_window_attention_ref(q, pk, pv, table, base)
    go, gl = gathered_window_ref(q, pk, pv, table, base)
    if dt == jnp.float32:
        _assert_ulp(out, ro, 4)
        _assert_ulp(lse, rl, 32)
    else:
        np.testing.assert_allclose(np.float32(out), np.float32(ro),
                                   atol=tol(dt), rtol=tol(dt))
        np.testing.assert_allclose(np.float32(lse), np.float32(rl),
                                   atol=tol(dt), rtol=tol(dt))
    np.testing.assert_allclose(np.float32(out), np.float32(go),
                               atol=tol(dt), rtol=tol(dt))
    np.testing.assert_allclose(np.float32(lse), np.float32(gl),
                               atol=tol(dt), rtol=tol(dt))


def test_paged_window_tile_plan_splits_heads_only_for_vmem():
    """All KV heads ride one grid step unless the step's VMEM would pass
    the budget: a 256-row query tile (S 64 x G 4) splits into head
    groups, a decode tile of the same heads does not, and every plan
    fits."""
    from repro.kernels.paged_attention.kernel import (VMEM_BUDGET_BYTES,
                                                      _step_vmem_bytes)
    wide = tile_plan(Hkv=8, bs=16, hd=128, S=64, G=4, itemsize=2,
                     max_blocks=64)
    narrow = tile_plan(Hkv=8, bs=16, hd=128, S=1, G=4, itemsize=2,
                       max_blocks=64)
    assert narrow == (16, 8)
    assert wide[0] == 16 and 8 % wide[1] == 0 and wide[1] < 8
    for P, hg in (wide, narrow):
        S = 64 if (P, hg) == wide else 1
        assert _step_vmem_bytes(hg, P * 16, S, 4, 128,
                                2) <= VMEM_BUDGET_BYTES
    assert tile_plan(Hkv=2, bs=16, hd=64, S=1, G=2, itemsize=4,
                     max_blocks=3) == (3, 2)


@pytest.mark.parametrize("S", [1, 64])
def test_paged_kernel_never_reads_past_held_pages(S):
    """Pages past a row's held length are never read: with the scratch
    block, where every table tail points, full of NaN, every output and
    lse is finite and equal to the zeroed-scratch case. A kernel that
    copied the tails and only masked their scores would turn them into
    NaN (a masked weight of 0 times NaN)."""
    Hq, Hkv, hd, bs, mb, dt = 8, 2, 32, 16, 40, jnp.float32
    T = _block_tokens(Hq, Hkv, hd, bs, mb, dt, S)
    base = [0, T - S, T - S + 1, 5]
    q, pk, pv, table, base = _window_case(len(base), S, Hq, Hkv, hd, bs,
                                          mb, dt, base=base)
    runs = [paged_window(q, pk.at[0].set(fill), pv.at[0].set(fill),
                         table, base) for fill in (jnp.nan, 0.0)]
    (out, lse), (out0, lse0) = runs
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(lse)).all()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out0))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse0))


def test_paged_window_kernel_decode_degenerate():
    """S = 1 windows run the *same* tile shapes and op order as plain
    decode — the fused kernel at q_len 1 is bitwise identical to
    ``paged_decode_attention``, so serving one kernel to all three
    consumers costs decode nothing."""
    q, pk, pv, table, lens = _paged_case(3, 8, 2, 64, 16, 4, jnp.float32)
    od, ld = paged_decode(q, pk, pv, table, lens)
    ow, lw = paged_window(q[:, None], pk, pv, table, lens - 1)
    np.testing.assert_array_equal(np.asarray(od), np.asarray(ow[:, 0]))
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lw[:, 0]))


def test_paged_window_kernel_ignores_scratch_garbage():
    """Scratch poisoning, window edition: unowned table tails point at
    scratch block 0 whose contents are garbage by design — poisoning it
    must not perturb any window output bit."""
    q, pk, pv, table, base = _window_case(3, 4, 8, 2, 64, 16, 4,
                                          jnp.float32)
    out, lse = paged_window(q, pk, pv, table, base)
    out2, lse2 = paged_window(q, pk.at[0].set(1e9), pv.at[0].set(-1e9),
                              table, base)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse2))


def test_paged_verify_serving_path_kernel_vs_gather():
    """Through the serving entry point (`attention.paged_verify_attention`
    with the scatter and n_write scratch-diversion): kernel and gather
    paths must leave every *owned* pool block bitwise identical and
    agree on every window position the engine can commit (positions
    past a row's n_write read diverted garbage and are never
    committed — acceptance is capped below them)."""
    from repro.models.attention import paged_verify_attention as sv
    B, S, Hq, Hkv, hd, bs, mb = 3, 4, 8, 2, 64, 8, 6
    q, pk, pv, table, base = _window_case(B, S, Hq, Hkv, hd, bs, mb,
                                          jnp.float32, seed=3)
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    k_new = jax.random.normal(ks[0], (B, S, Hkv, hd))
    v_new = jax.random.normal(ks[1], (B, S, Hkv, hd))
    # full window / partial grant / parked rider (all writes diverted)
    n_write = jnp.asarray([S, 2, 0], jnp.int32)
    o_g, pk_g, pv_g = sv(q, pk[None], pv[None], k_new, v_new, table, base,
                         n_write, 0, use_kernel=False)
    o_k, pk_k, pv_k = sv(q, pk[None], pv[None], k_new, v_new, table, base,
                         n_write, 0, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(pk_g)[:, 1:],
                                  np.asarray(pk_k)[:, 1:])
    np.testing.assert_array_equal(np.asarray(pv_g)[:, 1:],
                                  np.asarray(pv_k)[:, 1:])
    og = np.float32(o_g).reshape(B, S, Hq, hd)
    ok = np.float32(o_k).reshape(B, S, Hq, hd)
    for b in range(B):
        c = int(n_write[b])
        np.testing.assert_allclose(ok[b, :c], og[b, :c], atol=3e-5,
                                   rtol=3e-5)


# window tokens x query heads x KV heads x head_dim x dtype; heads of 64
# are lane-packed two to a pool row (attention.lane_pack)
INPLACE_GRID = [
    (1, 8, 2, 128, jnp.float32),      # decode
    (4, 8, 2, 128, jnp.float32),      # speculative verify
    (64, 8, 2, 128, jnp.float32),     # chunk window
    (1, 8, 4, 64, jnp.float32),       # lane-packed decode
    (4, 8, 4, 64, jnp.bfloat16),      # lane-packed verify, bf16
    (64, 8, 4, 64, jnp.bfloat16),     # lane-packed chunk window, bf16
]


@pytest.mark.parametrize("S,Hq,Hkv,hd,dt", INPLACE_GRID)
def test_paged_kernel_writes_window_in_place(S, Hq, Hkv, hd, dt):
    """The kernel's in-place write against a pool stacked over three
    layers, through the serving entry points (decode for S = 1, the
    verify/chunk window otherwise), kernel against the jnp path: the
    target layer's pages equal the jnp scatter's, every other layer is
    bit-for-bit untouched, and the outputs agree on every position the
    engine can commit. Rows start on a block edge, mid-block, and one
    window straddles a compute-block edge; one row writes part of its
    window and one none of it (a parked rider), and in decode one row's
    write site is the scratch block. The scratch block itself is left
    out of the comparison: diverted writes land there in the jnp path
    and nowhere in the kernel's, and it is garbage by design."""
    from repro.models.attention import (lane_pack, paged_decode_attention,
                                        paged_verify_attention)
    L, layer, bs, mb = 3, 1, 16, 24
    f = lane_pack(hd, Hkv)
    T = _block_tokens(Hq, Hkv // f, hd * f, bs, mb, dt, S)
    base = np.array([0, 37, T - S // 2 - 1, 90], np.int32)
    B = len(base)
    n_write = np.array([S, max(S // 2, 1), 0, S], np.int32)
    nb = B * mb + 2
    ks = jax.random.split(jax.random.PRNGKey(S * 10 + hd), 5)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), dt)
    shape = (L, nb, Hkv // f, bs, hd * f)
    pk = jax.random.normal(ks[1], shape, dt)
    pv = jax.random.normal(ks[2], shape, dt)
    k_new = jax.random.normal(ks[3], (B, S, Hkv, hd), dt)
    v_new = jax.random.normal(ks[4], (B, S, Hkv, hd), dt)
    free = list(np.random.default_rng(S).permutation(np.arange(1, nb)))
    table = np.zeros((B, mb), np.int32)
    for b in range(B):
        for i in range(-(-int(base[b] + S) // bs)):
            table[b, i] = free.pop()
    if S == 1:
        # a parked row: its write site is the scratch block
        table[2, base[2] // bs] = 0
        run = lambda kernel: paged_decode_attention(       # noqa: E731
            q, pk, pv, k_new, v_new, jnp.asarray(table), jnp.asarray(base),
            layer, use_kernel=kernel)
        n_write = np.ones(B, np.int32)
    else:
        run = lambda kernel: paged_verify_attention(       # noqa: E731
            q, pk, pv, k_new, v_new, jnp.asarray(table), jnp.asarray(base),
            jnp.asarray(n_write), layer, use_kernel=kernel)
    o_g, pk_g, pv_g = run(False)
    o_k, pk_k, pv_k = run(True)
    for new, ref, old in ((pk_k, pk_g, pk), (pv_k, pv_g, pv)):
        new, ref, old = (np.asarray(a).view(np.uint8) for a in (new, ref,
                                                                old))
        np.testing.assert_array_equal(new[layer, 1:], ref[layer, 1:])
        for other in set(range(L)) - {layer}:
            np.testing.assert_array_equal(new[other], old[other])
    assert not np.array_equal(np.asarray(pk_k[layer]), np.asarray(pk[layer]))
    og = np.float32(o_g).reshape(B, S, Hq, hd)
    ok = np.float32(o_k).reshape(B, S, Hq, hd)
    for b in range(B):
        if S == 1 and table[b, base[b] // bs] == 0:
            continue
        c = int(n_write[b])
        np.testing.assert_allclose(ok[b, :c], og[b, :c], atol=tol(dt),
                                   rtol=tol(dt))


# ---------------------------------------------------------------- ssm scan
from repro.kernels.ssm_scan.ops import selective_scan as pallas_ssm  # noqa: E402
from repro.kernels.ssm_scan.ref import ssm_scan_ref  # noqa: E402


def _ssm_inputs(key, B, T, di, N, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    u = jax.random.normal(ks[0], (B, T, di), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, di), dtype)) * 0.1
    Bm = jax.random.normal(ks[2], (B, T, N), dtype)
    Cm = jax.random.normal(ks[3], (B, T, N), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (di, N), jnp.float32) * 0.3)
    D = jnp.ones((di,), jnp.float32)
    s0 = jnp.zeros((B, di, N), jnp.float32)
    return u, dt, Bm, Cm, A, D, s0


@pytest.mark.parametrize("B,T,di,N,bt", [
    (2, 128, 64, 16, 64),
    (1, 96, 128, 16, 32),
    (1, 64, 32, 8, 16),
])
def test_ssm_scan_matches_ref(B, T, di, N, bt):
    args = _ssm_inputs(RNG, B, T, di, N)
    y, sT = pallas_ssm(*args, bt=bt)
    ry, rs = ssm_scan_ref(*args)
    np.testing.assert_allclose(np.float32(y), np.float32(ry), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.float32(sT), np.float32(rs), atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_dtypes(dtype):
    args = _ssm_inputs(RNG, 1, 64, 32, 16, dtype)
    y, sT = pallas_ssm(*args, bt=32)
    ry, rs = ssm_scan_ref(*[a.astype(jnp.float32)
                            if a.dtype == jnp.bfloat16 else a for a in args])
    atol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.float32(y), np.float32(ry), atol=atol,
                               rtol=atol)


def test_ssm_scan_state_carry_equals_two_halves():
    B, T, di, N = 1, 64, 32, 16
    u, dt, Bm, Cm, A, D, s0 = _ssm_inputs(RNG, B, 2 * T, di, N)
    yf, sf = pallas_ssm(u, dt, Bm, Cm, A, D, s0, bt=32)
    y1, s1 = pallas_ssm(u[:, :T], dt[:, :T], Bm[:, :T], Cm[:, :T], A, D,
                        s0, bt=32)
    y2, s2 = pallas_ssm(u[:, T:], dt[:, T:], Bm[:, T:], Cm[:, T:], A, D,
                        s1, bt=32)
    np.testing.assert_allclose(np.float32(jnp.concatenate([y1, y2], 1)),
                               np.float32(yf), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.float32(s2), np.float32(sf), atol=1e-4,
                               rtol=1e-4)


def test_ssm_scan_matches_model_block():
    """The kernel agrees with repro.models.ssm.selective_scan — the
    hymba model path it replaces on TPU."""
    from repro.models import ssm as model_ssm
    args = _ssm_inputs(RNG, 2, 64, 32, 16)
    y, sT = pallas_ssm(*args, bt=32)
    my, ms = model_ssm.selective_scan(*args)
    np.testing.assert_allclose(np.float32(y), np.float32(my), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.float32(sT), np.float32(ms), atol=2e-4,
                               rtol=2e-4)
