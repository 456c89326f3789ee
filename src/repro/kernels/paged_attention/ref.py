"""jnp oracles for fused paged GQA attention (q_len >= 1 windows).

Two references at different distances from the kernel:

* ``paged_window_attention_ref`` replays the kernel's *exact* streaming
  recurrence — the same shared helpers per block (exact-sum score
  contraction, fused-exp weights, single-contraction rescale, the
  integer causal-in-window mask), in the same order, at the same
  ``(hg, S*G, P*bs)`` tile shapes (``kernel.tile_plan``) — as a loop
  over each row's held compute blocks, one call per grid step.
  In float32 the interpret-mode kernel's **attention output matches it
  bit-for-bit** (every sum and contraction on that path is an
  exactly-rounded, fixed-order add chain — see ``kernel._exact_sum`` /
  ``kernel._rescale_accumulate``); the auxiliary LSE output carries a
  few ULP of residue from ``log``'s per-context codegen. (True
  universal bitwise equality between two separately-compiled XLA:CPU
  programs is not contractable — the backend deletes
  ``optimization_barrier`` during compilation and keeps per-context
  freedom in transcendental codegen — so the differential grid asserts
  out <= 4 ulp / lse <= 32 ulp; a real kernel bug — wrong block, wrong
  mask, wrong rescale — is 3+ orders of magnitude larger.)
* ``gathered_window_ref`` is the independent oracle: gather the pool
  through the table (exactly what the portable jnp serving path does)
  and run one-shot causal-in-window masked softmax attention. The
  kernel and the streaming ref must agree with it to dtype-tiered
  tolerance — this catches a bug that the replayed recurrence would
  faithfully replay.

``paged_decode_attention_ref`` / ``gathered_decode_ref`` are the
single-token (S = 1) entry points the decode grid asserts against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (_block_update, _finish,
                                                  held_pages, tile_plan)

NEG_INF = float("-inf")


def paged_window_attention_ref(q, pool_k, pool_v, block_table, base_lens, *,
                               sliding_window: int = 0, scale=None):
    """Streaming-softmax oracle over the compute-block sweep, q_len >= 1.

    q (B,S,Hq,hd) — S window tokens per row at positions
    ``base_lens[b] + [0, S)`` (K/V already scattered); pool_k/pool_v
    (num_blocks, Hkv, bs, hd); block_table (B, max_blocks) int32;
    base_lens (B,) tokens resident per row before the window; ``scale``
    the softmax scale (None: 1/sqrt(hd)). Returns (out (B,S,Hq,hd) in
    q.dtype, lse (B,S,Hq) f32)."""
    B, S, Hq, hd = q.shape
    scale = 1.0 / (hd ** 0.5) if scale is None else scale
    Hkv, bs = pool_k.shape[1], pool_k.shape[2]
    G = Hq // Hkv
    R = S * G
    max_blocks = block_table.shape[1]
    P, hg = tile_plan(Hkv=Hkv, bs=bs, hd=hd, S=S, G=G,
                      itemsize=pool_k.dtype.itemsize, max_blocks=max_blocks)
    T = P * bs
    qg = jnp.transpose(q.reshape(B, S, Hkv, G, hd),
                       (0, 2, 1, 3, 4)).reshape(B, Hkv, R, hd)
    base_lens = jnp.asarray(base_lens, jnp.int32).reshape(-1)
    # the last compute block may reach past the table; those pages are
    # never held, so any index does (the kernel copies nothing there)
    n_cols = -(-max_blocks // P) * P
    table = jnp.pad(jnp.asarray(block_table, jnp.int32),
                    ((0, 0), (0, n_cols - max_blocks)))

    def tile(pool, phys, h0):
        """(hg, T, hd) float32 of the compute block's pages, the
        kernel's ``(hg, P, bs, hd)`` buffer read as one tile."""
        g = jax.lax.dynamic_slice_in_dim(pool[phys], h0, hg, axis=1)
        return jnp.transpose(g, (1, 0, 2, 3)).astype(
            jnp.float32).reshape(hg, T, hd)

    @jax.jit
    def one_step(qt, table_b, base, h0, pool_k, pool_v):
        """One grid step of the kernel: row ``table_b``/``base``, heads
        ``h0 + [0, hg)``, its held compute blocks in order."""
        n_blk = (held_pages(base, S, bs, max_blocks) + P - 1) // P

        def body(j, carry):
            acc, m = carry
            phys = jax.lax.dynamic_slice_in_dim(table_b, j * P, P)
            return _block_update(qt.astype(jnp.float32),
                                 tile(pool_k, phys, h0),
                                 tile(pool_v, phys, h0), acc, m, j, base,
                                 S=S, G=G, scale=scale,
                                 window=sliding_window, deterministic=True)

        # acc[..., :hd] is the output accumulator, acc[..., hd] the
        # softmax denominator — one fused contraction per block, same as
        # the kernel (see kernel._rescale_accumulate for why)
        init = (jnp.zeros((hg, R, hd + 1), jnp.float32),
                jnp.full((hg, R, 1), NEG_INF, jnp.float32))
        acc, m = jax.lax.fori_loop(0, n_blk, body, init)
        out, lse = _finish(acc, m, q.dtype)
        return out, lse[..., 0]

    # One call per (row, head group), as the kernel's grid runs it: every
    # contraction here runs at exactly the (hg, R, T) tile shape of the
    # kernel's compute block, not batched across rows.
    outs, lses = [], []
    for b in range(B):
        o_g, l_g = [], []
        for h0 in range(0, Hkv, hg):
            o, l = one_step(qg[b, h0:h0 + hg], table[b], base_lens[b], h0,
                            pool_k, pool_v)
            o_g.append(o)
            l_g.append(l)
        outs.append(jnp.concatenate(o_g))
        lses.append(jnp.concatenate(l_g))
    out, lse = jnp.stack(outs), jnp.stack(lses)          # (B,Hkv,R,*)
    out = jnp.transpose(out.reshape(B, Hkv, S, G, hd),
                        (0, 2, 1, 3, 4)).reshape(B, S, Hq, hd)
    lse = jnp.transpose(lse.reshape(B, Hkv, S, G),
                        (0, 2, 1, 3)).reshape(B, S, Hq)
    return out, lse


def paged_decode_attention_ref(q, pool_k, pool_v, block_table, lengths, *,
                               sliding_window: int = 0, scale=None):
    """Single-token streaming oracle — the window ref at S = 1.

    q (B,Hq,hd); lengths (B,) valid tokens per row. Returns
    (out (B,Hq,hd) in q.dtype, lse (B,Hq) f32)."""
    base = jnp.asarray(lengths, jnp.int32).reshape(-1) - 1
    out, lse = paged_window_attention_ref(q[:, None], pool_k, pool_v,
                                          block_table, base,
                                          sliding_window=sliding_window,
                                          scale=scale)
    return out[:, 0], lse[:, 0]


def _gather_heads(pool, block_table):
    """Gather each row's blocks through its table: (num_blocks, Hkv, bs,
    hd) -> (B, Hkv, max_blocks*bs, hd) in logical order, float32."""
    B, max_blocks = block_table.shape
    _, Hkv, bs, hd = pool.shape
    g = jnp.transpose(pool[block_table], (0, 2, 1, 3, 4))  # (B,Hkv,mb,bs,hd)
    return g.reshape(B, Hkv, max_blocks * bs, hd).astype(jnp.float32)


def gathered_window_ref(q, pool_k, pool_v, block_table, base_lens, *,
                        sliding_window: int = 0):
    """Independent window oracle: table gather + one-shot masked softmax
    with the causal-in-window mask (query w of row b sees cache
    positions <= base_lens[b] + w)."""
    B, S, Hq, hd = q.shape
    Hkv, bs = pool_k.shape[1], pool_k.shape[2]
    G = Hq // Hkv
    max_blocks = block_table.shape[1]
    T = max_blocks * bs
    qg = q.reshape(B, S, Hkv, G, hd).astype(jnp.float32)
    kx = _gather_heads(pool_k, block_table)                  # (B,Hkv,T,hd)
    vx = _gather_heads(pool_v, block_table)
    s = jnp.einsum("bskgd,bktd->bkstg", qg, kx) / jnp.sqrt(float(hd))
    base = jnp.asarray(base_lens, jnp.int32).reshape(-1)
    i = base[:, None] + jnp.arange(S)[None, :]               # (B,S) abs pos
    j = jnp.arange(T)
    valid = j[None, None, :] <= i[:, :, None]                # (B,S,T)
    if sliding_window:
        valid &= j[None, None, :] > i[:, :, None] - sliding_window
    s = jnp.where(valid[:, None, :, :, None], s, -jnp.inf)   # (B,Hkv,S,T,G)
    lse = jax.nn.logsumexp(s, axis=3)                        # (B,Hkv,S,G)
    w = jnp.exp(s - lse[:, :, :, None, :])
    o = jnp.einsum("bkstg,bktd->bskgd", w, vx)
    out = o.reshape(B, S, Hq, hd).astype(q.dtype)
    return out, jnp.transpose(lse, (0, 2, 1, 3)).reshape(B, S, Hq)


def gathered_decode_ref(q, pool_k, pool_v, block_table, lengths, *,
                        sliding_window: int = 0):
    """Independent oracle: table gather + one-shot masked softmax."""
    B, Hq, hd = q.shape
    Hkv, bs = pool_k.shape[1], pool_k.shape[2]
    G = Hq // Hkv
    max_blocks = block_table.shape[1]
    T = max_blocks * bs
    qg = q.reshape(B, Hkv, G, hd).astype(jnp.float32)
    kx = _gather_heads(pool_k, block_table)                  # (B,Hkv,T,hd)
    vx = _gather_heads(pool_v, block_table)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, kx) / jnp.sqrt(float(hd))
    lengths = jnp.asarray(lengths, jnp.int32).reshape(-1)
    j = jnp.arange(T)
    valid = j[None, :] < lengths[:, None]                    # (B, T)
    if sliding_window:
        valid &= j[None, :] >= lengths[:, None] - sliding_window
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)                       # (B,Hkv,G)
    w = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bkgt,bktd->bkgd", w, vx)
    return (o.reshape(B, Hq, hd).astype(q.dtype), lse.reshape(B, Hq))
