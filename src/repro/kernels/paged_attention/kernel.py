"""Fused paged GQA attention for TPU: one kernel, q_len >= 1.

The serving engine's KV lives in a shared block pool
``(L, num_blocks, Hkv, block_size, hd)``, stacked over layers, and each
slot maps its logical positions through a per-slot block table
(``repro.serve.blocks``).
The portable jnp path (`attention.paged_decode_attention` /
`attention.paged_verify_attention`) *gathers* each row's blocks into a
transient ``(B, max_blocks*bs)`` buffer before the attention math —
O(B x max_seq) of extra HBM traffic per layer per step.

This kernel writes the window's K/V into the pool and reads the pool
**in place**, and only the pages a row holds. The block table, the
per-row base lengths and the layer index ride in as scalar-prefetch
operands (SMEM), with each row's count of tokens to write; the whole
stacked pool stays in HBM (``memory_space=pl.ANY``), aliased from input
to output, and the kernel starts its own DMAs. No layer of the pool is
sliced out and nothing is copied around the call.

One fused tile serves every serving consumer:

* **plain decode** — ``q_len = 1``, the degenerate window;
* **speculative verify** — ``q_len = k+1`` draft windows, each query
  masked causally *inside* the window;
* **chunked prefill** — a prompt chunk is a window of known tokens
  against the partially-resident prompt.

Grid ``(B, Hkv // hg)``: one step per batch row and *head group* of
``hg`` KV heads, and inside it a loop over the row's **compute blocks**
of ``P`` pages (``P * bs`` tokens). Row ``b``'s window reads cache
positions below ``base[b] + S``, so it holds
``ceil((base[b] + S) / bs)`` pages; the loop runs
``ceil(held / P)`` times and copies exactly the held pages — one
``make_async_copy`` per page, a contiguous ``(hg, bs, hd)`` slab of the
head-major pool addressed through the table — into a two-slot VMEM
buffer. The next compute block (or the next grid step's first one) is
in flight while the current one is computed. Pages past the held
bound are neither copied nor computed: table tails cost nothing.
``P`` and ``hg`` come from :func:`tile_plan`, from the shapes alone.

**The write.** Row ``b`` writes its first ``n_write[b]`` window tokens
at positions ``base[b] + [0, n_write[b])``, which lie in its last held
pages. Once such a page is in the VMEM buffer, its new rows are
merged in (a one-hot pick of the window's rows per head) and the
whole page is copied back to the pool with one DMA per leaf: a page is
a tile-aligned slab, where a single bf16 row would be half a packed
sublane. The block's math then reads the merged buffer, so a window
sees its own tokens whether or not its copies have landed. Owned pages
are disjoint across rows (the engine copy-on-writes shared tails), so
no other grid step reads them; a buffer slot's page writes are waited
for before a copy refills it, and all of them before the kernel ends.
Window tokens past ``n_write`` are not written.

All ``S * G`` query rows of one KV head (G = Hq/Hkv) ride one
``(S*G, hd)`` tile, batched over the step's ``hg`` heads, with flash
accumulators in VMEM across the block loop. **Causal-in-window
masking** happens per query row: window position ``w = row // G`` of
batch row ``b`` attends to cache positions ``[0, base[b] + w]`` —
``base`` is the per-row count of tokens resident *before* the window,
so every window token conditions on the committed context plus its own
in-window prefix, exactly what ``w+1`` sequential single-token calls
would each see. Rows at different base lengths mask per-row via the
prefetched vector — ragged continuous batching needs no padding and no
HBM mask tensor. A buffer page that was not copied holds whatever the
slot held before; the mask keeps it out of the scores and V is zeroed
there, since a masked weight of 0 times a non-finite value is NaN.

Emits (out, lse) so sequence-sharded pools can merge partials with the
same closed-form LSE combine as the stripe decode kernel.

Layout and Mosaic tiling: the q/out/lse blocks end in their arrays'
full ``(S*G, hd)`` / ``(S*G, 1)`` dimensions. A page copy lands in
``buf[slot, :, p]`` of a ``(2, hg, P, bs, hd)`` buffer, whose last two
dimensions are the page's own, and the K/V tiles are upcast to float32
before ``(hg, P, bs, hd)`` is read as ``(hg, P*bs, hd)``, which is
tile-aligned for any ``bs`` that is a multiple of 8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = float("-inf")

# Tokens one compute block aims at: large enough that a row takes a few
# loop iterations, not one per page, and small enough that the last,
# partly held block of a row wastes little math.
BLOCK_TOKENS = 256
# VMEM one grid step may plan for: three quarters of the 16 MiB that
# Mosaic scopes for a kernel by default on TPU v5e, the rest left for
# the compiler's own temporaries.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _step_vmem_bytes(hg: int, T: int, S: int, G: int, hd: int,
                     itemsize: int) -> int:
    """VMEM of one grid step with ``hg`` heads, ``T``-token compute
    blocks and an ``S``-token window of ``G`` query heads per KV head,
    counted in (8, 128) float32 tiles: the two-slot K/V buffers, the
    float32 tiles the block's math makes (upcast K and V, ``[V | 1]``
    stacked on the accumulator, scores, weights and ``[p | diag(alpha)]``),
    the accumulators, and the pipelined q/out/lse and new K/V blocks. A
    page's merge of new rows (its one-hot pick and float32 rows) is far
    smaller than the block's math and dead before it starts."""
    lanes = lambda n: _round_up(n, 128)                      # noqa: E731
    R = S * G
    Rp = _round_up(R, 8)
    kv_buffers = 2 * 2 * hg * T * hd * itemsize
    kv_f32 = 2 * hg * T * lanes(hd) * 4
    v_aug = hg * _round_up(T + R, 8) * lanes(hd + 1) * 4
    scores = 3 * hg * Rp * lanes(T + 1) * 4
    p_aug = hg * Rp * lanes(T + R) * 4
    accumulators = hg * Rp * (lanes(hd + 1) + 128) * 4
    q_f32 = hg * Rp * lanes(hd) * 4
    io_blocks = 2 * hg * Rp * (2 * lanes(hd) * itemsize + 128 * 4)
    new_blocks = 2 * 2 * hg * _round_up(S, 16) * lanes(hd) * itemsize
    return (kv_buffers + kv_f32 + v_aug + scores + p_aug + accumulators
            + q_f32 + io_blocks + new_blocks)


def tile_plan(*, Hkv: int, bs: int, hd: int, S: int, G: int, itemsize: int,
              max_blocks: int) -> tuple[int, int]:
    """``(P, hg)``: pages per compute block and KV heads per grid step,
    for an ``S``-token window of ``G`` query heads per KV head.

    ``P`` covers :data:`BLOCK_TOKENS` tokens (never more pages than the
    table has); ``hg`` is the largest divisor of ``Hkv`` whose step fits
    :data:`VMEM_BUDGET_BYTES`, all heads where they fit. Only where one
    head does not fit is ``P`` halved. The kernel and its streaming
    oracle both take their tile shapes from here."""
    P = max(1, min(max_blocks, BLOCK_TOKENS // bs))
    while True:
        for hg in range(Hkv, 0, -1):
            if Hkv % hg == 0 and _step_vmem_bytes(
                    hg, P * bs, S, G, hd, itemsize) <= VMEM_BUDGET_BYTES:
                return P, hg
        if P == 1:
            return 1, 1
        P //= 2


def _exact_precision(dtype):
    """A one-hot contraction returns its picks exactly: bf16 products
    are exact on the MXU at any precision, float32 ones only at the
    highest."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def held_pages(base, S: int, bs: int, max_blocks: int):
    """Pages of a row's table its window reads: positions below
    ``base + S``, clipped to the table."""
    return jnp.clip((base + S + bs - 1) // bs, 0, max_blocks)


def _rescale_accumulate(p, alpha, v, acc, *, deterministic: bool):
    """One flash-attention accumulate step as a SINGLE contraction per
    head, batched over the leading head axis.

    acc (H, R, hd+1) carries the output accumulator in [..., :hd] and
    the softmax denominator in [..., hd]. The classic update
    ``alpha * acc + [p @ v, sum(p)]`` leaves XLA free to seed the dot's
    reduction with the rescaled addend (FMA / accumulator-init fusion),
    which rounds differently per compilation context — the one freedom
    that broke bit-exactness between the compiled kernel and its jnp
    oracle. Folding the rescale into the matmul removes the seeding:

        [p | diag(alpha)] @ [[v | 1], [acc]]

    is ONE (R, T+R) x (T+R, hd+1) contraction — every product
    (including ``alpha_r * acc_r``) enters the same reduction, and the
    denominator column rides along for free.

    ``deterministic`` (the interpret/oracle mode) additionally pins the
    rounding order: the contraction is lowered as a broadcast multiply
    into an ``_exact_sum`` add chain instead of a ``dot_general`` (whose
    small-shape emitter reassociates per context). The compiled TPU
    path keeps the plain ``dot_general`` (MXU) — bit-parity across
    hardware is meaningless anyway.
    """
    H, R = p.shape[0], p.shape[1]
    eye = jnp.eye(R, dtype=bool)[None]
    p_aug = jnp.concatenate([p, jnp.where(eye, alpha, 0.0)], axis=2)
    v_aug = jnp.concatenate(
        [jnp.concatenate([v, jnp.ones((H, v.shape[1], 1), jnp.float32)],
                         axis=2), acc], axis=1)
    if not deterministic:
        return jax.lax.dot_general(p_aug, v_aug, (((2,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32)
    return _exact_sum(p_aug[:, :, :, None] * v_aug[:, None, :, :], 2)


def _exact_sum(x, axis: int):
    """Sum with ONE defined rounding order: a sequential ``lax.scan``
    chain of plain adds. An XLA ``reduce`` leaves the backend free to
    split the reduction loop into partial accumulators (reassociation)
    or lower a minor-axis reduce as a horizontal SIMD tree — both
    context-dependent orders that show up as kernel-vs-oracle ulp
    drift. IEEE adds are exactly rounded, so a fixed-order add chain
    yields the same bits under any codegen of the adds themselves."""
    xs = jnp.moveaxis(x, axis, 0)
    total, _ = jax.lax.scan(lambda c, t: (c + t, None),
                            jnp.zeros_like(xs[0]), xs)
    return total


def _p_and_alpha(s, mask, m_prev, m_safe):
    """Softmax weights p = exp(s - m_safe) and rescale alpha =
    exp(m_prev - m_safe) out of ONE (..., T+1) exp op. Besides saving a
    transcendental launch, this narrows a determinism gap: a lone
    (R, 1)-shaped exp was observed to compile differently depending on
    unrelated ops elsewhere in the module (vector-vs-scalar codegen of
    the polynomial), while the wide exp is far more stable — one shared
    op means p and alpha can't round apart from each other."""
    z = jnp.concatenate([s, m_prev], axis=-1) - m_safe       # (..., T+1)
    e = jnp.exp(z)
    p = jnp.where(mask, e[..., :-1], 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), e[..., -1:], 0.0)
    return p, alpha


def _qk_scores(q, k, scale: float, *, deterministic: bool):
    """Score contraction q (H, R, hd) x k (H, T, hd) -> (H, R, T),
    batched over heads. Same determinism split as
    ``_rescale_accumulate``: ``dot_general`` for the compiled TPU path;
    a broadcast multiply feeding an ``_exact_sum`` add chain for the
    interpret/oracle mode."""
    if not deterministic:
        return jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32) * scale
    return _exact_sum(q[:, :, None, :] * k[:, None, :, :], 3) * scale


def _window_mask(s_shape, j, base, *, T: int, G: int, window: int):
    """Causal-in-window validity for the (..., R, T) score tile of
    compute block ``j`` (cache positions ``j*T + [0, T)``): query row r
    is window position ``w = r // G`` of its batch row, valid through
    cache position ``base + w`` (its own scatter included), so
    ``n_valid = base + w + 1`` — per query row, not per batch row. A
    sliding window then clips the low side at ``n_valid - window``.
    Integer-only, exact under any codegen."""
    nd = len(s_shape)
    kpos = j * T + jax.lax.broadcasted_iota(jnp.int32, s_shape, nd - 1)
    w_off = jax.lax.broadcasted_iota(jnp.int32, s_shape, nd - 2) // G
    n_valid = base + w_off + 1
    mask = kpos < n_valid
    if window:
        mask &= kpos >= n_valid - window
    return mask


def _block_update(q, k, v, acc, m_prev, j, base, *, S: int, G: int,
                  scale: float, window: int, deterministic: bool):
    """One compute block of the flash recurrence for the (hg, R) query
    tile: k/v (hg, T, hd) float32 of cache positions ``j*T + [0, T)``.
    Positions at or past ``base + S`` are past every query row: their
    scores are masked and their V rows zeroed, whatever the tile holds
    there. Returns the new (acc, m)."""
    T = k.shape[1]
    s = _qk_scores(q, k, scale, deterministic=deterministic)  # (hg, R, T)
    mask = _window_mask(s.shape, j, base, T=T, G=G, window=window)
    s = jnp.where(mask, s, NEG_INF)
    vpos = j * T + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    v = jnp.where(vpos < base + S, v, 0.0)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p, alpha = _p_and_alpha(s, mask, m_prev, m_safe)
    acc = _rescale_accumulate(p, alpha, v, acc, deterministic=deterministic)
    return acc, m_new


def _finish(acc, m, dtype):
    """(out, lse) from the accumulators: a row that saw nothing ends
    with acc 0 and m -inf, i.e. out 0 and lse log(1e-30)."""
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    l = jnp.maximum(acc[..., -1:], 1e-30)
    return (acc[..., :-1] / l).astype(dtype), m_safe + jnp.log(l)


def _paged_window_kernel(table_ref, base_ref, layer_ref, nw_ref, q_ref,
                         kn_ref, vn_ref, _pool_k_in, _pool_v_in, o_ref,
                         lse_ref, pool_k, pool_v, kbuf, vbuf, sems, wsems,
                         acc_ref, m_ref, slot_ref, pend_ref, *,
                         scale: float, S: int, G: int, bs: int, P: int,
                         hg: int, n_groups: int, n_rows: int,
                         max_blocks: int, window: int, deterministic: bool):
    b, g = pl.program_id(0), pl.program_id(1)
    T = P * bs
    hd = kbuf.shape[-1]
    t = b * n_groups + g                     # linear grid step
    layer = layer_ref[0]
    held = lambda row: held_pages(base_ref[row], S, bs,  # noqa: E731
                                  max_blocks)
    heads = pl.ds(g * hg, hg)
    pairs = ((pool_k, kbuf), (pool_v, vbuf))

    def block_copies(row, grp, i, slot, p):
        """Page ``p`` of compute block ``i`` of (row, head group): one
        K and one V copy of a contiguous (hg, bs, hd) pool slab."""
        phys = table_ref[row * max_blocks + i * P + p]
        return [pltpu.make_async_copy(pool.at[layer, phys,
                                              pl.ds(grp * hg, hg)],
                                      buf.at[slot, :, p], sems.at[kv, slot])
                for kv, (pool, buf) in enumerate(pairs)]

    def for_held_pages(row, grp, i, slot, pages, method):
        def page(p, carry):
            for c in block_copies(row, grp, i, slot, p):
                getattr(c, method)()
            return carry
        jax.lax.fori_loop(0, jnp.minimum(P, pages - i * P), page, 0)

    def page_writes(slot, p, phys):
        """The K and V copies of buffer page ``p`` back to pool block
        ``phys``, this step's heads."""
        return [pltpu.make_async_copy(buf.at[slot, :, p],
                                      pool.at[layer, phys, heads],
                                      wsems.at[kv, slot])
                for kv, (pool, buf) in enumerate(pairs)]

    def drain(slot):
        """Wait for the page writes still reading buffer slot ``slot``."""
        def one(n, carry):
            for c in page_writes(slot, 0, 0):
                c.wait()
            return carry
        jax.lax.fori_loop(0, pend_ref[slot], one, 0)
        pend_ref[slot] = 0

    def start_block(row, grp, i, slot, pages):
        """Copy a compute block in, once its buffer slot is free."""
        drain(slot)
        for_held_pages(row, grp, i, slot, pages, "start")

    @pl.when(t == 0)
    def _():
        slot_ref[0] = 0
        pend_ref[0] = 0
        pend_ref[1] = 0

    pages = held(b)
    n_blk = (pages + P - 1) // P
    slot0 = slot_ref[0]
    # the previous step started this step's first block as its last one
    # ran, if it had any block to run
    prefetched = (t > 0) & (held(jnp.maximum(t - 1, 0) // n_groups) > 0)

    @pl.when((pages > 0) & jnp.logical_not(prefetched))
    def _():
        start_block(b, g, 0, slot0, pages)

    t_next = t + 1
    b_next = jnp.minimum(t_next // n_groups, n_rows - 1)
    pages_next = held(b_next)
    start_next = (t_next < n_rows * n_groups) & (pages_next > 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    q = q_ref[0].astype(jnp.float32)                         # (hg, R, hd)
    base = base_ref[b]
    n_write = nw_ref[b]
    # pages holding window positions [base, base + n_write)
    first_page = jnp.maximum(base, 0) // bs
    end_page = jnp.where(n_write > 0, (base + n_write + bs - 1) // bs, 0)

    def write_pages(i, slot):
        """Write the window's K/V into the pages of compute block ``i``
        that hold them, in the buffer (what the block's math reads) and
        back to the pool (one DMA per page and leaf)."""
        def page(gp, carry):
            p = gp - i * P
            off = gp * bs - base                 # window index of row 0
            row = off + jax.lax.broadcasted_iota(jnp.int32, (hg, bs, S), 1)
            col = jax.lax.broadcasted_iota(jnp.int32, (hg, bs, S), 2)
            new = (row[..., :1] >= 0) & (row[..., :1] < n_write)
            # the page's rows of the window: a one-hot (bs, S) pick per
            # head, exact (one product per row), at any offset
            pick = ((row == col) & (col < n_write)).astype(kn_ref.dtype)
            for buf, n_ref in ((kbuf, kn_ref), (vbuf, vn_ref)):
                picked = jax.lax.dot_general(
                    pick, n_ref[0], (((2,), (1,)), ((0,), (0,))),
                    precision=_exact_precision(n_ref.dtype),
                    preferred_element_type=jnp.float32)
                buf[slot, :, p] = jnp.where(
                    new, picked,
                    buf[slot, :, p].astype(jnp.float32)).astype(buf.dtype)
            for c in page_writes(slot, p, table_ref[b * max_blocks + gp]):
                c.start()
            pend_ref[slot] += 1
            return carry
        jax.lax.fori_loop(jnp.maximum(first_page, i * P),
                          jnp.minimum(end_page, jnp.minimum(pages,
                                                            i * P + P)),
                          page, 0)

    def body(i, carry):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blk)
        def _():
            start_block(b, g, i + 1, 1 - slot, pages)

        @pl.when((i + 1 == n_blk) & start_next)
        def _():
            start_block(b_next, t_next % n_groups, 0, 1 - slot, pages_next)

        for_held_pages(b, g, i, slot, pages, "wait")
        write_pages(i, slot)
        k = kbuf[slot].astype(jnp.float32).reshape(hg, T, hd)
        v = vbuf[slot].astype(jnp.float32).reshape(hg, T, hd)
        acc, m = _block_update(q, k, v, acc_ref[...], m_ref[...], i, base,
                               S=S, G=G, scale=scale, window=window,
                               deterministic=deterministic)
        acc_ref[...] = acc
        m_ref[...] = m
        return carry

    jax.lax.fori_loop(0, n_blk, body, 0)
    slot_ref[0] = (slot0 + n_blk) % 2
    out, lse = _finish(acc_ref[...], m_ref[...], o_ref.dtype)
    o_ref[0] = out
    lse_ref[0] = lse

    @pl.when(t + 1 == n_rows * n_groups)
    def _():
        # the last step: every page write is done before the kernel ends
        drain(0)
        drain(1)


@functools.partial(jax.jit, static_argnames=("sliding_window", "interpret",
                                             "scale"))
def paged_window_attention(q, k_new, v_new, pool_k, pool_v, block_table,
                           base_lens, layer, n_write, *,
                           sliding_window: int = 0, interpret: bool = True,
                           scale: float | None = None):
    """The fused multi-token tile, writing the window's K/V in place.

    q (B, S, Hq, hd) — S window tokens per row at absolute positions
    ``base_lens[b] + [0, S)``; k_new/v_new (B, S, Hkv, hd) their K/V;
    pool_k/pool_v the whole stacked pool (L, num_blocks, Hkv, bs, hd);
    block_table (B, max_blocks) int32; base_lens (B,) int32 tokens
    resident per row *before* the window; ``layer`` the layer of the
    pool this call reads and writes; n_write (B,) int32 window tokens
    row b writes (the first ``n_write[b]``; the rest are not written
    anywhere). Window query w of row b attends to cache positions
    ``[0, base_lens[b] + w]`` (causal in the window) as the pool holds
    them after the writes; ``scale`` the softmax scale (None:
    1/sqrt(hd)). Returns (out (B,S,Hq,hd) in q.dtype, lse (B,S,Hq) f32,
    pool_k, pool_v): the pools are the inputs, aliased and updated in
    place, so inside a program that donates them nothing is copied.

    ``S = 1`` with ``base_lens = lengths - 1`` is exactly the classic
    single-token paged decode — one code path, every consumer."""
    B, S, Hq, hd = q.shape
    Hkv, bs = pool_k.shape[2], pool_k.shape[3]
    G = Hq // Hkv
    R = S * G
    max_blocks = block_table.shape[1]
    base = jnp.asarray(base_lens, jnp.int32).reshape(-1)
    n_write = jnp.asarray(n_write, jnp.int32).reshape(-1)
    if S % 2 == 0 and _step_vmem_bytes(
            1, min(BLOCK_TOKENS, max_blocks * bs), S, G, hd,
            pool_k.dtype.itemsize) > VMEM_BUDGET_BYTES:
        # the window's query rows alone overflow one step's VMEM (the
        # [p | diag(alpha)] tile grows as R^2): run it as two half
        # windows, the second starting where the first ends and reading
        # what the first wrote
        h = S // 2
        parts = []
        for i in range(2):
            cut = lambda a: a[:, i * h:(i + 1) * h]          # noqa: E731
            out, lse, pool_k, pool_v = paged_window_attention(
                cut(q), cut(k_new), cut(v_new), pool_k, pool_v, block_table,
                base + i * h, layer, jnp.clip(n_write - i * h, 0, h),
                sliding_window=sliding_window, interpret=interpret,
                scale=scale)
            parts.append((out, lse))
        out, lse = (jnp.concatenate(p, axis=1) for p in zip(*parts))
        return out, lse, pool_k, pool_v
    P, hg = tile_plan(Hkv=Hkv, bs=bs, hd=hd, S=S, G=G,
                      itemsize=pool_k.dtype.itemsize, max_blocks=max_blocks)
    n_groups = Hkv // hg
    # (B,S,Hkv,G,hd) -> (B,Hkv,S,G,hd) -> (B,Hkv,S*G,hd): all of one KV
    # head's window queries ride one MXU tile; row r is window position
    # r // G, query head r % G.
    qg = jnp.transpose(q.reshape(B, S, Hkv, G, hd),
                       (0, 2, 1, 3, 4)).reshape(B, Hkv, R, hd)
    # (B,S,Hkv,hd) -> (B,Hkv,S,hd) in the pool's dtype: the window's
    # rows of one head, as the pages hold them
    kn, vn = (jnp.transpose(a, (0, 2, 1, 3)).astype(pool_k.dtype)
              for a in (k_new, v_new))

    kernel = functools.partial(
        _paged_window_kernel,
        scale=1.0 / (hd ** 0.5) if scale is None else scale, S=S, G=G, bs=bs,
        P=P, hg=hg, n_groups=n_groups, n_rows=B, max_blocks=max_blocks,
        window=sliding_window, deterministic=interpret)
    group = lambda b, g, *_: (b, g, 0, 0)                    # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_groups),
        in_specs=[
            pl.BlockSpec((1, hg, R, hd), group),
            pl.BlockSpec((1, hg, S, hd), group),
            pl.BlockSpec((1, hg, S, hd), group),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, hg, R, hd), group),
            pl.BlockSpec((1, hg, R, 1), group),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, hg, P, bs, hd), pool_k.dtype),   # K, two slots
            pltpu.VMEM((2, hg, P, bs, hd), pool_v.dtype),   # V, two slots
            pltpu.SemaphoreType.DMA((2, 2)),                # reads (K|V, slot)
            pltpu.SemaphoreType.DMA((2, 2)),                # writes
            pltpu.VMEM((hg, R, hd + 1), jnp.float32),       # acc | denominator
            pltpu.VMEM((hg, R, 1), jnp.float32),            # running max
            pltpu.SMEM((1,), jnp.int32),                    # slot of next block
            pltpu.SMEM((2,), jnp.int32),                    # writes per slot
        ],
    )
    out, lse, pool_k, pool_v = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, R, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, R, 1), jnp.float32),
            jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
            jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype),
        ],
        # the pools are updated in place: operands 7 and 8 (after the
        # four scalar-prefetch operands, q and the new K/V) are outputs
        # 2 and 3
        input_output_aliases={7: 2, 8: 3},
        # a step prefetches the next step's first block, so the grid
        # runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_window_attention",
    )(block_table.reshape(-1).astype(jnp.int32), base,
      jnp.asarray(layer, jnp.int32).reshape(1), n_write, qg, kn, vn, pool_k,
      pool_v)
    out = jnp.transpose(out.reshape(B, Hkv, S, G, hd),
                        (0, 2, 1, 3, 4)).reshape(B, S, Hq, hd)
    lse = jnp.transpose(lse.reshape(B, Hkv, S, G),
                        (0, 2, 1, 3)).reshape(B, S, Hq)
    return out, lse, pool_k, pool_v


@functools.partial(jax.jit, static_argnames=("sliding_window", "interpret",
                                             "scale"))
def paged_decode_attention(q, k_new, v_new, pool_k, pool_v, block_table,
                           lengths, layer, *, sliding_window: int = 0,
                           interpret: bool = True,
                           scale: float | None = None):
    """Single-token decode — the fused window kernel at its S = 1
    degenerate case. q (B,Hq,hd); k_new/v_new (B,Hkv,hd) the new
    token's K/V, written at position ``lengths - 1`` of layer ``layer``
    of the stacked pool_k/pool_v (L, num_blocks, Hkv, bs, hd);
    block_table (B, max_blocks) int32; lengths (B,) int32 valid tokens
    per row, the new one included (a row of length 0 holds nothing and
    writes nothing). Returns (out (B,Hq,hd) in q.dtype, lse (B,Hq) f32,
    pool_k, pool_v)."""
    lengths = jnp.asarray(lengths, jnp.int32).reshape(-1)
    out, lse, pool_k, pool_v = paged_window_attention(
        q[:, None], k_new[:, None], v_new[:, None], pool_k, pool_v,
        block_table, lengths - 1, layer, (lengths > 0).astype(jnp.int32),
        sliding_window=sliding_window, interpret=interpret, scale=scale)
    return out[:, 0], lse[:, 0], pool_k, pool_v
