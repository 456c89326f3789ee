"""Fused paged GQA attention for TPU: one kernel, q_len >= 1.

The serving engine's KV lives in a shared block pool
``(num_blocks, Hkv, block_size, hd)`` per layer, and each slot maps its
logical positions through a per-slot block table (``repro.serve.blocks``).
The portable jnp path (`attention.paged_decode_attention` /
`attention.paged_verify_attention`) *gathers* each row's blocks into a
transient ``(B, max_blocks*bs)`` buffer before the attention math —
O(B x max_seq) of extra HBM traffic per layer per step.

This kernel reads the pool **in place**: the block table and per-row
base lengths ride in as scalar-prefetch operands (SMEM), and the K/V
BlockSpec index maps dereference the table, so each grid step DMAs
one KV head of one physical block, a contiguous ``(block_size, hd)``
tile, from the pool into VMEM. Nothing is
materialized per-row; the only per-step HBM traffic is the blocks a row
actually owns (plus masked-off scratch for table tails).

One fused tile serves every serving consumer:

* **plain decode** — ``q_len = 1``, the degenerate window;
* **speculative verify** — ``q_len = k+1`` draft windows, each query
  masked causally *inside* the window;
* **chunked prefill** — a prompt chunk is a window of known tokens
  against the partially-resident prompt.

Grid (B, Hkv, max_blocks): all ``q_len * G`` query rows of one KV head
(G = Hq/Hkv) are processed together as an ``(S*G, hd)`` tile (the same
MXU-occupancy trick as ``decode_attention``, extended across the
window), with the block sweep innermost over flash-style VMEM
accumulators. **Causal-in-window masking** happens per query row:
window position ``w = row // G`` of batch row ``b`` attends to cache
positions ``[0, base[b] + w]`` — ``base`` is the per-row count of
tokens resident *before* the window, so every window token conditions
on the committed context plus its own in-window prefix, exactly what
``w+1`` sequential single-token calls would each see. Rows at
different base lengths mask per-row via the prefetched vector — ragged
continuous batching needs no padding and no HBM mask tensor.

Emits (out, lse) so sequence-sharded pools can merge partials with the
same closed-form LSE combine as the stripe decode kernel.

Layout and Mosaic tiling: the last two dimensions of every block must be
divisible by (8, 128) or equal the array's own. The pool is head-major
so a K/V block is ``(1, 1, bs, hd)`` (last two dims the array's own),
and lse leaves as ``(B, Hkv, R, 1)`` so its block ``(1, 1, R, 1)`` ends
in full dimensions too. A token-major pool with a ``(1, bs, 1, hd)``
block puts a 1 in the second-minor place of the ``Hkv`` axis, which the
TPU compiler refuses for ``Hkv > 1``; interpret mode does not check it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = float("-inf")


def _rescale_accumulate(p, alpha, v, acc, *, deterministic: bool):
    """One flash-attention accumulate step as a SINGLE contraction.

    acc (R, hd+1) carries the output accumulator in [:, :hd] and the
    softmax denominator in [:, hd]. The classic update
    ``alpha * acc + [p @ v, sum(p)]`` leaves XLA free to seed the dot's
    reduction with the rescaled addend (FMA / accumulator-init fusion),
    which rounds differently per compilation context — the one freedom
    that broke bit-exactness between the compiled kernel and its jnp
    oracle. Folding the rescale into the matmul removes the seeding:

        [p | diag(alpha)] @ [[v | 1], [acc]]

    is ONE (R, bs+R) x (bs+R, hd+1) contraction — every product
    (including ``alpha_r * acc_r``) enters the same reduction, and the
    denominator column rides along for free.

    ``deterministic`` (the interpret/oracle mode) additionally pins the
    rounding order: the contraction is lowered as a broadcast multiply
    into an ``_exact_sum`` add chain instead of a ``dot_general`` (whose
    small-shape emitter reassociates per context). The compiled TPU
    path keeps the plain ``dot_general`` (MXU) — bit-parity across
    hardware is meaningless anyway.
    """
    R = p.shape[0]
    p_aug = jnp.concatenate(
        [p, jnp.where(jnp.eye(R, dtype=bool), alpha, 0.0)], axis=1)
    v_aug = jnp.concatenate(
        [jnp.concatenate([v, jnp.ones((v.shape[0], 1), jnp.float32)],
                         axis=1), acc], axis=0)
    if not deterministic:
        return jax.lax.dot_general(p_aug, v_aug, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    return _exact_sum(p_aug[:, :, None] * v_aug[None, :, :], 1)


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
    exp(m_prev - m_safe) out of ONE (R, bs+1) exp op. Besides saving a
    transcendental launch, this narrows a determinism gap: a lone
    (R, 1)-shaped exp was observed to compile differently depending on
    unrelated ops elsewhere in the module (vector-vs-scalar codegen of
    the polynomial), while the wide exp is far more stable — one shared
    op means p and alpha can't round apart from each other."""
    z = jnp.concatenate([s, m_prev], axis=1) - m_safe        # (R, bs+1)
    e = jnp.exp(z)
    p = jnp.where(mask, e[:, :-1], 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), e[:, -1:], 0.0)
    return p, alpha


def _qk_scores(q, k, scale: float, *, deterministic: bool):
    """Masked-score contraction q (R, hd) x k (bs, hd) -> (R, bs).
    Same determinism split as ``_rescale_accumulate``: ``dot_general``
    for the compiled TPU path; a broadcast multiply feeding an
    ``_exact_sum`` add chain for the interpret/oracle mode."""
    if not deterministic:
        return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * scale
    return _exact_sum(q[:, None, :] * k[None, :, :], 2) * scale


def _window_mask(s_shape, j: int, base, *, bs: int, G: int, window: int):
    """Causal-in-window validity for the (R, bs) score tile of KV block
    ``j``: query row r is window position ``w = r // G`` of its batch
    row, valid through cache position ``base + w`` (its own scatter
    included), so ``n_valid = base + w + 1`` — per query row, not per
    batch row. A sliding window then clips the low side at
    ``n_valid - window``. Integer-only, exact under any codegen."""
    kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    w_off = jax.lax.broadcasted_iota(jnp.int32, s_shape, 0) // G
    n_valid = base + w_off + 1
    mask = kpos < n_valid
    if window:
        mask &= kpos >= n_valid - window
    return mask


def _paged_window_kernel(table_ref, base_ref, q_ref, k_ref, v_ref, o_ref,
                         lse_ref, acc_ref, m_ref, *, scale: float,
                         bs: int, G: int, window: int, n_blocks: int,
                         deterministic: bool):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    base = base_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)                  # (S*G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bs, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = _qk_scores(q, k, scale, deterministic=deterministic)
    mask = _window_mask(s.shape, j, base, bs=bs, G=G, window=window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p, alpha = _p_and_alpha(s, mask, m_prev, m_safe)
    acc_ref[...] = _rescale_accumulate(p, alpha, v, acc_ref[...],
                                       deterministic=deterministic)
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _write():
        l = jnp.maximum(acc_ref[:, -1:], 1e-30)
        o_ref[0, 0] = (acc_ref[:, :-1] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_safe + jnp.log(l)              # (S*G, 1)


@functools.partial(jax.jit, static_argnames=("sliding_window", "interpret"))
def paged_window_attention(q, pool_k, pool_v, block_table, base_lens, *,
                           sliding_window: int = 0, interpret: bool = True):
    """The fused multi-token tile. q (B, S, Hq, hd) — S window tokens
    per row at absolute positions ``base_lens[b] + [0, S)``, their K/V
    already scattered into the pool; pool_k/pool_v (num_blocks, Hkv,
    bs, hd); block_table (B, max_blocks) int32; base_lens (B,) int32
    tokens resident per row *before* the window. Window query w of row
    b attends to cache positions ``[0, base_lens[b] + w]`` (causal in
    the window). Returns (out (B,S,Hq,hd) in q.dtype, lse (B,S,Hq) f32).

    ``S = 1`` with ``base_lens = lengths - 1`` is exactly the classic
    single-token paged decode — one code path, every consumer."""
    B, S, Hq, hd = q.shape
    Hkv, bs = pool_k.shape[1], pool_k.shape[2]
    G = Hq // Hkv
    R = S * G
    max_blocks = block_table.shape[1]
    # (B,S,Hkv,G,hd) -> (B,Hkv,S,G,hd) -> (B,Hkv,S*G,hd): all of one KV
    # head's window queries ride one MXU tile; row r is window position
    # r // G, query head r % G.
    qg = jnp.transpose(q.reshape(B, S, Hkv, G, hd),
                       (0, 2, 1, 3, 4)).reshape(B, Hkv, R, hd)

    kernel = functools.partial(_paged_window_kernel,
                               scale=1.0 / (hd ** 0.5), bs=bs, G=G,
                               window=sliding_window, n_blocks=max_blocks,
                               deterministic=interpret)

    # The index maps receive the scalar-prefetch refs after the grid
    # indices: K/V tiles are addressed *through the block table*, so the
    # pool is read in place — physical block table[b, j] is the (b, ., j)
    # step's tile, whatever pool slot it landed in at admission time.
    flat_table = block_table.reshape(-1).astype(jnp.int32)

    def kv_map(b, h, j, table, lens):
        return (table[b * max_blocks + j], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, R, hd), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), kv_map),
            pl.BlockSpec((1, 1, bs, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, R, hd), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, R, 1), lambda b, h, j, *_: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, hd + 1), jnp.float32),    # acc | denominator
            pltpu.VMEM((R, 1), jnp.float32),         # running max
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, R, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, R, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(flat_table, jnp.asarray(base_lens, jnp.int32).reshape(-1), qg,
      pool_k, pool_v)
    out = jnp.transpose(out.reshape(B, Hkv, S, G, hd),
                        (0, 2, 1, 3, 4)).reshape(B, S, Hq, hd)
    lse = jnp.transpose(lse.reshape(B, Hkv, S, G),
                        (0, 2, 1, 3)).reshape(B, S, Hq)
    return out, lse


@functools.partial(jax.jit, static_argnames=("sliding_window", "interpret"))
def paged_decode_attention(q, pool_k, pool_v, block_table, lengths, *,
                           sliding_window: int = 0, interpret: bool = True):
    """Single-token decode — the fused window kernel at its S = 1
    degenerate case. q (B,Hq,hd); pool_k/pool_v (num_blocks, Hkv, bs,
    hd); block_table (B, max_blocks) int32; lengths (B,) int32 valid
    tokens per row (the new token's K/V already scattered into its
    block). Returns (out (B,Hq,hd) in q.dtype, lse (B,Hq) f32)."""
    base = jnp.asarray(lengths, jnp.int32).reshape(-1) - 1
    out, lse = paged_window_attention(q[:, None], pool_k, pool_v,
                                      block_table, base,
                                      sliding_window=sliding_window,
                                      interpret=interpret)
    return out[:, 0], lse[:, 0]
