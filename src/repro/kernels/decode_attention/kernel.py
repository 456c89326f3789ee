"""Single-token GQA decode attention for TPU.

Grid (B, Hkv, T/bk): all G=Hq/Hkv query heads of one KV head are
processed together as a (G, hd) tile — on TPU this keeps the MXU busy on
what is otherwise a bandwidth-bound matvec (G rows amortize each KV tile
load, the GQA insight applied to the memory hierarchy). The KV-block
sweep is innermost with VMEM accumulators; ``n_valid`` arrives via scalar
prefetch (SMEM) so masking needs no HBM mask tensor.

Emits (out, lse): with a sequence-sharded cache each shard runs this
kernel over its local KV slice and partials merge with the closed-form
LSE combine (ref.merge_partials) via a tiny all-gather — flash-decoding
on TPU collectives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = float("-inf")


def _decode_kernel(n_valid_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, bk: int,
                   window: int, n_k_blocks: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n_valid = n_valid_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)                  # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < n_valid
    if window:
        mask &= kpos >= n_valid - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k_blocks - 1)
    def _write():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_safe + jnp.log(l))[:, 0]


@functools.partial(jax.jit, static_argnames=("sliding_window", "bk",
                                             "interpret"))
def decode_attention(q, k, v, n_valid, *, sliding_window: int = 0,
                     bk: int = 256, interpret: bool = True):
    """q (B,Hq,hd), k/v (B,Hkv,T,hd), n_valid scalar int32.
    Returns (out (B,Hq,hd), lse (B,Hq) f32)."""
    B, Hq, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bk = min(bk, T)
    assert T % bk == 0
    nk = T // bk
    qg = q.reshape(B, Hkv, G, hd)

    kernel = functools.partial(_decode_kernel, scale=1.0 / (hd ** 0.5),
                               bk=bk, window=sliding_window, n_k_blocks=nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, *_: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, *_: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G), lambda b, h, j, *_: (b, h, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), qg, k, v)
    return out.reshape(B, Hq, hd), lse.reshape(B, Hq)
