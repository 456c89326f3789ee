"""Blocked flash attention for TPU (GQA, causal / sliding-window).

TPU-native design (not a CUDA port): the grid is (B, Hq, S/bq, T/bk) with
the KV-block dimension innermost ("arbitrary" semantics) so the online-
softmax accumulators live in VMEM scratch across the KV sweep; q/k/v
tiles stream HBM->VMEM via BlockSpecs; the two matmuls per tile hit the
MXU with 128-aligned (bq, hd)x(hd, bk) shapes; masking is computed from
the grid indices with iota on the VPU (no mask tensor in HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, bq: int, bk: int, causal: bool,
                  window: int, q_offset: int, n_k_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                 # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + q_offset
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (m == -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k_blocks - 1)
    def _write():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, sliding_window: int = 0,
                    bq: int = 128, bk: int = 128, interpret: bool = True):
    """q (B,Hq,S,hd), k/v (B,Hkv,T,hd) -> (B,Hq,S,hd)."""
    B, Hq, S, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    nq, nk = S // bq, T // bk
    grid = (B, Hq, nq, nk)

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / (hd ** 0.5), bq=bq, bk=bk,
        causal=causal, window=sliding_window, q_offset=T - S, n_k_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
