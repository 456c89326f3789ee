"""WKV6 recurrence for TPU (data-dependent decay, Finch §4).

Grid (B, H, T/bt): the (hd x hd) per-head state lives in VMEM scratch and
is carried across time blocks (the grid's innermost "arbitrary" dim);
r/k/v/w stream in (bt, hd) tiles. Within a block the recurrence is a
``fori_loop`` of rank-1 updates on the VPU — hd=64 rows keep the update
vectorizable. (A chunked matmul formulation that moves intra-block work
onto the MXU is the documented follow-up in EXPERIMENTS §Perf; the
sequential-in-block form is the correctness baseline and is already
HBM-optimal: each element is read once.)

TPU adaptation note: the CUDA kernels for RWKV parallelize over (B, H,
hd-lanes) threads with the state in registers; the TPU analogue is the
(B, H) grid with state in VMEM and lane-parallelism via the VPU's 8x128
vregs — same dataflow, memory-hierarchy-native.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
                state_ref, *, bt: int, n_t_blocks: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0]

    u = u_ref[0].astype(jnp.float32)                    # (hd,)

    def step(t, _):
        r_t = r_ref[0, t, 0].astype(jnp.float32)        # (hd,)
        k_t = k_ref[0, t, 0].astype(jnp.float32)
        v_t = v_ref[0, t, 0].astype(jnp.float32)
        w_t = w_ref[0, t, 0].astype(jnp.float32)
        S = state_ref[...]                              # (hd, hd)
        kv = k_t[:, None] * v_t[None, :]
        out = jnp.sum(r_t[:, None] * (S + u[:, None] * kv), axis=0)
        o_ref[0, t, 0] = out.astype(o_ref.dtype)
        state_ref[...] = w_t[:, None] * S + kv
        return ()

    jax.lax.fori_loop(0, bt, step, ())

    @pl.when(it == n_t_blocks - 1)
    def _write():
        sT_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def wkv_scan(r, k, v, w, u, state, *, bt: int = 64, interpret: bool = True):
    """r/k/v/w: (B,T,H,hd); u: (H,hd); state: (B,H,hd,hd) f32.
    Returns (out (B,T,H,hd) f32, final_state (B,H,hd,hd) f32)."""
    B, T, H, hd = r.shape
    bt = min(bt, T)
    assert T % bt == 0
    nt = T // bt

    kernel = functools.partial(_wkv_kernel, bt=bt, n_t_blocks=nt)
    ts = pl.BlockSpec((1, bt, 1, hd), lambda b, h, t: (b, t, h, 0))
    out, sT = pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[
            ts, ts, ts, ts,
            pl.BlockSpec((1, hd), lambda b, h, t: (h, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, 1, hd), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, state)
    return out, sT
