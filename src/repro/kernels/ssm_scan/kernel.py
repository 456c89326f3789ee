"""Selective (Mamba-style) SSM scan for TPU — hymba's SSM path.

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * u_t
    y_t = C_t . h_t + D * u_t

Grid (B, di/bd, T/bt): the (bd x N) diagonal state lives in VMEM scratch
and is carried across time blocks (innermost "arbitrary" grid dim);
u/dt stream in (bt, bd) tiles and the input-dependent B_t/C_t in (bt, N)
tiles shared by every channel block. Within a block the recurrence is a
``fori_loop`` of rank-1 state updates on the VPU (N = 16 for hymba, so a
state row fits one vreg lane group).

Why this matters (EXPERIMENTS §Roofline): the XLA lowering of the same
scan round-trips the (B, di, N) state through HBM every timestep —
hymba's train memory term is dominated by it. Here the state never
leaves VMEM within a (b, d)-block's pass over T; HBM traffic drops to
the streaming inputs/outputs, which is the kernel's lower bound.

TPU adaptation note: CUDA Mamba kernels hold h in registers per thread
(one channel each) and sync via shared memory; the TPU analogue is the
(bd, N) VMEM tile with VPU lane parallelism over channels — same
dataflow, memory-hierarchy-native.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssm_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
                y_ref, sT_ref, state_ref, *, bt: int, n_t_blocks: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_ref[...] = s0_ref[0]

    A = a_ref[...].astype(jnp.float32)                   # (bd, N)
    D = d_ref[0].astype(jnp.float32)                     # (bd,)

    def step(t, _):
        u_t = u_ref[0, t].astype(jnp.float32)            # (bd,)
        dt_t = dt_ref[0, t].astype(jnp.float32)          # (bd,)
        B_t = b_ref[0, t].astype(jnp.float32)            # (N,)
        C_t = c_ref[0, t].astype(jnp.float32)            # (N,)
        h = state_ref[...]                               # (bd, N)
        dA = jnp.exp(dt_t[:, None] * A)
        h = dA * h + (dt_t * u_t)[:, None] * B_t[None, :]
        state_ref[...] = h
        y_ref[0, t] = (h @ C_t + D * u_t).astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, bt, step, ())

    @pl.when(it == n_t_blocks - 1)
    def _write():
        sT_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("bt", "bd", "interpret"))
def ssm_scan(u, dt, Bm, Cm, A, D, state, *, bt: int = 64, bd: int = 0,
             interpret: bool = True):
    """u/dt: (B,T,di); Bm/Cm: (B,T,N); A: (di,N); D: (di,);
    state: (B,di,N) f32. Returns (y (B,T,di) f32, final_state)."""
    B, T, di = u.shape
    N = Bm.shape[-1]
    bt = min(bt, T)
    assert T % bt == 0, (T, bt)
    if not bd:
        bd = next((c for c in (256, 128, 64, 32) if di % c == 0), di)
    assert di % bd == 0, (di, bd)
    nt, nd = T // bt, di // bd

    kernel = functools.partial(_ssm_kernel, bt=bt, n_t_blocks=nt)
    y, sT = pl.pallas_call(
        kernel,
        grid=(B, nd, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),   # u
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),   # dt
            pl.BlockSpec((1, bt, N), lambda b, d, t: (b, t, 0)),    # B
            pl.BlockSpec((1, bt, N), lambda b, d, t: (b, t, 0)),    # C
            pl.BlockSpec((bd, N), lambda b, d, t: (d, 0)),          # A
            pl.BlockSpec((1, bd), lambda b, d, t: (0, d)),          # D
            pl.BlockSpec((1, bd, N), lambda b, d, t: (b, d, 0)),    # s0
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, bd, N), lambda b, d, t: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, dt, Bm, Cm, A, D.reshape(1, di), state)
    return y, sT
