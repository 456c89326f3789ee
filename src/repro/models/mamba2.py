"""Mamba-2 mixer [arXiv:2405.21060], as in GraniteMoeHybrid / Nemotron-H:

    z, xBC, dt = in_proj(u)
    xBC        = silu(causal_conv1d(xBC) + conv_b);  x, B, C = split(xBC)
    dt         = softplus(dt + dt_bias);  A = -exp(A_log)     (per head)
    h_t        = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
    y_t        = C_t . h_t + D x_t
    out        = out_proj(rmsnorm(y * silu(z)) * norm)

Heads share ``B`` and ``C`` within a group. Two forms of the same
recurrence:

* :func:`mamba2_window` — a window of tokens per row (a prefill, an
  admission, a chunk window), in the chunked SSD (state-space duality)
  form: within a chunk of ``cfg.mamba_chunk`` tokens the output is one
  masked matrix product, across chunks a short scan carries the state.
  Each row may start from a carried state and may be right-padded:
  positions past its ``n_valid`` get ``dt = 0`` (no decay, no input)
  and do not move the conv state, so the row ends in exactly the state
  of its true length.
* :func:`mamba2_step` — one token per row (decode), the recurrence
  itself; rows with ``advance`` false keep their state.

The SSM state is float32, ``(B, heads, head_dim, state)``; the conv
state holds the last ``conv - 1`` inputs of the conv, in the model dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers

HIGHEST = jax.lax.Precision.HIGHEST


def init_mamba2(rng, cfg, dtype=None):
    d, H, di, C = cfg.d_model, cfg.mamba_heads, cfg.mamba_inner, \
        cfg.mamba_conv_dim
    dtype = dtype or cfg.dtype
    ks = jax.random.split(rng, 5)
    # the published initialisation: dt log-uniform in [1e-3, 1e-1]
    # through dt_bias = softplus^-1(dt), A = -exp(A_log) in [-16, -1]
    dt = jnp.exp(jax.random.uniform(ks[4], (H,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "in_proj": layers.dense_init(ks[0], d, di + C + H, dtype),
        "conv_w": layers.dense_init(ks[1], cfg.mamba_conv, C, dtype),
        "conv_b": jnp.zeros((C,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(ks[2], (H,), jnp.float32,
                                            1.0, 16.0)).astype(dtype),
        "D": jnp.ones((H,), dtype),
        "norm": jnp.ones((di,), dtype),
        "out_proj": layers.dense_init(ks[3], di, d, dtype),
    }


def init_state(cfg, batch: int) -> dict:
    """Zeroed recurrent state of one Mamba-2 layer for ``batch`` rows."""
    return {
        "ssm": jnp.zeros((batch, cfg.mamba_heads, cfg.mamba_head_dim,
                          cfg.mamba_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.mamba_conv - 1, cfg.mamba_conv_dim),
                          cfg.dtype),
    }


def _split(zxbcdt, cfg):
    di, C = cfg.mamba_inner, cfg.mamba_conv_dim
    return zxbcdt[..., :di], zxbcdt[..., di:di + C], zxbcdt[..., di + C:]


def _heads(xbc, cfg):
    """Split conv outputs (..., C) into x (..., G, Hg, P), B and C
    (..., G, N), all float32."""
    G, N, P = cfg.mamba_groups, cfg.mamba_state, cfg.mamba_head_dim
    Hg = cfg.mamba_heads // G
    di = cfg.mamba_inner
    xbc = xbc.astype(jnp.float32)
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(*lead, G, Hg, P)
    Bm = xbc[..., di:di + G * N].reshape(*lead, G, N)
    Cm = xbc[..., di + G * N:].reshape(*lead, G, N)
    return x, Bm, Cm


def _dt(dt_raw, p):
    return jax.nn.softplus(dt_raw.astype(jnp.float32)
                           + p["dt_bias"].astype(jnp.float32))


def _gate_norm(y, z, p, cfg):
    """rmsnorm(y * silu(z)) * norm over the inner width, in float32."""
    with jax.named_scope("mamba2.gate_norm"):
        g = y * jax.nn.silu(z.astype(jnp.float32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + cfg.norm_eps)
        return (g * p["norm"].astype(jnp.float32)).astype(z.dtype)


def ssd(x, dt, A, Bm, Cm, h0, chunk: int):
    """Chunked SSD scan. x (B, T, G, Hg, P), dt (B, T, G, Hg), A (G, Hg),
    Bm / Cm (B, T, G, N), h0 (B, G, Hg, P, N), all float32. Returns
    (y (B, T, G, Hg, P), the state after the last position)."""
    Bsz, T = x.shape[:2]
    Q = min(chunk, T)
    pad = (-T) % Q
    if pad:
        padt = lambda a: jnp.pad(a, ((0, 0), (0, pad))  # noqa: E731
                                 + ((0, 0),) * (a.ndim - 2))
        x, dt, Bm, Cm = padt(x), padt(dt), padt(Bm), padt(Cm)
    nc = (T + pad) // Q
    ch = lambda a: a.reshape(Bsz, nc, Q, *a.shape[2:])      # noqa: E731
    x, dt, Bm, Cm = ch(x), ch(dt), ch(Bm), ch(Cm)
    acum = jnp.cumsum(dt * A, axis=2)                 # (B,nc,Q,G,Hg) <= 0
    # within a chunk: y_i = sum_{j<=i} (C_i.B_j) exp(acum_i - acum_j) dt_j x_j
    seg = acum[:, :, :, None] - acum[:, :, None, :]  # (B,nc,Qi,Qj,G,Hg)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcijg", Cm, Bm, precision=HIGHEST)
    w = cb[..., None] * decay * dt[:, :, None]        # (B,nc,Qi,Qj,G,Hg)
    y = jnp.einsum("bcijgh,bcjghp->bcighp", w, x, precision=HIGHEST)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(acum[:, :, -1:] - acum) * dt     # (B,nc,Q,G,Hg)
    s = jnp.einsum("bcjgh,bcjghp,bcjgn->bcghpn", to_end, x, Bm,
                   precision=HIGHEST)
    chunk_decay = jnp.exp(acum[:, :, -1])             # (B,nc,G,Hg)

    def carry(h, inp):
        s_c, d_c = inp
        return d_c[..., None, None] * h + s_c, h

    h_last, h_start = jax.lax.scan(
        carry, h0, (jnp.moveaxis(s, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_start = jnp.moveaxis(h_start, 0, 1)             # (B,nc,G,Hg,P,N)
    y = y + jnp.einsum("bcign,bcghpn->bcighp", Cm, h_start,
                       precision=HIGHEST) * jnp.exp(acum)[..., None]
    y = y.reshape(Bsz, nc * Q, *y.shape[3:])[:, :T]
    return y, h_last


def mamba2_window(u, p, cfg, state=None, n_valid=None):
    """A window of tokens per row. u (B, T, d); ``state`` the rows'
    carried state (None: zeros, a fresh sequence); ``n_valid`` (B,)
    real tokens per row, the rest right-padding (None: all T). Returns
    (out (B, T, d), state after each row's ``n_valid`` tokens)."""
    B, T, _ = u.shape
    K = cfg.mamba_conv
    G, Hg = cfg.mamba_groups, cfg.mamba_heads // cfg.mamba_groups
    if state is None:
        state = init_state(cfg, B)
    n = jnp.full((B,), T, jnp.int32) if n_valid is None \
        else jnp.asarray(n_valid, jnp.int32).reshape(-1)
    z, xbc, dt_raw = _split(u @ p["in_proj"], cfg)
    with jax.named_scope("mamba2.conv"):
        xpad = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc], 1)
        w = p["conv_w"].astype(jnp.float32)
        conv = sum(xpad[:, k:k + T].astype(jnp.float32) * w[k]
                   for k in range(K)) + p["conv_b"].astype(jnp.float32)
        xbc = jax.nn.silu(conv).astype(u.dtype)
        # the conv's last K-1 inputs before each row's end: xpad index i
        # holds position i - (K-1), so they sit at n .. n + K - 2
        idx = n[:, None] + jnp.arange(K - 1)[None, :]
        new_conv = jnp.take_along_axis(xpad, idx[:, :, None], axis=1)
    x, Bm, Cm = _heads(xbc, cfg)
    valid = jnp.arange(T)[None, :] < n[:, None]
    dt = jnp.where(valid[..., None], _dt(dt_raw, p), 0.0)
    dt = dt.reshape(B, T, G, Hg)
    A = -jnp.exp(p["A_log"].astype(jnp.float32)).reshape(G, Hg)
    P, N = cfg.mamba_head_dim, cfg.mamba_state
    h0 = state["ssm"].reshape(B, G, Hg, P, N)
    with jax.named_scope("mamba2.ssd"):
        y, h = ssd(x, dt, A, Bm, Cm, h0, cfg.mamba_chunk)
        y = y + p["D"].astype(jnp.float32).reshape(G, Hg)[..., None] * x
    y = _gate_norm(y.reshape(B, T, cfg.mamba_inner), z, p, cfg)
    return y @ p["out_proj"], {"ssm": h.reshape(state["ssm"].shape),
                               "conv": new_conv.astype(state["conv"].dtype)}


def mamba2_step(u, p, cfg, state, advance=None):
    """One token per row. u (B, 1, d); ``advance`` (B,) bool: rows whose
    state this token moves (None: all). Returns (out (B, 1, d), state)."""
    B = u.shape[0]
    G, Hg = cfg.mamba_groups, cfg.mamba_heads // cfg.mamba_groups
    P, N = cfg.mamba_head_dim, cfg.mamba_state
    z, xbc, dt_raw = _split(u[:, 0] @ p["in_proj"], cfg)
    with jax.named_scope("mamba2.conv"):
        win = jnp.concatenate([state["conv"].astype(xbc.dtype),
                               xbc[:, None]], 1)       # (B, K, C)
        conv = jnp.einsum("bkc,kc->bc", win.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32), precision=HIGHEST)
        xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32)) \
            .astype(u.dtype)
    x, Bm, Cm = _heads(xbc, cfg)                      # (B,G,Hg,P), (B,G,N)
    with jax.named_scope("mamba2.step"):
        dt = _dt(dt_raw, p).reshape(B, G, Hg)
        A = -jnp.exp(p["A_log"].astype(jnp.float32)).reshape(G, Hg)
        h = state["ssm"].reshape(B, G, Hg, P, N)
        h = jnp.exp(dt * A)[..., None, None] * h \
            + (dt[..., None] * x)[..., None] * Bm[:, :, None, None, :]
        y = jnp.einsum("bghpn,bgn->bghp", h, Cm, precision=HIGHEST) \
            + p["D"].astype(jnp.float32).reshape(G, Hg)[..., None] * x
        new = {"ssm": h.reshape(state["ssm"].shape),
               "conv": win[:, 1:].astype(state["conv"].dtype)}
        if advance is not None:
            keep = jnp.asarray(advance, bool)
            new = {k: jnp.where(keep.reshape((B,) + (1,) * (v.ndim - 1)),
                                v, state[k]) for k, v in new.items()}
    y = _gate_norm(y.reshape(B, cfg.mamba_inner), z, p, cfg)
    return (y @ p["out_proj"])[:, None], new
