"""Mixture-of-Experts FFN with capacity-based top-k dispatch.

Two distributed modes (picked by divisibility against the mesh ``model``
axis — see ``repro.sharding.rules``):

* **EP** (expert-parallel, kimi-k2: 384 experts / 16 = 24 per group):
  each model-axis group owns a contiguous expert slice; every group
  dispatches its *local tokens* to its *local experts* and the partial
  outputs are ``psum``-ed over the model axis.
* **TP** (expert-tensor-parallel, grok-1: 8 experts < 16 groups): every
  group holds all experts but only a ``d_ff / model`` slice; the expert
  contraction is partial over d_ff and ``psum``-ed.

The dispatch is sort-free: slot positions come from a one-hot prefix
count, so it lowers to cumsum + scatter (no dynamic shapes) and is
identical on a single device (E_loc = E, no psum) for smoke tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models import layers


def init_moe(rng, cfg, dtype=None):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    dtype = dtype or cfg.dtype
    ks = jax.random.split(rng, 4)

    def e_init(k, din, dout):
        return jax.vmap(lambda kk: layers.dense_init(kk, din, dout, dtype))(
            jax.random.split(k, E))

    p = {
        "router": layers.dense_init(ks[0], d, E, jnp.float32),
        "w_in": e_init(ks[1], d, f),
        "w_out": e_init(ks[2], f, d),
    }
    if cfg.act in layers.GATED_ACTS:
        p["w_gate"] = e_init(ks[3], d, f)
    return p


def capacity(n_tokens: int, cfg) -> int:
    """Static per-expert slot count for a local token block."""
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / max(cfg.n_experts, 1))
    return max(8, -(-c // 8) * 8)  # round up to 8


def route(x2d: jnp.ndarray, router: jnp.ndarray, cfg):
    """Returns (weights (T,k), ids (T,k), aux_loss scalar)."""
    logits = (x2d.astype(jnp.float32) @ router)             # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    # Switch-style load-balance loss + router z-loss
    me = jnp.mean(probs, axis=0)                            # (E,)
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], cfg.n_experts, dtype=jnp.float32), axis=0)
    aux = cfg.n_experts * jnp.sum(me * ce)
    zloss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return w, ids, cfg.router_aux_weight * aux + 1e-3 * zloss


def dispatch_tables(ids, w, e0: int, E_loc: int, C: int):
    """Slot assignment for experts [e0, e0+E_loc).

    Returns (token_idx (E_loc, C) int32 in [0, T] where T = pad,
             gate_w (E_loc, C) f32).
    """
    T, k = ids.shape
    P = T * k
    pair_e = ids.reshape(P)
    pair_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    pair_w = w.reshape(P).astype(jnp.float32)

    le = pair_e - e0
    in_range = (le >= 0) & (le < E_loc)
    le = jnp.where(in_range, le, E_loc)                     # E_loc = dump row
    onehot = jax.nn.one_hot(le, E_loc + 1, dtype=jnp.int32)  # (P, E_loc+1)
    prefix = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.sum(prefix * onehot, axis=-1)                  # (P,)
    keep = in_range & (pos < C)
    row = jnp.where(keep, le, E_loc)
    col = jnp.where(keep, pos, 0)
    tok = jnp.full((E_loc + 1, C), T, jnp.int32)
    tok = tok.at[row, col].set(jnp.where(keep, pair_t, T))
    gw = jnp.zeros((E_loc + 1, C), jnp.float32)
    gw = gw.at[row, col].set(jnp.where(keep, pair_w, 0.0))
    return tok[:E_loc], gw[:E_loc]


def expert_compute(g, p_experts, cfg, slice_f=None):
    """g: (E_loc, C, d) -> (E_loc, C, d) through each expert's FFN."""
    w_in, w_out = p_experts["w_in"], p_experts["w_out"]
    if "w_gate" in p_experts:
        h = layers.act_fn(cfg.act)(
            jnp.einsum("ecd,edf->ecf", g, p_experts["w_gate"])) * \
            jnp.einsum("ecd,edf->ecf", g, w_in)
    else:
        h = layers.act_fn(cfg.act)(jnp.einsum("ecd,edf->ecf", g, w_in))
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def moe_ffn_local(x2d, p, cfg, *, e0=0, E_loc=None, expert_slice=None):
    """Contribution of experts [e0, e0+E_loc) for local tokens x2d (T, d).

    expert_slice: optional fn selecting the local expert-weight block.
    Returns (out (T, d) — PARTIAL if E_loc < n_experts, aux_loss).
    """
    T, d = x2d.shape
    E_loc = cfg.n_experts if E_loc is None else E_loc
    C = capacity(T, cfg)
    w, ids, aux = route(x2d, p["router"], cfg)
    tok, gw = dispatch_tables(ids, w, e0, E_loc, C)
    xp = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
    g = xp[tok]                                             # (E_loc, C, d)
    pe = {k_: v for k_, v in p.items() if k_ != "router"}
    if expert_slice is not None:
        pe = expert_slice(pe)
    elif E_loc < cfg.n_experts and \
            all(v.shape[0] == cfg.n_experts for v in pe.values()):
        # local API with a sub-range of experts: slice the weight block
        # (under shard_map the weights arrive pre-sliced instead)
        pe = {k_: v[e0:e0 + E_loc] for k_, v in pe.items()}
    y = expert_compute(g, pe, cfg)
    y = y * gw[..., None].astype(y.dtype)
    out = jnp.zeros((T + 1, d), y.dtype)
    out = out.at[tok].add(y)
    return out[:T].astype(x2d.dtype), aux


def _flat_index(axes) -> "jnp.ndarray":
    """Row-major device index over a tuple of mesh axes (inside shard_map)."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def moe_decode_ffn(x, p, cfg, plan):
    """Weight-stationary MoE decode (§Perf, kimi-k2 x decode_32k).

    At decode the token batch is ~MBs while the expert weights are ~GBs
    per layer, so the train-mode pattern (all-gather the FSDP-sharded
    expert rows into the shard_map) moves 5 orders of magnitude more
    bytes than the tokens. Instead: keep the 2-D weight layout resident
    (EP: (E/model, d/fsdp, f); TP: (E, d/fsdp, f/model)), all-gather the
    TOKENS over the fsdp axes, contract partially, and psum the partial
    token outputs — fsdp for the d-contraction, model for the expert
    (EP) or f (TP) partials. Collective bytes per layer drop from the
    weight bytes to a few token-sized buffers.
    """
    from jax.sharding import PartitionSpec as P
    B, S, d = x.shape
    mesh, maxis = plan.mesh, plan.model_axis
    fsdp = tuple(a for a in (plan.weight_fsdp if isinstance(
        plan.weight_fsdp, tuple) else (plan.weight_fsdp,)) if a)
    n_model = plan.axis_size(maxis)
    n_fsdp = plan.axis_size(fsdp) if fsdp else 1
    E_loc = cfg.n_experts // n_model if plan.moe_mode == "ep" \
        else cfg.n_experts
    b_ax = plan._div(B, plan.batch_axes)
    d_loc = d // n_fsdp

    # weight specs mirroring rules.param_spec's moe branch
    if plan.moe_mode == "ep":
        wspec = {"router": P(None),
                 "w_in": P(maxis, fsdp or None, None),
                 "w_gate": P(maxis, fsdp or None, None),
                 "w_out": P(maxis, None, fsdp or None)}
    else:
        wspec = {"router": P(None),
                 "w_in": P(None, fsdp or None, maxis),
                 "w_gate": P(None, fsdp or None, maxis),
                 "w_out": P(None, maxis, fsdp or None)}
    wspec = {k_: wspec[k_] for k_ in p}

    b_axes = (b_ax,) if isinstance(b_ax, str) else tuple(b_ax or ())

    def body(xb, pb):
        # ---- gather all tokens (tiny at decode) to every device
        if b_axes:
            xg = jax.lax.all_gather(xb, b_axes, axis=0, tiled=True)
        else:
            xg = xb
        T = xg.shape[0] * xg.shape[1]
        x2d = xg.reshape(T, d)
        w, ids, aux = route(x2d, pb["router"], cfg)      # replicated compute
        e0 = jax.lax.axis_index(maxis) * E_loc if plan.moe_mode == "ep" \
            else 0
        C = capacity(T, cfg)
        tok, gw = dispatch_tables(ids, w, e0, E_loc, C)
        xp = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
        g = xp[tok]                                      # (E_loc, C, d)
        # ---- partial contraction over this device's d rows
        i_f = _flat_index(fsdp) if fsdp else jnp.zeros((), jnp.int32)
        g_loc = jax.lax.dynamic_slice_in_dim(g, i_f * d_loc, d_loc, axis=2)
        w_in, w_out = pb["w_in"], pb["w_out"]
        h = jnp.einsum("ecd,edf->ecf", g_loc, w_in)
        if "w_gate" in pb:
            hg = jnp.einsum("ecd,edf->ecf", g_loc, pb["w_gate"])
            if fsdp:
                h = jax.lax.psum(h, fsdp)
                hg = jax.lax.psum(hg, fsdp)
            h = layers.act_fn(cfg.act)(hg) * h
        else:
            if fsdp:
                h = jax.lax.psum(h, fsdp)
            h = layers.act_fn(cfg.act)(h)
        y = jnp.einsum("ecf,efd->ecd", h, w_out)         # (E_loc, C, d_loc)
        y = y * gw[..., None].astype(y.dtype)
        out = jnp.zeros((T + 1, d_loc), y.dtype)
        out = out.at[tok].add(y)
        out = out[:T]
        # EP: expert partials; TP: f partials — both close over model
        out = jax.lax.psum(out, maxis)
        if fsdp:                                         # reassemble d
            out = jax.lax.all_gather(out, fsdp, axis=1, tiled=True)
        out = out.reshape(xg.shape[0], S, d).astype(x.dtype)
        if b_axes:                                       # back to my batch
            i_b = _flat_index(b_axes)
            out = jax.lax.dynamic_slice_in_dim(
                out, i_b * xb.shape[0], xb.shape[0], axis=0)
        aux = jax.lax.pmean(aux, b_axes + (maxis,))
        return out, aux

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(b_ax, None, None), wspec),
                       out_specs=(P(b_ax, None, None), P()),
                       check_vma=False)
    return fn(x, p)


def moe_ffn(x, p, cfg, plan=None):
    """x: (B, S, d). plan: repro.sharding.rules.ParallelPlan or None.

    Returns (out (B,S,d), aux_loss scalar).
    """
    B, S, d = x.shape
    if plan is None or plan.mesh is None or not plan.moe_mode:
        out, aux = moe_ffn_local(x.reshape(B * S, d), p, cfg)
        return out.reshape(B, S, d), aux

    if plan.kind == "decode":
        return moe_decode_ffn(x, p, cfg, plan)

    from jax.sharding import PartitionSpec as P
    mesh = plan.mesh
    maxis = plan.model_axis               # "model"
    n_model = plan.axis_size(maxis)
    mode = plan.moe_mode                  # "ep" | "tp"
    # batch dim replicates when not divisible (e.g. decode with B=1)
    b_ax = plan._div(B, plan.batch_axes)
    batch_axes = (b_ax,) if isinstance(b_ax, str) else (b_ax or ())

    xspec = P(b_ax, None, None)
    if mode == "ep":
        E_loc = cfg.n_experts // n_model
        wspec = {k_: (P(None) if k_ == "router" else P(maxis, None, None))
                 for k_ in p}

        def body(xb, pb):
            i = jax.lax.axis_index(maxis)
            Bb, Sb, _ = xb.shape
            out, aux = moe_ffn_local(xb.reshape(Bb * Sb, d), pb, cfg,
                                     e0=i * E_loc, E_loc=E_loc)
            out = jax.lax.psum(out, maxis)
            aux = jax.lax.pmean(aux, batch_axes + (maxis,))
            return out.reshape(Bb, Sb, d), aux
    else:  # tp: all experts, d_ff sliced over model axis
        wspec = {k_: (P(None) if k_ == "router" else P(None, None, maxis))
                 for k_ in p}
        wspec["w_out"] = P(None, maxis, None)

        def body(xb, pb):
            Bb, Sb, _ = xb.shape
            out, aux = moe_ffn_local(xb.reshape(Bb * Sb, d), pb, cfg)
            out = jax.lax.psum(out, maxis)
            aux = jax.lax.pmean(aux, batch_axes + (maxis,))
            return out.reshape(Bb, Sb, d), aux

    wspec_tree = {k_: wspec[k_] for k_ in p}
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(xspec, wspec_tree),
                       out_specs=(xspec, P()),
                       check_vma=False)
    return fn(x, p)
