"""Backbone blocks for every family + scan-over-layers stacks.

Each family defines: ``init_block(rng, cfg)``, and a block apply function
``(x, p, cfg, mode, cache, extras, plan) -> (x, new_cache, aux)``.
Blocks are stacked with a leading L axis and consumed by ``lax.scan``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mamba2, moe, rwkv6, ssm


# ----------------------------------------------------------------- init
def init_block(rng, cfg, *, kind: str):
    """kind: dense | moe | hybrid | rwkv | encoder | decoder_x (cross-attn)
    | mamba | attention (the layer kinds of a ``cfg.layer_types``
    pattern)."""
    d = cfg.d_model
    dtype = cfg.dtype
    ks = jax.random.split(rng, 8)
    if kind in LAYER_KINDS:
        mixer = ("mamba", mamba2.init_mamba2(ks[0], cfg)) if kind == "mamba" \
            else ("attn", attention.init_attention(ks[0], cfg))
        return {"ln1": jnp.ones((d,), dtype), mixer[0]: mixer[1],
                "ln2": jnp.ones((d,), dtype),
                "ffn": layers.init_mlp(ks[1], d, cfg.d_ff, cfg.act, dtype)}
    if kind == "rwkv":
        return {
            "ln1": jnp.ones((d,), dtype),
            "tmix": rwkv6.init_time_mix(ks[0], cfg),
            "ln2": jnp.ones((d,), dtype),
            "cmix": rwkv6.init_channel_mix(ks[1], cfg),
        }
    p = {
        "ln1": jnp.ones((d,), dtype),
        "attn": attention.init_attention(ks[0], cfg),
        "ln2": jnp.ones((d,), dtype),
    }
    if kind == "moe":
        p["moe"] = moe.init_moe(ks[1], cfg)
    else:
        p["ffn"] = layers.init_mlp(ks[1], d, cfg.d_ff, cfg.act, dtype)
    if kind == "hybrid":
        p["ssm"] = ssm.init_ssm(ks[2], cfg)
        p["ln_attn_out"] = jnp.ones((d,), dtype)
        p["ln_ssm_out"] = jnp.ones((d,), dtype)
    if kind == "decoder_x":
        p["lnx"] = jnp.ones((d,), dtype)
        p["xattn"] = attention.init_cross_attention(ks[3], cfg)
    return p


# kinds a ``cfg.layer_types`` pattern may name, and the cache leaves
# each keeps: paged K/V, or per-row recurrent state
LAYER_KINDS = ("mamba", "attention")
CACHE_KEYS = {"mamba": ("ssm", "conv"), "attention": ("k", "v")}


def block_kind(cfg) -> str:
    if cfg.layer_types:
        return "pattern"
    if cfg.family == "ssm":
        return "rwkv"
    if cfg.family == "moe":
        return "moe"
    if cfg.family == "hybrid":
        return "hybrid"
    if cfg.cross_attention:
        return "decoder_x"
    return "dense"


# ----------------------------------------------------------------- apply
def apply_block(x, p, cfg, *, kind, mode, cache=None, extras=None, plan=None):
    """Returns (x, new_cache, aux_loss). extras: dict with positions /
    mrope_positions / enc_kv / cache_len as applicable."""
    extras = extras or {}
    aux = jnp.zeros((), jnp.float32)
    eps = cfg.norm_eps

    if kind == "rwkv":
        tcache = None if cache is None else {"state": cache["state"],
                                             "last_x": cache["last_x_t"]}
        ccache = None if cache is None else {"last_x": cache["last_x_c"]}
        h, tnew = rwkv6.time_mix(layers.rmsnorm(x, p["ln1"], eps), p["tmix"],
                                 cfg, tcache)
        x = x + h
        h, cnew = rwkv6.channel_mix(layers.rmsnorm(x, p["ln2"], eps), p["cmix"],
                                    cfg, ccache)
        x = x + h
        new_cache = None
        if mode != "train":
            new_cache = {"state": tnew["state"], "last_x_t": tnew["last_x"],
                         "last_x_c": cnew["last_x"]}
        return x, new_cache, aux

    # --- attention families ---
    h = layers.rmsnorm(x, p["ln1"], eps)
    acache = None
    if cache is not None and "k" in cache:
        acache = {"k": cache["k"], "v": cache["v"]}
    attn_out, acache_new = attention.attention_block(
        h, p["attn"], cfg, mode=mode, cache=acache,
        cache_len=extras.get("cache_len"),
        positions=extras.get("positions"),
        mrope_positions=extras.get("mrope_positions"), plan=plan,
        block_table=extras.get("block_table"),
        paged_kernel=extras.get("paged_kernel", False),
        n_write=extras.get("n_write"), layer=extras.get("layer"))

    if kind == "hybrid":
        scache = None if cache is None else {"state": cache["ssm_state"]}
        ssm_out, snew = ssm.ssm_block(h, p["ssm"], cfg, scache)
        attn_out = layers.rmsnorm(attn_out, p["ln_attn_out"], eps)
        ssm_out = layers.rmsnorm(ssm_out, p["ln_ssm_out"], eps)
        x = x + 0.5 * (attn_out + ssm_out)
    else:
        x = x + attn_out
        snew = None

    if kind == "decoder_x":
        hx = layers.rmsnorm(x, p["lnx"], eps)
        x = x + attention.cross_attention_block(hx, extras["enc_kv"],
                                                p["xattn"], cfg)

    h = layers.rmsnorm(x, p["ln2"], eps)
    if kind == "moe":
        ffn_out, aux = moe.moe_ffn(h, p["moe"], cfg, plan)
    else:
        ffn_out = layers.mlp(h, p["ffn"], cfg.act)
    x = x + ffn_out

    new_cache = None
    if mode != "train" and (acache_new is not None or snew is not None):
        new_cache = {}
        if acache_new is not None:
            new_cache.update(acache_new)
        if snew is not None:
            new_cache["ssm_state"] = snew["state"]
    return x, new_cache, aux


def apply_layer(x, p, cfg, *, kind, mode, cache=None, extras=None,
                plan=None):
    """One layer of a ``cfg.layer_types`` pattern: RMSNorm, the mixer
    (Mamba-2 or attention), RMSNorm, the MLP, each branch scaled by
    ``cfg.residual_multiplier`` into the residual. Returns (x, new_cache);
    the cache is the layer's ``CACHE_KEYS[kind]`` leaves."""
    extras = extras or {}
    r = cfg.residual_multiplier
    h = layers.rmsnorm(x, p["ln1"], cfg.norm_eps)
    if kind == "mamba":
        if mode == "decode" and h.shape[1] == 1:
            out, new_cache = mamba2.mamba2_step(h, p["mamba"], cfg, cache,
                                                extras.get("advance"))
        else:
            # a window: prefill / admission from zeros, or a chunk window
            # from the rows' carried state; n_valid right-pads each row
            out, new_cache = mamba2.mamba2_window(
                h, p["mamba"], cfg, state=cache,
                n_valid=extras.get("n_valid", extras.get("n_write")))
    else:
        out, new_cache = attention.attention_block(
            h, p["attn"], cfg, mode=mode, cache=cache,
            cache_len=extras.get("cache_len"), plan=plan,
            block_table=extras.get("block_table"),
            paged_kernel=extras.get("paged_kernel", False),
            n_write=extras.get("n_write"), layer=extras.get("layer"))
    x = x + r * out
    h = layers.rmsnorm(x, p["ln2"], cfg.norm_eps)
    return x + r * layers.mlp(h, p["ffn"], cfg.act), new_cache


# ----------------------------------------------------------------- stack
def init_stack(rng, cfg, n_layers: int, kind: str):
    if kind == "pattern":
        ks = jax.random.split(rng, len(LAYER_KINDS))
        return {k: layers.stacked(r, cfg.layer_types.count(k),
                                  lambda kk, k=k: init_block(kk, cfg, kind=k))
                for k, r in zip(LAYER_KINDS, ks) if k in cfg.layer_types}
    return layers.stacked(rng, n_layers,
                          lambda k: init_block(k, cfg, kind=kind))


def _runs(period: tuple) -> list:
    """``(kind, first index of the kind in the period, length)`` of each
    run of consecutive same-kind layers of the period."""
    runs, seen = [], {}
    for kind in period:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return [tuple(r) for r in runs]


def apply_pattern_stack(x, blocks, cfg, *, mode, cache=None, extras=None,
                        plan=None):
    """A stack of mixed layer kinds (``cfg.layer_types``): a scan over
    the periods of the pattern, each period's layers in order inside it,
    every run of same-kind layers an inner scan (so the program holds
    one body per run, not per layer). ``blocks`` holds each kind's
    layers stacked in layer order (``{"mamba": (n_m, ...), "attention":
    (n_a, ...)}``), the cache each kind's ``CACHE_KEYS`` leaves the same
    way. The cache rides the scans as carry and each layer updates its
    own slice in place; a paged attention layer (a ``block_table`` in
    ``extras``) takes the whole stacked pool and its layer index, and
    the kernel reads and writes that layer's pages in place. A prefill
    (no cache given) fills zeroed leaves with each layer's K/V and final
    state. Returns (x, cache, 0)."""
    period = cfg.period
    count = {k: period.count(k) for k in set(period)}
    runs = _runs(period)
    fresh = cache is None
    paged = (extras or {}).get("block_table") is not None
    if mode != "train" and fresh:
        shapes = jax.eval_shape(lambda h: _layer_outputs(h, blocks, cfg, mode,
                                                         extras, plan), x)
        cache = {n: jnp.zeros(a.shape, a.dtype) for n, a in shapes.items()}

    def layer(kind, l, h, c):
        p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, l, keepdims=False), blocks[kind])
        if paged and kind == "attention":
            h, nc = apply_layer(h, p, cfg, kind=kind, mode=mode,
                                cache={n: c[n] for n in CACHE_KEYS[kind]},
                                extras=dict(extras, layer=l), plan=plan)
            return h, dict(c, **nc)
        ci = None if fresh else {n: jax.lax.dynamic_index_in_dim(
            c[n], l, keepdims=False) for n in CACHE_KEYS[kind]}
        h, nc = apply_layer(h, p, cfg, kind=kind, mode=mode, cache=ci,
                            extras=extras, plan=plan)
        if c is not None:
            c = dict(c, **{n: jax.lax.dynamic_update_index_in_dim(
                c[n], nc[n].astype(c[n].dtype), l, 0) for n in nc})
        return h, c

    def body(carry, per):
        h, c = carry
        if plan is not None and mode == "train":
            h = plan.constrain_residual(h)
        for kind, first, n in runs:
            base = per * count[kind] + first
            if n == 1:
                h, c = layer(kind, base, h, c)
            else:
                (h, c), _ = jax.lax.scan(
                    lambda hc, j, kind=kind, base=base:
                    (layer(kind, base + j, *hc), None),
                    (h, c), jnp.arange(n))
        return (h, c), None

    fn = jax.checkpoint(body) if (cfg.remat and mode == "train") else body
    n_per = cfg.n_layers // len(period)
    (x, cache), _ = jax.lax.scan(fn, (x, None if mode == "train" else cache),
                                 jnp.arange(n_per))
    return x, cache, jnp.zeros((), jnp.float32)


def _layer_outputs(h, blocks, cfg, mode, extras, plan) -> dict:
    """The cache leaves a fresh prefill of ``h`` leaves: one layer of
    each kind run for its output shapes, stacked over the kind's layers
    (for ``jax.eval_shape`` only)."""
    out = {}
    for kind in sorted(set(cfg.layer_types)):
        p = jax.tree.map(lambda a: a[0], blocks[kind])
        _, nc = apply_layer(h, p, cfg, kind=kind, mode=mode, extras=extras,
                            plan=plan)
        n = cfg.layer_types.count(kind)
        out.update({k: jnp.zeros((n,) + v.shape, v.dtype)
                    for k, v in nc.items()})
    return out


def apply_stack(x, blocks, cfg, *, kind, mode, cache=None, extras=None,
                plan=None):
    """Apply the stacked layer params.

    All modes scan over the L axis. (§Perf iteration log: unrolling the
    decode loop was tried and REFUTED — rebuilding the stacked cache with
    ``jnp.stack`` plus per-layer dtype converts kept more buffers live
    than the scan's in-place loop state: 33.7 vs 27.0 GiB peak on the
    deepseek-7b x decode_32k dry-run.)

    cache: pytree stacked over L (or None). Returns (x, new_cache, aux_sum).

    A paged cache (a ``block_table`` in ``extras``) rides the scan as
    carry, whole, beside the layer index: each layer's kernel reads and
    writes its own pages of the stacked pool in place, so the step
    neither slices the pool per layer nor rebuilds it as a scan output.
    """
    if (extras or {}).get("block_table") is not None:
        def paged_body(carry, xs):
            h, c = carry
            bp, l = xs
            h, c, aux = apply_block(h, bp, cfg, kind=kind, mode=mode,
                                    cache=c, extras=dict(extras, layer=l),
                                    plan=plan)
            return (h, c), aux

        n_layers = jax.tree.leaves(blocks)[0].shape[0]
        (x, cache), aux = jax.lax.scan(paged_body, (x, cache),
                                       (blocks, jnp.arange(n_layers)))
        return x, cache, jnp.sum(aux)

    def body(carry, xs):
        h = carry
        bp, c = xs
        if plan is not None and mode == "train":
            h = plan.constrain_residual(h)
        h, new_c, aux = apply_block(h, bp, cfg, kind=kind, mode=mode,
                                    cache=c, extras=extras, plan=plan)
        return h, (new_c, aux)

    fn = jax.checkpoint(body) if (cfg.remat and mode == "train") else body
    xs = (blocks, cache)
    x, (new_cache, aux) = jax.lax.scan(fn, x, xs)
    return x, new_cache, jnp.sum(aux)
