"""GQA attention: full/sliding-window causal for train+prefill, and
single-token decode against a (possibly sequence-sharded) KV cache.

These are the pure-jnp paths used for CPU smoke tests and for the dry-run
lowering (the SPMD partitioner turns the softmax/contraction over a
sequence-sharded KV cache into the flash-decoding LSE-combine collectives).
On TPU the hot paths swap in the Pallas kernels from ``repro.kernels``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers


def init_attention(rng, cfg, dtype=None):
    d, hd = cfg.d_model, cfg.hd
    dtype = dtype or cfg.dtype
    ks = jax.random.split(rng, 4)
    p = {
        "w_q": layers.dense_init(ks[0], d, cfg.n_heads * hd, dtype),
        "w_kv": layers.dense_init(ks[1], d, 2 * cfg.n_kv_heads * hd, dtype),
        "w_o": layers.dense_init(ks[2], cfg.n_heads * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def qkv(x, p, cfg, positions=None, mrope_positions=None):
    """Project to q (B,S,Hq,hd), k/v (B,S,Hkv,hd) with rope + qk_norm."""
    B, S, _ = x.shape
    hd = cfg.hd
    q = (x @ p["w_q"]).reshape(B, S, cfg.n_heads, hd)
    kv = (x @ p["w_kv"]).reshape(B, S, 2, cfg.n_kv_heads, hd)
    k, v = kv[:, :, 0], kv[:, :, 1]
    if cfg.qk_norm:
        q = layers.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope == "rope":
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        assert mrope_positions is not None
        q = layers.apply_mrope(q, mrope_positions, cfg.rope_theta)
        k = layers.apply_mrope(k, mrope_positions, cfg.rope_theta)
    return q, k, v


def _expand_kv(kv, G: int):
    """(B,T,Hkv,hd) -> (B,T,Hq,hd) by repeating each kv head G times.

    The repeat-KV formulation (vs grouping q into (Hkv,G,hd)) keeps the
    q-head axis intact, so head-sharded attention never reshapes a
    sharded dim — the (Hq)->(Hkv,G) reshape forced an all-to-all rehard
    of q/scores per layer under TP (§Perf, minitron-8b x train_4k). The
    repeat is a broadcast: per device it materializes only local heads.
    """
    if G == 1:
        return kv
    B, T, Hkv, hd = kv.shape
    return jnp.broadcast_to(kv[:, :, :, None, :], (B, T, Hkv, G, hd)) \
        .reshape(B, T, Hkv * G, hd)


def _gqa_scores(q, k, scale=None):
    """q (B,S,Hq,hd), k (B,T,Hkv,hd) -> scores (B,Hq,S,T) in f32, scaled
    by ``scale`` (None: 1/sqrt(hd))."""
    B, S, Hq, hd = q.shape
    kx = _expand_kv(k, Hq // k.shape[2])
    s = jnp.einsum("bshd,bthd->bhst", q, kx,
                   preferred_element_type=jnp.float32)
    if scale is not None:
        return s * scale
    return s / jnp.sqrt(jnp.asarray(hd, jnp.float32))


def _combine(scores, v, Hq: int):
    """scores (B,Hq,S,T) f32, v (B,T,Hkv,hd) -> out (B,S,Hq*hd)."""
    B, _, S, T = scores.shape
    vx = _expand_kv(v, Hq // v.shape[2])
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", w.astype(v.dtype), vx)
    return o.reshape(B, S, Hq * v.shape[-1])


Q_CHUNK = 1024  # query-block size for the chunked jnp path


def _masked_attention(q, k, v, q_offset, *, sliding_window=0, causal=True,
                      scale=None):
    """q (B,S,Hq,hd) at absolute positions q_offset + [0,S)."""
    S, T = q.shape[1], k.shape[1]
    scores = _gqa_scores(q, k, scale)
    i = jnp.arange(S)[:, None] + q_offset     # absolute q positions
    j = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= j <= i
    if sliding_window:
        mask &= j > i - sliding_window
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    return _combine(scores, v, q.shape[2])


def causal_attention(q, k, v, *, sliding_window: int = 0, causal: bool = True,
                     scale=None):
    """Full or sliding-window (causal) attention; q/k/v aligned in time.

    Long sequences are processed in query chunks (``lax.scan``) so the
    score tensor never materializes at (S, T) — the XLA-level analogue of
    the Pallas flash kernel's q-block grid.
    """
    B, S, Hq, hd = q.shape
    T = k.shape[1]
    if S <= Q_CHUNK or S % Q_CHUNK:
        return _masked_attention(q, k, v, T - S, sliding_window=sliding_window,
                                 causal=causal, scale=scale)
    nc = S // Q_CHUNK
    qc = jnp.moveaxis(q.reshape(B, nc, Q_CHUNK, Hq, hd), 1, 0)

    def body(_, inp):
        i, qi = inp
        o = _masked_attention(qi, k, v, T - S + i * Q_CHUNK,
                              sliding_window=sliding_window, causal=causal,
                              scale=scale)
        return None, o

    # flash-attention memory behaviour: recompute each chunk's scores in
    # the backward pass instead of stacking (nc, B, H, Q_CHUNK, T) f32
    # score tensors for it (whisper train: 30 GiB of saved scores, §Perf)
    _, out = jax.lax.scan(jax.checkpoint(body), None, (jnp.arange(nc), qc))
    # out: (nc, B, Q_CHUNK, Hq*hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, Hq * hd)


def decode_attention(q, k_cache, v_cache, n_valid, *, sliding_window: int = 0,
                     scale=None):
    """One new token per sequence attending to the cache.

    q: (B, 1, Hq, hd); k/v_cache: (B, T, Hkv, hd); n_valid: scalar or (B,)
    count of valid cache entries (the new token's K/V already written).

    With the cache sequence axis sharded, the softmax reductions and the
    PV contraction lower to partial-max/partial-sum + all-reduce — i.e.
    flash-decoding style LSE combination, inserted by the partitioner.
    """
    scores = _gqa_scores(q, k_cache, scale)                # (B,Hq,1,T)
    T = k_cache.shape[1]
    j = jnp.arange(T)
    n_valid = jnp.asarray(n_valid)
    valid = j[None, :] < n_valid.reshape(-1, 1)            # (B or 1, T)
    if sliding_window:
        valid &= j[None, :] >= n_valid.reshape(-1, 1) - sliding_window
    scores = jnp.where(valid[:, None, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    return _combine(scores, v_cache, q.shape[2])


def verify_decode_attention(q, k_cache, v_cache, base, *, sliding_window=0,
                            scale=None):
    """Multi-token (speculative verify) decode against a stripe cache.

    q: (B, S, Hq, hd) — S = k+1 tokens per row at absolute positions
    ``base[b] + [0, S)`` (their K/V already written); k/v_cache:
    (B, T, Hkv, hd); base: (B,) tokens cached per row *before* this
    window. Causal masking inside the window: query j attends to cache
    positions <= base[b] + j, so position j's output conditions on the
    committed context plus proposals d_1..d_j — exactly what j+1
    sequential ``decode_attention`` calls would each see.
    """
    scores = _gqa_scores(q, k_cache, scale)                # (B,Hq,S,T)
    S, T = q.shape[1], k_cache.shape[1]
    base = jnp.asarray(base).reshape(-1, 1, 1)             # (B,1,1)
    i = base + jnp.arange(S)[None, :, None]                # abs q position
    j = jnp.arange(T)[None, None, :]
    valid = j <= i
    if sliding_window:
        valid &= j > i - sliding_window
    scores = jnp.where(valid[:, None, :, :], scores,
                       jnp.finfo(jnp.float32).min)
    return _combine(scores, v_cache, q.shape[2])


LANES = 128


def lane_pack(hd: int, n_kv: int) -> int:
    """How many KV heads share one row of the paged pool. A TPU DMA of a
    ``(block_size, hd)`` page must span whole 128-lane rows, so heads
    narrower than 128 are stored side by side: ``LANES // hd`` adjacent
    heads per row (1 when ``hd`` fills the lanes or the heads do not
    divide evenly)."""
    f = LANES // hd if hd < LANES and LANES % hd == 0 else 1
    return f if n_kv % f == 0 else 1


def _lane_packed(fn, q, pool_k, pool_v, k_new, v_new, *args, scale, **kw):
    """Run the paged attention ``fn`` against a lane-packed pool
    (``lane_pack``): K/V rows hold ``f`` adjacent heads, and each query
    is widened to the row with zeros outside its own head's lanes, so
    q.k and p.v see only that head; the output keeps its own lanes.
    Returns what ``fn`` returns, the output unpacked."""
    B, S, Hq, hd = q.shape
    Hkv = k_new.shape[2]
    f = pool_k.shape[-1] // hd
    own = jax.nn.one_hot((jnp.arange(Hq) // (Hq // Hkv)) % f, f,
                         dtype=q.dtype)                      # (Hq, f)
    wide = (q[:, :, :, None, :] * own[:, :, None]).reshape(B, S, Hq, f * hd)
    pack = lambda t: t.reshape(B, S, Hkv // f, f * hd)       # noqa: E731
    out, pool_k, pool_v = fn(wide, pool_k, pool_v, pack(k_new), pack(v_new),
                             *args, scale=hd ** -0.5 if scale is None
                             else scale, **kw)
    out = jnp.sum(out.reshape(B, S, Hq, f, hd) * own[..., None], axis=3)
    return out.reshape(B, S, Hq * hd), pool_k, pool_v


def _gather_pool(pool, block_table):
    """Gather each row's blocks through its table into a stripe:
    (num_blocks, Hkv, bs, hd) -> (B, max_blocks*bs, Hkv, hd)."""
    B, max_blocks = block_table.shape
    _, Hkv, bs, hd = pool.shape
    g = jnp.swapaxes(pool[block_table], 2, 3)         # (B,mb,bs,Hkv,hd)
    return g.reshape(B, max_blocks * bs, Hkv, hd)


def paged_verify_attention(q, pool_k, pool_v, k_new, v_new, block_table,
                           cache_len, n_write, layer, *,
                           sliding_window: int = 0, use_kernel: bool = False,
                           scale=None):
    """Multi-token window against layer ``layer`` of the stacked KV
    block pool (``(L, num_blocks, Hkv, bs, hd)`` per leaf): the
    speculative **verify** step and the **chunked-prefill** step share
    this path (a prompt chunk is a window of known tokens written
    against the partially-resident prompt; the causal-inside-the-window
    mask is exactly the partial-prompt causal mask).

    q/k_new/v_new: (B, S, H*, hd) — S window tokens per row at
    positions ``cache_len[b] + [0, S)``; n_write: (B,) tokens of the
    window row b actually owns blocks for (``n_spec + 1`` when
    verifying, the row's chunk token count when chunk-prefilling; 0 for
    parked riders). Window token j of row b is written at
    ``(block_table[b, (len+j) // bs], (len+j) % bs)`` when ``j <
    n_write[b]`` and never into the row's table otherwise — a row must
    never write speculative K/V into a block it has not been granted
    (it could still be shared with another sequence, or not allocated
    at all). Reads past a row's n_write are garbage but masked out of
    every output the caller commits (acceptance is capped at n_spec).
    Returns (out (B, S, Hq*hd), new_pool_k, new_pool_v).

    ``use_kernel`` runs the **fused multi-token Pallas kernel**
    (``kernels.paged_attention.paged_window_attention``): ONE launch
    covers the whole (q_len, kv_len) window — every window query of
    a row rides the same query tile, masked causally *inside* the
    window (query j of row b sees cache positions <= cache_len[b] + j,
    its per-row base length) — and writes the granted tokens into the
    layer's pages in place (the window's tokens past n_write are not
    written), reading through the scalar-prefetched block table: the
    stacked pool is neither sliced per layer nor copied. The jnp path
    (the CPU reference) scatters into the stacked pool, diverting the
    tokens past n_write to the scratch block, gathers the layer once
    and applies the same causal-in-window mask.
    """
    from repro.serve.blocks import SCRATCH_BLOCK
    if pool_k.shape[-1] != q.shape[-1]:
        return _lane_packed(paged_verify_attention, q, pool_k, pool_v,
                            k_new, v_new, block_table, cache_len, n_write,
                            layer, sliding_window=sliding_window,
                            use_kernel=use_kernel, scale=scale)
    B, S = q.shape[:2]
    base = jnp.asarray(cache_len, jnp.int32).reshape(-1)    # (B,)
    if use_kernel:
        from repro.kernels.paged_attention.ops import (
            paged_window_attention as _window_kernel)
        out, _, pool_k, pool_v = _window_kernel(
            q, k_new, v_new, pool_k, pool_v, block_table, base, layer,
            n_write, sliding_window=sliding_window, scale=scale)
        return out.reshape(B, S, -1), pool_k, pool_v
    bs = pool_k.shape[3]
    pos = base[:, None] + jnp.arange(S)[None, :]            # (B,S)
    rows = jnp.arange(B)
    safe = jnp.arange(S)[None, :] < jnp.asarray(n_write,
                                                jnp.int32).reshape(-1, 1)
    phys = jnp.where(safe, block_table[rows[:, None], pos // bs],
                     SCRATCH_BLOCK)                         # (B,S)
    # head-major pool: the (B,S) advanced indices around the head slice
    # address (B, S, Hkv, hd) sites, the layout of k_new/v_new
    pool_k = pool_k.at[layer, phys, :, pos % bs].set(
        k_new.astype(pool_k.dtype))
    pool_v = pool_v.at[layer, phys, :, pos % bs].set(
        v_new.astype(pool_v.dtype))
    out = verify_decode_attention(q, _gather_pool(pool_k[layer], block_table),
                                  _gather_pool(pool_v[layer], block_table),
                                  base, sliding_window=sliding_window,
                                  scale=scale)
    return out, pool_k, pool_v


def paged_decode_attention(q, pool_k, pool_v, k_new, v_new, block_table,
                           cache_len, layer, *, sliding_window: int = 0,
                           use_kernel: bool = False, scale=None):
    """Decode one token per sequence against layer ``layer`` of a shared
    KV **block pool** stacked over layers.

    q/k_new/v_new: (B, 1, H*, hd); pool_k/pool_v: (L, num_blocks, Hkv,
    bs, hd), head-major so the kernel's per-head block tile is
    contiguous; block_table: (B, max_blocks) int32; cache_len: (B,)
    tokens already cached per row. Row b's logical position j lives at
    ``(block_table[b, j // bs], j % bs)`` — the new token's K/V is
    written there (owned blocks are disjoint across rows — with prefix
    sharing the engine copy-on-writes any shared tail before the step —
    so the writes never collide; unowned table entries point at the
    reserved scratch block 0). Returns (out, new_pool_k, new_pool_v).

    Two paths behind ``use_kernel``:

    * **False (portable jnp reference)** — scatter the token into the
      stacked pool, gather each row's effective cache of the layer
      through its table row into a transient (B, max_blocks*bs) buffer
      and run the same masked ``decode_attention`` as the stripe path,
      so the attention math — and therefore the emitted token stream —
      is unchanged.
    * **True (Pallas kernel)** — ``kernels.paged_attention`` writes the
      token into its page and reads K/V through the block table *in
      place*, the whole stacked pool aliased in and out (the
      scalar-prefetched table and layer address one DMA per page the
      row holds); no transient gather, no per-layer slice. This is the
      q_len = 1 **degenerate case of the fused window kernel** that
      also serves speculative verify and chunked prefill (see
      ``paged_verify_attention``) — one kernel body behind every paged
      consumer. Compiled on TPU, interpret mode elsewhere; held
      bit-exact (f32) against its streaming jnp oracle by the
      differential grids in ``tests/test_kernels.py``.
    """
    if pool_k.shape[-1] != q.shape[-1]:
        return _lane_packed(paged_decode_attention, q, pool_k, pool_v,
                            k_new, v_new, block_table, cache_len, layer,
                            sliding_window=sliding_window,
                            use_kernel=use_kernel, scale=scale)
    idx = jnp.asarray(cache_len, jnp.int32).reshape(-1)     # (B,)
    B = block_table.shape[0]
    if use_kernel:
        from repro.kernels.paged_attention.ops import (
            paged_decode_attention as _paged_kernel)
        out, _, pool_k, pool_v = _paged_kernel(
            q[:, 0], k_new[:, 0], v_new[:, 0], pool_k, pool_v, block_table,
            idx + 1, layer, sliding_window=sliding_window, scale=scale)
        return out.reshape(B, 1, -1), pool_k, pool_v
    bs = pool_k.shape[3]
    phys = block_table[jnp.arange(B), idx // bs]            # (B,)
    pool_k = pool_k.at[layer, phys, :, idx % bs].set(
        k_new[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[layer, phys, :, idx % bs].set(
        v_new[:, 0].astype(pool_v.dtype))
    out = decode_attention(q, _gather_pool(pool_k[layer], block_table),
                           _gather_pool(pool_v[layer], block_table), idx + 1,
                           sliding_window=sliding_window, scale=scale)
    return out, pool_k, pool_v


def attention_block(x, p, cfg, *, mode: str, cache=None, cache_len=None,
                    positions=None, mrope_positions=None, causal=True,
                    sliding_window=None, plan=None, block_table=None,
                    paged_kernel=False, n_write=None, layer=None):
    """Full attention sub-block incl. output proj. Returns (out, new_cache).

    cache: dict(k=(B,T,Hkv,hd), v=(B,T,Hkv,hd)) or None — or, with
    ``block_table`` set, the whole paged pool stacked over layers,
    dict(k=(L,num_blocks,Hkv,bs,hd), ...), of which this block reads
    and writes layer ``layer`` and returns the whole pool.
    In decode mode, ``x`` with more than one token per row is a
    **multi-token window** — a speculative verify window or a chunked
    prefill window: the S tokens write K/V at positions
    ``cache_len[b] + [0, S)`` (paged writes diverted to scratch past
    ``n_write[b]``) and attend causally inside the window against the
    already-resident cache.
    """
    win = cfg.sliding_window if sliding_window is None else sliding_window
    # an explicit softmax scale (Granite's attention_multiplier); None
    # keeps 1/sqrt(hd)
    scale = cfg.attention_multiplier or None
    if mode == "decode" and x.shape[1] > 1:
        # ---- multi-token window (speculative verify / chunked prefill) ----
        B, S, _ = x.shape
        idx = jnp.asarray(cache_len, jnp.int32).reshape(-1)
        pos = idx[:, None] + jnp.arange(S)[None, :]          # (B,S)
        q, k, v = qkv(x, p, cfg, positions=pos,
                      mrope_positions=mrope_positions)
        if block_table is not None:
            nw = jnp.full((B,), S, jnp.int32) if n_write is None \
                else jnp.asarray(n_write, jnp.int32)
            o, k_cache, v_cache = paged_verify_attention(
                q, cache["k"], cache["v"], k, v, block_table, idx, nw,
                layer, sliding_window=win, use_kernel=paged_kernel,
                scale=scale)
        else:
            rows = jnp.arange(B)[:, None]
            k_cache = cache["k"].at[rows, pos].set(
                k.astype(cache["k"].dtype))
            v_cache = cache["v"].at[rows, pos].set(
                v.astype(cache["v"].dtype))
            o = verify_decode_attention(q, k_cache, v_cache, idx,
                                        sliding_window=win, scale=scale)
        return o @ p["w_o"], {"k": k_cache, "v": v_cache}
    if mode == "decode":
        # cache_len = number of tokens already cached; the new token goes
        # at index cache_len and attends to indices [0, cache_len].
        # Scalar cache_len decodes all rows at one length (lock-step);
        # a (B,) vector gives every slot its own length (mixed-length
        # continuous batching — each row ropes, writes, and masks at its
        # own position).
        pos = cache_len if positions is None else positions
        q, k, v = qkv(x, p, cfg, positions=jnp.reshape(pos, (-1, 1)),
                      mrope_positions=mrope_positions)
        if plan is not None and plan.mesh is not None:
            # Flash-decoding layout (§Perf): the single-token q is tiny —
            # replicate its heads so the seq-sharded cache never reshards;
            # each model-group computes partial attention over its KV
            # slice and the softmax/PV reductions close with small psums.
            from jax.sharding import PartitionSpec as P
            b = plan._div(q.shape[0], plan.batch_axes)
            rep = lambda t: jax.lax.with_sharding_constraint(
                t, plan.ns(P(b, None, None, None)))
            q, k, v = rep(q), rep(k), rep(v)
        if block_table is not None:
            # paged KV: cache leaves are the shared block pool
            o, k_cache, v_cache = paged_decode_attention(
                q, cache["k"], cache["v"], k, v, block_table, cache_len,
                layer, sliding_window=win, use_kernel=paged_kernel,
                scale=scale)
        else:
            idx = jnp.asarray(cache_len, jnp.int32)
            if idx.ndim == 0:
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
            else:
                # per-slot write index: scatter row b's K/V at [b, idx[b]]
                rows = jnp.arange(k.shape[0])
                k_cache = cache["k"].at[rows, idx].set(
                    k[:, 0].astype(cache["k"].dtype))
                v_cache = cache["v"].at[rows, idx].set(
                    v[:, 0].astype(cache["v"].dtype))
            o = decode_attention(q, k_cache, v_cache, cache_len + 1,
                                 sliding_window=win, scale=scale)
        if plan is not None and plan.mesh is not None:
            # pin the joined attention output replicated as well — the
            # row-sharded w_o otherwise drags head-sharding back through
            # the combine and the partitioner re-shards the cache
            from jax.sharding import PartitionSpec as P
            o = jax.lax.with_sharding_constraint(
                o, plan.ns(P(plan._div(o.shape[0], plan.batch_axes),
                             None, None)))
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        q, k, v = qkv(x, p, cfg, positions=positions,
                      mrope_positions=mrope_positions)
        o = causal_attention(q, k, v, sliding_window=win, causal=causal,
                             scale=scale)
        new_cache = {"k": k, "v": v} if mode == "prefill" else None
    return o @ p["w_o"], new_cache


# ------------------------------------------------------------- cross-attn
def init_cross_attention(rng, cfg, dtype=None):
    d, hd = cfg.d_model, cfg.hd
    dtype = dtype or cfg.dtype
    ks = jax.random.split(rng, 3)
    return {
        "w_q": layers.dense_init(ks[0], d, cfg.n_heads * hd, dtype),
        "w_kv": layers.dense_init(ks[1], d, 2 * cfg.n_kv_heads * hd, dtype),
        "w_o": layers.dense_init(ks[2], cfg.n_heads * hd, d, dtype),
    }


def cross_attention_block(x, enc_kv, p, cfg):
    """x (B,S,d) attends to precomputed encoder K/V (B,T,Hkv,hd)."""
    B, S, _ = x.shape
    q = (x @ p["w_q"]).reshape(B, S, cfg.n_heads, cfg.hd)
    o = causal_attention(q, enc_kv["k"], enc_kv["v"], causal=False)
    return o @ p["w_o"]


def encode_cross_kv(enc_out, p, cfg):
    B, T, _ = enc_out.shape
    kv = (enc_out @ p["w_kv"]).reshape(B, T, 2, cfg.n_kv_heads, cfg.hd)
    return {"k": kv[:, :, 0], "v": kv[:, :, 1]}
