"""Model factory: one composable entry point for every assigned arch.

``build_model(cfg)`` returns a ``Model`` with pure functions:
    init(rng)                                   -> params
    train_loss(params, batch, plan)             -> (loss, metrics)
    prefill(params, batch, plan)                -> (logits_last, cache)
    decode_step(params, token, cache, cache_len, plan) -> (logits, cache)
    init_cache(batch_size, cache_capacity)      -> zeroed cache pytree
    input_specs(shape)                          -> ShapeDtypeStruct batch

Batch dicts:
    train:   {"tokens": (B, S+1) int32 [, "patch_embeds" | "frames"]}
    prefill: {"tokens": (B, S) int32 [, "patch_embeds" | "frames"]}
    decode:  token (B, 1) int32 + cache + cache_len (existing token count)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mamba2, transformer

GRID = 16  # stub vision patch grid side (n_patches = GRID*GRID when 256)


# ===================================================================== init
def init_params(rng, cfg):
    d, dtype = cfg.d_model, cfg.dtype
    ks = jax.random.split(rng, 8)
    kind = transformer.block_kind(cfg)
    vp = padded_vocab(cfg)
    p: dict[str, Any] = {
        "embed": (jax.random.normal(ks[0], (vp, d), jnp.float32)
                  * 0.02).astype(dtype),
        "blocks": transformer.init_stack(ks[1], cfg, cfg.n_layers, kind),
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.dense_init(ks[2], d, vp, dtype)
    # rope == "learned" (whisper) uses computed sinusoidal positions — no
    # table, so the 32k/500k serving shapes need no max-length carve-out.
    if cfg.encoder_layers:
        p["encoder"] = {
            "blocks": transformer.init_stack(ks[4], cfg, cfg.encoder_layers,
                                             "dense"),
            "final_norm": jnp.ones((d,), dtype),
            "pos_embed": (jax.random.normal(ks[5], (cfg.n_frames, d),
                                            jnp.float32) * 0.02).astype(dtype),
        }
    return p


# ================================================================ embedding
def _embed_tokens(p, cfg, tokens):
    x = p["embed"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _mrope_positions(B, n_patches, s_text):
    """Static M-RoPE position ids (B, 3, P + s_text) for one leading image."""
    g = max(int(n_patches ** 0.5), 1)
    pi = jnp.arange(n_patches)
    patch = jnp.stack([jnp.zeros_like(pi), pi // g, pi % g])      # (3, P)
    t0 = g  # text starts after the grid extent
    ti = jnp.arange(s_text) + t0
    text = jnp.stack([ti, ti, ti])                                 # (3, S)
    pos = jnp.concatenate([patch, text], axis=1)                   # (3, P+S)
    return jnp.broadcast_to(pos[None], (B, 3, pos.shape[1])).astype(jnp.int32)


def _build_inputs(p, cfg, batch, *, drop_last_token: bool):
    """Returns (x (B,S,d), extras, label_offset) for train/prefill."""
    tokens = batch["tokens"]
    if drop_last_token:
        tokens = tokens[:, :-1]
    B, S_text = tokens.shape
    extras: dict[str, Any] = {}
    prefix = 0
    if cfg.frontend == "vision":
        pe = batch["patch_embeds"].astype(cfg.dtype)               # (B,P,d)
        x = jnp.concatenate([pe, _embed_tokens(p, cfg, tokens)], axis=1)
        prefix = pe.shape[1]
        extras["mrope_positions"] = _mrope_positions(B, prefix, S_text)
    else:
        x = _embed_tokens(p, cfg, tokens)
        if cfg.rope == "learned":
            x = x + layers.sinusoidal_pos(jnp.arange(x.shape[1]),
                                          cfg.d_model, x.dtype)[None]
    if cfg.frontend == "audio":
        enc = _run_encoder(p, cfg, batch["frames"].astype(cfg.dtype))
        # precompute per-layer cross K/V from encoder output
        xkv = jax.vmap(lambda bp: attention.encode_cross_kv(enc, bp["xattn"],
                                                            cfg))(p["blocks"])
        extras["enc_kv_stack"] = xkv                                # (L,B,T,H,hd)
    return x, extras, prefix


def _run_encoder(p, cfg, frames):
    e = p["encoder"]
    x = frames + e["pos_embed"][None, : frames.shape[1], :]

    def body(h, bp):
        hh = layers.rmsnorm(h, bp["ln1"], cfg.norm_eps)
        o, _ = attention.attention_block(hh, bp["attn"], cfg, mode="train",
                                         causal=False, sliding_window=0)
        h = h + o
        hh = layers.rmsnorm(h, bp["ln2"], cfg.norm_eps)
        return h + layers.mlp(hh, bp["ffn"], cfg.act), None

    x, _ = jax.lax.scan(body, x, e["blocks"])
    return layers.rmsnorm(x, e["final_norm"], cfg.norm_eps)


def padded_vocab(cfg) -> int:
    """Embedding/head rows padded to a multiple of 128 (shardable over
    any mesh axis <=128; whisper's 51865 and hymba's 32001 otherwise
    force replicated logits — 18 GiB/device at train_4k, §Perf)."""
    return -(-cfg.vocab_size // 128) * 128


def _logits(p, cfg, x):
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    out = x @ head
    vp = head.shape[-1]
    if vp != cfg.vocab_size:          # mask padded ids, keep sharding
        pad_mask = jnp.arange(vp) >= cfg.vocab_size
        out = jnp.where(pad_mask, jnp.asarray(-1e9, out.dtype), out)
    if cfg.logits_scaling != 1.0:
        out = out / jnp.asarray(cfg.logits_scaling, out.dtype)
    return out


# ============================================================ stack wrapper
def _run_stack(p, cfg, x, *, mode, cache, extras, plan):
    kind = transformer.block_kind(cfg)
    if kind == "pattern":
        return transformer.apply_pattern_stack(
            x, p["blocks"], cfg, mode=mode, cache=cache, extras=extras,
            plan=plan)
    if kind == "decoder_x":
        # cross K/V is a per-layer scanned input
        enc_kv_stack = (extras or {}).pop("enc_kv_stack", None)
        if enc_kv_stack is None and cache is not None:
            enc_kv_stack = {"k": cache.pop("xk"), "v": cache.pop("xv")}

        def body(h, xs):
            bp, c, ekv = xs
            ex = dict(extras or {})
            ex["enc_kv"] = ekv
            h, new_c, aux = transformer.apply_block(
                h, bp, cfg, kind=kind, mode=mode, cache=c, extras=ex,
                plan=plan)
            return h, (new_c, aux)

        fn = jax.checkpoint(body) if (cfg.remat and mode == "train") else body
        x, (new_cache, aux) = jax.lax.scan(fn, x, (p["blocks"], cache,
                                                   enc_kv_stack))
        if new_cache is not None and mode != "train":
            new_cache["xk"] = enc_kv_stack["k"]
            new_cache["xv"] = enc_kv_stack["v"]
        return x, new_cache, jnp.sum(aux)
    return transformer.apply_stack(x, p["blocks"], cfg, kind=kind, mode=mode,
                                   cache=cache, extras=extras, plan=plan)


# ===================================================================== model
@dataclass(frozen=True)
class Model:
    cfg: Any

    # ---------------- init ----------------
    def init(self, rng):
        return init_params(rng, self.cfg)

    # ---------------- train ----------------
    def train_loss(self, params, batch, plan=None):
        cfg = self.cfg
        x, extras, prefix = _build_inputs(params, cfg, batch,
                                          drop_last_token=True)
        if plan is not None:
            x = plan.constrain_act(x)
        x, _, aux = _run_stack(params, cfg, x, mode="train", cache=None,
                               extras=extras, plan=plan)
        x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if prefix:
            x = x[:, prefix:, :]
        logits = _logits(params, cfg, x)
        if plan is not None:
            logits = plan.constrain_logits(logits)
        labels = batch["tokens"][:, 1:]
        loss = layers.softmax_xent(logits, labels)
        total = loss + aux
        return total, {"xent": loss, "aux": aux}

    # ---------------- prefill ----------------
    def prefill(self, params, batch, plan=None, *, last_idx=None,
                cache=None, cache_len=None, block_table=None,
                paged_kernel: bool = False, n_write=None):
        """last_idx: optional (B,) int32 — per-row index of the last *real*
        token when rows are right-padded to a shared bucket length (the
        serving engine's batched mixed-length admission). None keeps the
        unpadded behaviour: logits at the final position.

        **Chunked mode** (``cache`` is not None): ``batch["tokens"]``
        (B, S) is a **chunk window** of each row's prompt at start
        offset ``cache_len[b]`` — K/V written into the *resident* cache
        at positions ``cache_len[b] + [0, S)``, each query attending
        causally to everything already resident plus the window's own
        prefix. This is the same multi-token decode path the speculative
        :meth:`verify_step` uses (and inherits its proven differential
        property: position j's logits equal what the j+1-th of S
        sequential :meth:`decode_step` calls would produce), so a prompt
        split into chunk windows reproduces a monolithic prefill
        bit-for-bit. ``block_table``/``paged_kernel``/``n_write`` follow
        :meth:`verify_step` (paged rows write nothing into their blocks
        past their granted count). Returns (logits (B, S, V), cache).
        A ``cfg.layer_types`` stack's Mamba-2 layers run the window from
        the rows' carried state, each row over its first ``n_write``
        tokens. Other recurrent families (rwkv / hymba's SSM) cannot
        chunk — their state steps token-at-a-time — and raise. With
        ``last_idx`` set in chunked mode, only each row's last-real-
        position logits are computed (returned as (B, 1, V)) — a chunk
        caller samples at most one token per row, so projecting the
        whole window against the vocabulary would be pure waste."""
        if cache is not None:
            x, new_cache = self._window(params, batch["tokens"], cache,
                                        cache_len, plan, block_table,
                                        paged_kernel, n_write)
            if last_idx is not None:
                idx = jnp.asarray(last_idx, jnp.int32)
                x = x[jnp.arange(x.shape[0]), idx][:, None, :]
            return _logits(params, self.cfg, x), new_cache
        cfg = self.cfg
        x, extras, prefix = _build_inputs(params, cfg, batch,
                                          drop_last_token=False)
        if last_idx is not None:
            # right-padded rows: recurrent state ends at each row's length
            extras["n_valid"] = jnp.asarray(last_idx, jnp.int32) + 1
        if plan is not None:
            x = plan.constrain_act(x)
        x, cache, _ = _run_stack(params, cfg, x, mode="prefill", cache=None,
                                 extras=extras, plan=plan)
        x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if last_idx is None:
            x_last = x[:, -1:, :]
        else:
            idx = jnp.asarray(last_idx, jnp.int32) + prefix
            x_last = x[jnp.arange(x.shape[0]), idx][:, None, :]
        logits = _logits(params, cfg, x_last)
        return logits, cache

    # ---------------- decode ----------------
    def decode_step(self, params, token, cache, cache_len, plan=None,
                    block_table=None, paged_kernel: bool = False,
                    advance=None):
        """token (B,1) int32; cache_len = existing token count — a scalar
        (all rows at one length) or a (B,) vector (per-slot lengths for
        mixed-length continuous batching); the new token is written at
        index cache_len (per row when a vector).

        block_table: optional (B, max_blocks) int32 — paged-KV mode. The
        cache leaves are then a shared block pool (L, num_blocks,
        Hkv, block_size, hd) and row b's logical position j resolves to
        (block_table[b, j // block_size], :, j % block_size). Requires a
        (B,) cache_len vector. ``paged_kernel`` switches the paged read
        from the transient jnp gather to the in-place Pallas kernel
        (``kernels.paged_attention``; interpret mode off-TPU).
        ``advance`` (B,) bool: rows whose recurrent state this token moves
        (None: every row); a row that is not advanced keeps its state."""
        cfg = self.cfg
        B = token.shape[0]
        x = _embed_tokens(params, cfg, token)
        extras = {"cache_len": cache_len}
        if advance is not None:
            extras["advance"] = advance
        if block_table is not None:
            extras["block_table"] = jnp.asarray(block_table, jnp.int32)
            extras["paged_kernel"] = bool(paged_kernel)
        if cfg.rope == "learned":
            x = x + layers.sinusoidal_pos(
                jnp.reshape(cache_len, (-1, 1)), cfg.d_model, x.dtype)
        if cfg.rope == "mrope":
            pos = jnp.reshape(jnp.broadcast_to(
                jnp.asarray(cache_len, jnp.int32), (B,)), (B, 1, 1))
            extras["mrope_positions"] = jnp.broadcast_to(pos, (B, 3, 1))
        if plan is not None:
            x = plan.constrain_act(x)
        x, new_cache, _ = _run_stack(params, cfg, x, mode="decode",
                                     cache=cache, extras=extras, plan=plan)
        x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _logits(params, cfg, x)
        return logits, new_cache

    # ---------------- verify (speculative multi-token decode) ----------
    def verify_step(self, params, tokens, cache, cache_len, plan=None,
                    block_table=None, paged_kernel: bool = False,
                    n_write=None):
        """Multi-token decode: the speculative **verify** path — and,
        via :meth:`prefill`'s chunked mode, the **chunk-window** prompt
        ingestion path (a chunk of prompt tokens is a verify window
        whose tokens happen to be known-correct).

        tokens (B, S) int32 — row b's S = k+1 window tokens (the last
        committed token followed by the draft's proposals) at positions
        ``cache_len[b] + [0, S)``; cache_len (B,) int32 tokens already
        cached per row. Every window token writes its K/V at its own
        position and attends causally *inside the window* (query j sees
        cache positions <= cache_len[b] + j), so ``logits[:, j]`` equals
        what the j+1-th of S sequential :meth:`decode_step` calls would
        produce — the differential property ``tests/test_speculative.py``
        enforces. Returns (logits (B, S, V), new_cache).

        Paged mode (``block_table``): ``n_write`` (B,) caps how many
        window positions row b may write into its own blocks; the rest
        never reach them (the jnp path diverts them to the scratch block,
        the kernel writes nothing for them; a speculating row is
        granted blocks up to its watermark *before* the step — see
        ``ServingEngine._ensure_writable`` — and a rider row must not
        touch blocks it does not own). Only pure-attention ``{k, v}``
        caches verify: recurrent state (rwkv / hybrid SSM) advances
        token-at-a-time and has no multi-token catch-up here.
        """
        x, new_cache = self._window(params, tokens, cache, cache_len,
                                    plan, block_table, paged_kernel,
                                    n_write)
        logits = _logits(params, self.cfg, x)
        return logits, new_cache

    def _window(self, params, tokens, cache, cache_len, plan,
                block_table, paged_kernel, n_write):
        """Shared multi-token window body (verify / chunked prefill):
        runs the decode-mode stack over S tokens per row at positions
        ``cache_len[b] + [0, S)`` and returns the final-norm hidden
        states (B, S, d) plus the updated cache — the caller decides
        which positions to project against the vocabulary."""
        cfg = self.cfg
        kind = transformer.block_kind(cfg)
        if kind in ("rwkv", "hybrid"):
            raise ValueError(f"multi-token window unsupported for family "
                             f"{kind!r} (recurrent state is sequential)")
        B, S = tokens.shape
        x = _embed_tokens(params, cfg, tokens)
        idx = jnp.reshape(jnp.asarray(cache_len, jnp.int32), (-1,))
        extras = {"cache_len": idx}
        if block_table is not None:
            extras["block_table"] = jnp.asarray(block_table, jnp.int32)
            extras["paged_kernel"] = bool(paged_kernel)
            if n_write is not None:
                extras["n_write"] = jnp.asarray(n_write, jnp.int32)
        pos = idx[:, None] + jnp.arange(S)[None, :]
        if cfg.rope == "learned":
            x = x + layers.sinusoidal_pos(pos, cfg.d_model, x.dtype)
        if cfg.rope == "mrope":
            extras["mrope_positions"] = jnp.broadcast_to(
                pos[:, None, :], (B, 3, S)).astype(jnp.int32)
        if plan is not None:
            x = plan.constrain_act(x)
        x, new_cache, _ = _run_stack(params, cfg, x, mode="decode",
                                     cache=cache, extras=extras, plan=plan)
        x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return x, new_cache

    # ---------------- cache ----------------
    def init_cache(self, batch_size: int, capacity: int):
        """Zeroed decode cache with room for ``capacity`` tokens."""
        cfg = self.cfg
        L, B = cfg.n_layers, batch_size
        H, Hkv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model
        kind = transformer.block_kind(cfg)
        if kind == "pattern":
            n_a = cfg.layer_types.count("attention")
            kv = (n_a, B, capacity, Hkv, hd)
            return {"k": jnp.zeros(kv, cfg.dtype),
                    "v": jnp.zeros(kv, cfg.dtype), **self._state(B)}
        if kind == "rwkv":
            return {
                "state": jnp.zeros((L, B, H, hd, hd), jnp.float32),
                "last_x_t": jnp.zeros((L, B, d), cfg.dtype),
                "last_x_c": jnp.zeros((L, B, d), cfg.dtype),
            }
        cache = {
            "k": jnp.zeros((L, B, capacity, Hkv, hd), cfg.dtype),
            "v": jnp.zeros((L, B, capacity, Hkv, hd), cfg.dtype),
        }
        if kind == "hybrid":
            cache["ssm_state"] = jnp.zeros((L, B, cfg.dinner,
                                            max(cfg.ssm_state, 1)), jnp.float32)
        if kind == "decoder_x":
            cache["xk"] = jnp.zeros((L, B, cfg.n_frames, Hkv, hd), cfg.dtype)
            cache["xv"] = jnp.zeros((L, B, cfg.n_frames, Hkv, hd), cfg.dtype)
        return cache

    def _state(self, rows: int) -> dict:
        """Zeroed recurrent state of every Mamba-2 layer for ``rows``
        rows: leaves ``(n_mamba, rows, ...)``; empty without one."""
        n_m = self.cfg.layer_types.count("mamba")
        if not n_m:
            return {}
        one = mamba2.init_state(self.cfg, rows)
        return {k: jnp.zeros((n_m,) + v.shape, v.dtype)
                for k, v in one.items()}

    @property
    def can_page(self) -> bool:
        """Whether :meth:`init_paged_cache` can hold this model's cache:
        pure attention, or a layer pattern whose attention layers page
        and whose Mamba-2 layers keep per-slot state rows."""
        return transformer.block_kind(self.cfg) in ("dense", "moe",
                                                    "pattern")

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         slots: int = 1):
        """Zeroed block-pool KV: ``(L, num_blocks, Hkv, block_size, hd)``
        per leaf, shared by every slot through a per-slot block table
        (see ``repro.serve.blocks``). Head-major, so one KV head of one
        block is a contiguous ``(block_size, hd)`` tile: the paged
        kernel's K/V block then meets the TPU's (8, 128) tiling rule
        (``kernels/paged_attention/kernel.py``); heads narrower than the
        128 lanes share a row (``attention.lane_pack``). A layer-pattern
        model pages its attention layers' K/V (``L`` = its attention
        layers) and adds its Mamba-2 layers' recurrent state, O(1) in
        sequence length, as one row per slot (``slots`` rows). Other
        recurrent and cross-attention families do not page
        (:attr:`can_page`)."""
        cfg = self.cfg
        kind = transformer.block_kind(cfg)
        if not self.can_page:
            raise ValueError(f"paged KV unsupported for family {kind!r}: "
                             "only pure-attention {k, v} caches and layer-"
                             "pattern state rows page")
        L = cfg.layer_types.count("attention") if kind == "pattern" \
            else cfg.n_layers
        f = attention.lane_pack(cfg.hd, cfg.n_kv_heads)
        shape = (L, num_blocks, cfg.n_kv_heads // f, block_size, cfg.hd * f)
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype), **self._state(slots)}

    # ---------------- shape stand-ins ----------------
    def input_specs(self, shape) -> dict:
        """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        sds = jax.ShapeDtypeStruct
        i32, dt = jnp.int32, cfg.dtype
        if shape.kind == "train":
            batch = {"tokens": sds((B, S + 1), i32)}
            if cfg.frontend == "vision":
                batch["tokens"] = sds((B, S - cfg.n_patches + 1), i32)
                batch["patch_embeds"] = sds((B, cfg.n_patches, cfg.d_model), dt)
            if cfg.frontend == "audio":
                batch["frames"] = sds((B, cfg.n_frames, cfg.d_model), dt)
            return {"batch": batch}
        if shape.kind == "prefill":
            batch = {"tokens": sds((B, S), i32)}
            if cfg.frontend == "vision":
                batch["tokens"] = sds((B, S - cfg.n_patches), i32)
                batch["patch_embeds"] = sds((B, cfg.n_patches, cfg.d_model), dt)
            if cfg.frontend == "audio":
                batch["frames"] = sds((B, cfg.n_frames, cfg.d_model), dt)
            return {"batch": batch}
        # decode: one token against a cache of capacity S
        cache = jax.eval_shape(lambda: self.init_cache(B, S))
        return {
            "token": sds((B, 1), i32),
            "cache": cache,
            "cache_len": sds((), i32),
        }


def build_model(cfg) -> Model:
    return Model(cfg)
