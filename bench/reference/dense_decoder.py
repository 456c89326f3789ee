"""Plain float32 reference of a dense decoder (Llama / DeepSeek-LLM and
Qwen3 layouts), independent of the program: it reads the configuration's
sizes and the benchmark's own weights, and imports nothing of ``src/``.

Per layer: RMSNorm, attention (q/k RMSNorm per head for Qwen3, rotary
embedding on the two halves of each head, causal softmax, one KV head
per group of query heads), residual, RMSNorm, gated SiLU MLP, residual.
Then the final RMSNorm and the head (the embedding's transpose where the
configuration ties them). Matmuls run at ``highest`` precision, so a
float32 product is a float32 product on the TPU too.

One sequence at a time, layer by layer, with each layer's weights cast to
float32 only while it runs, so the reference fits beside the served
weights on one chip. The sequence is padded to a multiple of
``PAD``: causal attention keeps the padding from touching any real
position, and the compiled layer is reused across lengths.

``fp8=True`` is the control: every matmul of the linear layers takes its
operands rounded to float8 e4m3 (per-row scales for activations,
per-column for weights), the step a lower-precision serving path would
take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 256
QK_NORM_ARCHS = ("Qwen3ForCausalLM",)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rope(x, pos, theta):
    """x (T, H, hd): rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _layer(h, blocks, i, *, c, fp8):
    """One decoder layer on h (T, d) float32; ``blocks`` are the stacked
    served weights, layer ``i`` is cast to float32 here. ``c``: the
    sizes, as sorted (key, value) pairs."""
    c = dict(c)
    w = jax.tree.map(lambda a: a[i].astype(jnp.float32), blocks)
    T = h.shape[0]
    Hq, Hkv, hd = c["heads"], c["kv_heads"], c["head_dim"]
    x = _rms(h, w["ln1"], c["eps"])
    q = _mm(x, w["attn"]["w_q"], fp8).reshape(T, Hq, hd)
    kv = _mm(x, w["attn"]["w_kv"], fp8).reshape(T, 2, Hkv, hd)
    k, v = kv[:, 0], kv[:, 1]
    if c["qk_norm"]:
        q = _rms(q, w["attn"]["q_norm"], c["eps"])
        k = _rms(k, w["attn"]["k_norm"], c["eps"])
    pos = jnp.arange(T)
    q, k = _rope(q, pos, c["theta"]), _rope(k, pos, c["theta"])
    G = Hq // Hkv
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hst,thd->shd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(T, Hq * hd)
    h = h + _mm(o, w["attn"]["w_o"], fp8)
    x = _rms(h, w["ln2"], c["eps"])
    f = w["ffn"]
    a = jax.nn.silu(_mm(x, f["w_gate"], fp8)) * _mm(x, f["w_in"], fp8)
    return h + _mm(a, f["w_out"], fp8)


HEAD_CHUNK = 8192


@functools.partial(jax.jit, static_argnames=("c", "fp8", "tied"))
def _head(h, final_norm, head, *, c, fp8, tied):
    """Logits (T, vocab) in float32, the head cast to float32 one slice
    of ``HEAD_CHUNK`` vocabulary entries at a time (the last slice is
    moved back to end at the vocabulary's end)."""
    c = dict(c)
    x = _rms(h, final_norm.astype(jnp.float32), c["eps"])
    V = c["vocab"]
    C = min(HEAD_CHUNK, V)

    def piece(i, out):
        start = jnp.minimum(i * C, V - C)
        if tied:
            w = jax.lax.dynamic_slice_in_dim(head, start, C, axis=0).T
        else:
            w = jax.lax.dynamic_slice_in_dim(head, start, C, axis=1)
        part = _mm(x, w.astype(jnp.float32), fp8)
        return jax.lax.dynamic_update_slice_in_dim(out, part, start, axis=1)

    out = jnp.zeros((x.shape[0], V), jnp.float32)
    return jax.lax.fori_loop(0, -(-V // C), piece, out)


def _sizes(cfg: dict) -> dict:
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim") or
            cfg["hidden_size"] // cfg["num_attention_heads"],
            "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
            "vocab": cfg["vocab_size"],
            "qk_norm": cfg["architectures"][0] in QK_NORM_ARCHS}


def logits(weights: dict, cfg: dict, tokens: list, *, fp8: bool = False):
    """float32 logits (Tp, vocab) of one sequence padded to Tp, a
    multiple of ``PAD``; rows past ``len(tokens)`` are padding."""
    c = tuple(sorted(_sizes(cfg).items()))
    T = len(tokens)
    Tp = -(-T // PAD) * PAD
    ids = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, Tp - T)))
    h = _embed(weights["embed"], ids)
    for i in range(cfg["num_hidden_layers"]):
        h = _layer(h, weights["blocks"], jnp.int32(i), c=c, fp8=fp8)
    head = weights["embed"] if cfg["tie_word_embeddings"] \
        else weights["lm_head"]
    return _head(h, weights["final_norm"], head, c=c, fp8=fp8,
                 tied=bool(cfg["tie_word_embeddings"]))


@jax.jit
def _embed(table, ids):
    return table[ids].astype(jnp.float32)


@jax.jit
def _widest(lg, pick, mask):
    """Widest gap, over the masked positions, between the best logit and
    the logit of the token ``pick`` names there."""
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
    return jnp.max(jnp.where(mask, best - got, -jnp.inf))


def _positions(prompt: list, served: list):
    """The sequence the reference reads (prompt, then every served token
    but the last), and per padded position the served token it must
    predict and whether it is one."""
    seq = list(prompt) + list(served[:-1])
    Tp = -(-len(seq) // PAD) * PAD
    pick = np.zeros(Tp, np.int32)
    mask = np.zeros(Tp, bool)
    first = len(prompt) - 1
    pick[first:first + len(served)] = served
    mask[first:first + len(served)] = True
    return seq, jnp.asarray(pick), jnp.asarray(mask)


def served_gap(weights: dict, cfg: dict, prompt: list, served: list) -> float:
    """Widest gap, over the served tokens of one request, between the
    reference's best logit and the served token's logit."""
    seq, pick, mask = _positions(prompt, served)
    return float(_widest(logits(weights, cfg, seq), pick, mask))


def control_gap(weights: dict, cfg: dict, prompt: list, served: list) -> float:
    """Widest gap of the tokens the fp8 control puts first, at each
    position of the same prompt and served tokens."""
    seq, _, mask = _positions(prompt, served)
    lq = logits(weights, cfg, seq, fp8=True)
    pick = _argmax(lq)
    del lq
    return float(_widest(logits(weights, cfg, seq), pick, mask))


@jax.jit
def _argmax(lg):
    return jnp.argmax(lg, axis=-1).astype(jnp.int32)
