"""Plain float32 reference of a Mamba-2 + attention hybrid decoder (the
GraniteMoeHybrid layout with no experts: granite-4.0-h-micro),
independent of the program: it reads the configuration's sizes and the
benchmark's own weights, and imports nothing of ``src/``.

Embeddings times ``embedding_multiplier``. Per layer, in the order
``layer_types`` gives: RMSNorm, the mixer, the residual add of the
mixer's output times ``residual_multiplier``, RMSNorm, the gated SiLU
MLP, the same scaled residual add. Then the final RMSNorm, the head
(the embedding's transpose) and the logits over ``logits_scaling``.

- Attention: one KV head per group of query heads, causal softmax at
  scale ``attention_multiplier``, no positional embedding.
- Mamba-2, the published recurrence run one token at a time (not the
  program's chunked form): ``z, xBC, dt = in_proj(x)``; a causal
  depthwise conv of width ``mamba_d_conv`` with bias, then SiLU; split
  into x (heads of ``mamba_d_head``), B and C (``mamba_n_groups`` groups
  of ``mamba_d_state``); ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t =
  h_t C_t + D x_t``; then RMSNorm of ``y * silu(z)`` over the inner
  width times ``norm``, and ``out_proj``.

Matmuls run at ``highest`` precision, one sequence and one layer at a
time, each layer's weights cast to float32 only while it runs. The
sequence is padded to a multiple of ``PAD``; every layer is causal, so
padding touches no real position.

``fp8=True`` is the control: every matmul of the linear layers takes its
operands rounded to float8 e4m3, as in ``dense_decoder``.

The benchmark's weights for such a configuration are made here
(``weights``), in the program's layout: ``blocks`` holds each kind's
layers stacked in layer order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as bench_weights
from bench.reference import dense_decoder as dd

PAD = dd.PAD
HIGHEST = jax.lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["mamba_n_heads"]
    P, G, N = cfg["mamba_d_head"], cfg["mamba_n_groups"], cfg["mamba_d_state"]
    assert H * P == cfg["mamba_expand"] * d, "mamba heads x head dim"
    return {"d": d, "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": d // cfg["num_attention_heads"],
            "m_heads": H, "m_head_dim": P, "groups": G, "state": N,
            "conv": cfg["mamba_d_conv"], "inner": H * P,
            "conv_dim": H * P + 2 * G * N,
            "ff": cfg["shared_intermediate_size"],
            "eps": cfg["rms_norm_eps"], "vocab": cfg["vocab_size"],
            "scale": cfg["attention_multiplier"],
            "residual": cfg["residual_multiplier"]}


# ------------------------------------------------------------- weights
@functools.partial(jax.jit, static_argnames=("c",))
def _weights(k, *, c):
    """Seeded weights. The embedding's scale, ``8 / sqrt(d)``, gives
    logits of unit spread through the tied head over ``logits_scaling``
    8; the matrices that write a residual branch (``w_out``, ``w_o``,
    ``out_proj``) are ``branch`` times the usual ``1 / sqrt(fan_in)``
    (see ``weights``); Mamba-2's ``A``, ``dt`` and conv follow the
    published initialisation (A in [1, 16], dt log-uniform in
    [1e-3, 1e-1])."""
    c = dict(c)
    dt = bench_weights.DTYPES[c["dtype"]]
    d, F, V = c["d"], c["ff"], c["V"]
    Hq, Hkv, hd = c["heads"], c["kv_heads"], c["head_dim"]
    H, di, C, K = c["m_heads"], c["inner"], c["conv_dim"], c["conv"]
    n_m, n_a = c["n_mamba"], c["n_attn"]
    g = c["branch"]
    ks = iter(jax.random.split(k, 32))
    nrm = bench_weights._normal
    gam = bench_weights._gamma

    def common(L):
        return {"ln1": gam(next(ks), (L, d), dt),
                "ln2": gam(next(ks), (L, d), dt),
                "ffn": {"w_in": nrm(next(ks), (L, d, F), d ** -0.5, dt),
                        "w_gate": nrm(next(ks), (L, d, F), d ** -0.5, dt),
                        "w_out": nrm(next(ks), (L, F, d), g * F ** -0.5, dt)}}

    blocks = {}
    if n_m:
        u = jax.random.uniform(next(ks), (n_m, H), jnp.float32)
        dt0 = jnp.exp(np.log(1e-3) + u * (np.log(1e-1) - np.log(1e-3)))
        mamba = {
            "in_proj": nrm(next(ks), (n_m, d, di + C + H), d ** -0.5, dt),
            "conv_w": nrm(next(ks), (n_m, K, C), K ** -0.5, dt),
            "conv_b": nrm(next(ks), (n_m, C), 0.1, dt),
            # softplus^-1(dt0), so that dt starts near dt0
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dt),
            "A_log": jnp.log(jax.random.uniform(
                next(ks), (n_m, H), jnp.float32, 1.0, 16.0)).astype(dt),
            "D": gam(next(ks), (n_m, H), dt),
            "norm": gam(next(ks), (n_m, di), dt),
            "out_proj": nrm(next(ks), (n_m, di, d), g * di ** -0.5, dt)}
        blocks["mamba"] = {**common(n_m), "mamba": mamba}
    if n_a:
        attn = {"w_q": nrm(next(ks), (n_a, d, Hq * hd), d ** -0.5, dt),
                "w_kv": nrm(next(ks), (n_a, d, 2 * Hkv * hd), d ** -0.5, dt),
                "w_o": nrm(next(ks), (n_a, Hq * hd, d),
                           g * (Hq * hd) ** -0.5, dt)}
        blocks["attention"] = {**common(n_a), "attn": attn}
    return {"embed": nrm(next(ks), (V, d), 8.0 * d ** -0.5, dt),
            "blocks": blocks, "final_norm": gam(next(ks), (d,), dt)}


def weights(cfg: dict, seed: int) -> dict:
    """Weights of a hybrid configuration, made on the device in one
    jitted call, in the dtype they are served in."""
    s = sizes(cfg)
    types = cfg["layer_types"]
    # the embedding enters the stream times embedding_multiplier, and
    # each of the 2 x layers branches adds residual_multiplier x its
    # output. With branch outputs of ``branch`` per element the stream
    # ends near sqrt(d) x the embedding's part: the tied head then
    # favours the input token by about one logit's spread, not by many
    emb = cfg["embedding_multiplier"] * 8.0 * s["d"] ** -0.5
    branch = np.sqrt(s["d"]) * emb / (s["residual"]
                                      * np.sqrt(2 * len(types)))
    c = {k: s[k] for k in ("d", "ff", "heads", "kv_heads", "head_dim",
                           "m_heads", "inner", "conv_dim", "conv")}
    c.update(V=-(-cfg["vocab_size"] // 128) * 128, dtype=cfg["torch_dtype"],
             n_mamba=types.count("mamba"), n_attn=types.count("attention"),
             branch=float(branch))
    return _weights(bench_weights.key(seed), c=tuple(sorted(c.items())))


# ------------------------------------------------------------- layers
def _mlp(h, w, c, fp8):
    x = dd._rms(h, w["ln2"], c["eps"])
    f = w["ffn"]
    a = jax.nn.silu(dd._mm(x, f["w_gate"], fp8)) * dd._mm(x, f["w_in"], fp8)
    return h + c["residual"] * dd._mm(a, f["w_out"], fp8)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _attention_layer(h, blocks, i, *, c, fp8):
    c = dict(c)
    w = jax.tree.map(lambda a: a[i].astype(jnp.float32), blocks)
    T = h.shape[0]
    Hq, Hkv, hd = c["heads"], c["kv_heads"], c["head_dim"]
    x = dd._rms(h, w["ln1"], c["eps"])
    q = dd._mm(x, w["attn"]["w_q"], fp8).reshape(T, Hq, hd)
    kv = dd._mm(x, w["attn"]["w_kv"], fp8).reshape(T, 2, Hkv, hd)
    k = jnp.repeat(kv[:, 0], Hq // Hkv, axis=1)
    v = jnp.repeat(kv[:, 1], Hq // Hkv, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST) * c["scale"]
    pos = jnp.arange(T)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(T, Hq * hd)
    h = h + c["residual"] * dd._mm(o, w["attn"]["w_o"], fp8)
    return _mlp(h, w, c, fp8)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _mamba_layer(h, blocks, i, *, c, fp8):
    c = dict(c)
    w = jax.tree.map(lambda a: a[i].astype(jnp.float32), blocks)
    m = w["mamba"]
    T = h.shape[0]
    H, P, G, N = c["m_heads"], c["m_head_dim"], c["groups"], c["state"]
    di, K = c["inner"], c["conv"]
    x = dd._rms(h, w["ln1"], c["eps"])
    zxbcdt = dd._mm(x, m["in_proj"], fp8)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:di + c["conv_dim"]]
    dt = jax.nn.softplus(zxbcdt[:, di + c["conv_dim"]:] + m["dt_bias"])
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[j:j + T] * m["conv_w"][j] for j in range(K))
                      + m["conv_b"])
    xs = xbc[:, :di].reshape(T, H, P)
    rep = H // G
    Bm = jnp.repeat(xbc[:, di:di + G * N].reshape(T, G, N), rep, axis=1)
    Cm = jnp.repeat(xbc[:, di + G * N:].reshape(T, G, N), rep, axis=1)
    A = -jnp.exp(m["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * A)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xs, Bm, Cm, dt))
    y = (y + m["D"][:, None] * xs).reshape(T, di)
    y = dd._rms(y * jax.nn.silu(z), m["norm"], c["eps"])
    h = h + c["residual"] * dd._mm(y, m["out_proj"], fp8)
    return _mlp(h, w, c, fp8)


def logits(weights: dict, cfg: dict, tokens: list, *, fp8: bool = False):
    """float32 logits (Tp, vocab) of one sequence padded to Tp, a
    multiple of ``PAD``; rows past ``len(tokens)`` are padding."""
    c = tuple(sorted(sizes(cfg).items()))
    T = len(tokens)
    Tp = -(-T // PAD) * PAD
    ids = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, Tp - T)))
    h = dd._embed(weights["embed"], ids) * cfg["embedding_multiplier"]
    seen = {"mamba": 0, "attention": 0}
    for kind in cfg["layer_types"]:
        layer = _mamba_layer if kind == "mamba" else _attention_layer
        h = layer(h, weights["blocks"][kind], jnp.int32(seen[kind]), c=c,
                  fp8=fp8)
        seen[kind] += 1
    return dd._head(h, weights["final_norm"], weights["embed"], c=c,
                    fp8=fp8, tied=True) / cfg["logits_scaling"]


def served_gap(weights: dict, cfg: dict, prompt: list, served: list) -> float:
    """Widest gap, over the served tokens of one request, between the
    reference's best logit and the served token's logit."""
    seq, pick, mask = dd._positions(prompt, served)
    return float(dd._widest(logits(weights, cfg, seq), pick, mask))


def control_gap(weights: dict, cfg: dict, prompt: list, served: list) -> float:
    """Widest gap of the tokens the fp8 control puts first, at each
    position of the same prompt and served tokens."""
    seq, _, mask = dd._positions(prompt, served)
    pick = dd._argmax(logits(weights, cfg, seq, fp8=True))
    return float(dd._widest(logits(weights, cfg, seq), pick, mask))
