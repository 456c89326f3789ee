"""The benchmark's own seeded weights, made on the device in one jitted
call, in the dtype they are served in. The program is handed them; the
reference reads the same arrays. So the reference takes nothing the
program has made.

The trees follow the layouts the program's models read; a run checks
them leaf by leaf against the program's ``init`` shapes
(``check_layout``) before serving anything.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def key(seed: int, stream: int = 0):
    """A JAX key from a seed of any size (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _normal(k, shape, scale, dtype):
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def _gamma(k, shape, dtype):
    """Norm gains near 1, not exactly 1, so a gain left out shows."""
    return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("c",))
def _dense(k, *, c):
    c = dict(c)
    L, d, Hq, Hkv, hd, F, V = (c[n] for n in ("L", "d", "Hq", "Hkv", "hd",
                                               "F", "V"))
    dt = DTYPES[c["dtype"]]
    ks = iter(jax.random.split(k, 16))
    attn = {"w_q": _normal(next(ks), (L, d, Hq * hd), d ** -0.5, dt),
            "w_kv": _normal(next(ks), (L, d, 2 * Hkv * hd), d ** -0.5, dt),
            "w_o": _normal(next(ks), (L, Hq * hd, d), (Hq * hd) ** -0.5, dt)}
    if c["qk_norm"]:
        attn["q_norm"] = _gamma(next(ks), (L, hd), dt)
        attn["k_norm"] = _gamma(next(ks), (L, hd), dt)
    blocks = {"ln1": _gamma(next(ks), (L, d), dt), "attn": attn,
              "ln2": _gamma(next(ks), (L, d), dt),
              "ffn": {"w_in": _normal(next(ks), (L, d, F), d ** -0.5, dt),
                      "w_gate": _normal(next(ks), (L, d, F), d ** -0.5, dt),
                      "w_out": _normal(next(ks), (L, F, d), F ** -0.5, dt)}}
    p = {"embed": _normal(next(ks), (V, d), 0.02, dt), "blocks": blocks,
         "final_norm": _gamma(next(ks), (d,), dt)}
    if not c["tied"]:
        p["lm_head"] = _normal(next(ks), (d, V), d ** -0.5, dt)
    return p


def dense(cfg: dict, seed: int) -> dict:
    """Weights of a dense decoder configuration (HF key names)."""
    from bench.reference.dense_decoder import QK_NORM_ARCHS
    V = -(-cfg["vocab_size"] // 128) * 128
    c = {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
         "Hq": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
         "hd": cfg.get("head_dim") or
         cfg["hidden_size"] // cfg["num_attention_heads"],
         "F": cfg["intermediate_size"], "V": V,
         "dtype": cfg["torch_dtype"],
         "tied": bool(cfg["tie_word_embeddings"]),
         "qk_norm": cfg["architectures"][0] in QK_NORM_ARCHS}
    return _dense(key(seed), c=tuple(sorted(c.items())))


def _lstm(k, d_in, d_h, dt):
    k1, k2, k3 = jax.random.split(k, 3)
    return {"w": _normal(k1, (d_in, 4 * d_h), d_in ** -0.5, dt),
            "u": _normal(k2, (d_h, 4 * d_h), d_h ** -0.5, dt),
            "b": _normal(k3, (4 * d_h,), 0.1, dt)}


@functools.partial(jax.jit, static_argnames=("c",))
def _lan(k, *, c):
    c = dict(c)
    d, n_layers, V, n_labels = c["d"], c["layers"], c["V"], c["labels"]
    dt = DTYPES[c["dtype"]]
    ks = jax.random.split(k, n_layers + 2)
    layers, d_in = [], d
    for i in range(n_layers):
        kk = jax.random.split(ks[i], 5)
        layers.append({"fwd": _lstm(kk[0], d_in, d // 2, dt),
                       "bwd": _lstm(kk[1], d_in, d // 2, dt),
                       "w_q": _normal(kk[2], (d, d), d ** -0.5, dt),
                       "w_k": _normal(kk[3], (d, d), d ** -0.5, dt),
                       "w_v": _normal(kk[4], (d, d), d ** -0.5, dt)})
        d_in = 2 * d
    return {"embed": _normal(ks[-2], (V, d), 1.0, dt),
            "label_embed": _normal(ks[-1], (n_labels, d), 1.0, dt),
            "lan_layers": layers}


def lan(cfg: dict, n_labels: int, seed: int, stream: int) -> dict:
    """Weights of one BiLSTM-LAN service."""
    c = {"d": cfg["d_model"], "layers": cfg["n_layers"],
         "V": cfg["vocab_size"], "labels": n_labels,
         "dtype": cfg["torch_dtype"]}
    return _lan(key(seed, stream), c=tuple(sorted(c.items())))


def check_layout(ours, theirs_shapes) -> None:
    """Raise unless ``ours`` has the program's tree, shapes and dtypes."""
    a = jax.tree.map(lambda x: (x.shape, x.dtype), ours)
    b = jax.tree.map(lambda x: (x.shape, x.dtype), theirs_shapes)
    if jax.tree.structure(a) != jax.tree.structure(b) or \
            jax.tree.leaves(a) != jax.tree.leaves(b):
        raise ValueError("the benchmark's weights do not match the "
                         f"program's layout:\n{a}\n!=\n{b}")
