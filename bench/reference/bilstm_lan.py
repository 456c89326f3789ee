"""Plain float32 reference of the BiLSTM with label attention network
(Cui and Zhang, arXiv:1908.08676) as the paper's NER services run it,
independent of the program: it imports nothing of ``src/``.

Per layer: an LSTM forward and one backward over the padded sentence
(gates i, f, g, o from ``x W + h U + b``, the forget gate's input shifted
by +1, zero initial state), their outputs concatenated; then multi-head
attention from each token to the label embeddings (keys and values are
the projected label embeddings), and the attention output concatenated
to the LSTM output as the next layer's input. The last layer's
head-averaged attention scores over the labels are the label scores; the
served label is the best of them.

Matmuls run at ``highest`` precision. ``bf16=True`` is the control:
weights, activations and state held in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _lstm(p, x, reverse: bool, dt):
    B, S, _ = x.shape
    d_h = p["u"].shape[0]
    w, u, b = (p[n].astype(dt) for n in ("w", "u", "b"))

    def step(carry, x_t):
        h, c = carry
        z = (jnp.matmul(x_t, w, precision=HI) + jnp.matmul(h, u, precision=HI)
             + b)
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    init = (jnp.zeros((B, d_h), dt), jnp.zeros((B, d_h), dt))
    _, hs = jax.lax.scan(step, init, jnp.moveaxis(x, 1, 0), reverse=reverse)
    return jnp.moveaxis(hs, 0, 1)


@functools.partial(jax.jit, static_argnames=("n_heads", "bf16"))
def scores(params, ids, *, n_heads: int, bf16: bool = False):
    """Label scores (B, S, n_labels) of padded token ids (B, S)."""
    dt = jnp.bfloat16 if bf16 else jnp.float32
    x = params["embed"].astype(dt)[ids]
    lab = params["label_embed"].astype(dt)
    L = lab.shape[0]
    out = None
    for lp in params["lan_layers"]:
        h = jnp.concatenate([_lstm(lp["fwd"], x, False, dt),
                             _lstm(lp["bwd"], x, True, dt)], axis=-1)
        B, S, d = h.shape
        hd = d // n_heads
        q = jnp.matmul(h, lp["w_q"].astype(dt), precision=HI)
        k = jnp.matmul(lab, lp["w_k"].astype(dt), precision=HI)
        v = jnp.matmul(lab, lp["w_v"].astype(dt), precision=HI)
        q = q.reshape(B, S, n_heads, hd)
        k = k.reshape(L, n_heads, hd)
        v = v.reshape(L, n_heads, hd)
        s = jnp.einsum("bshd,lhd->bshl", q, k, precision=HI) / jnp.sqrt(
            jnp.asarray(hd, dt))
        a = jnp.einsum("bshl,lhd->bshd", jax.nn.softmax(s, axis=-1), v,
                       precision=HI).reshape(B, S, d)
        x = jnp.concatenate([h, a], axis=-1)
        out = jnp.mean(s, axis=2)
    return out.astype(jnp.float32)


def widest_gap(ref_scores, labels, mask) -> float:
    """Widest gap, over the masked tokens, between the reference's best
    label score and the score of the label served there."""
    best = jnp.max(ref_scores, axis=-1)
    got = jnp.take_along_axis(ref_scores, labels[..., None], axis=-1)[..., 0]
    return float(jnp.max(jnp.where(mask, best - got, -jnp.inf)))
