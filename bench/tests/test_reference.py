"""The plain references against the program, at reduced size on the CPU,
in float32: they must agree to rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.drivers.lm_serve import program_config
from bench.reference import bilstm_lan as ref_lan
from bench.reference import dense_decoder

SMALL = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
         "head_dim": 32, "intermediate_size": 256, "vocab_size": 384,
         "torch_dtype": "float32"}


def small(name, **kw):
    cfg = dict(spec.config(name), **SMALL, **kw)
    if name == "deepseek-7b":
        cfg.pop("head_dim")
        cfg["num_key_value_heads"] = 4
    else:
        cfg["num_key_value_heads"] = 2
    return cfg


@pytest.mark.parametrize("name", ["qwen3-4b", "deepseek-7b"])
def test_dense_reference_matches_program_prefill(name):
    from repro.models.model import build_model
    cfg = small(name)
    model = build_model(program_config(cfg))
    params = weights.dense(cfg, seed=3)
    weights.check_layout(params, jax.eval_shape(model.init, jax.random.key(0)))
    T = 40
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], T)
    positions = np.array([5, 17, 28, 39], np.int32)
    batch = jnp.asarray(np.tile(toks, (len(positions), 1)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = model.prefill(params, {"tokens": batch},
                               last_idx=jnp.asarray(positions))
    want = dense_decoder.logits(params, cfg, toks.tolist())[positions]
    np.testing.assert_allclose(np.asarray(got[:, 0, :cfg["vocab_size"]]),
                               np.asarray(want), rtol=2e-4, atol=2e-4)


def test_served_gap_is_zero_for_the_reference_argmax():
    cfg = small("qwen3-4b")
    params = weights.dense(cfg, seed=4)
    prompt = list(range(10, 30))
    seq = list(prompt)
    served = []
    for _ in range(5):
        nxt = int(jnp.argmax(dense_decoder.logits(params, cfg, seq)[len(seq) - 1]))
        served.append(nxt)
        seq.append(nxt)
    assert dense_decoder.served_gap(params, cfg, prompt, served) == 0.0
    bad = served[:2] + [(served[2] + 1) % cfg["vocab_size"]] + served[3:]
    assert dense_decoder.served_gap(params, cfg, prompt, bad) > 0.0


def test_lan_reference_matches_program_forward():
    from repro.models import bilstm_lan
    cfg = dict(spec.config("cv-ner4"))
    lan = bilstm_lan.LANConfig(vocab_size=cfg["vocab_size"], n_labels=5,
                               d_model=cfg["d_model"],
                               n_layers=cfg["n_layers"], n_heads=cfg["n_heads"])
    p = weights.lan(cfg, 5, seed=5, stream=0)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (8, cfg["max_sent_len"])), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = bilstm_lan.forward(p, lan, ids)
    want = ref_lan.scores(p, ids, n_heads=cfg["n_heads"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
