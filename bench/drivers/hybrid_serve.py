"""A hybrid (Mamba-2 + attention) language-model cell: the engine as
``launch/serve.py`` builds it, with the attention layers' K/V in the
paged pool and the Mamba-2 layers' state in per-slot rows, served and
pumped as ``lm_serve`` serves a dense decoder (``warm_shapes``,
``warm_up``, ``serve`` and its greedy sample are that module's). What is
this module's own: the program configuration, the seeded weights and
the reference (``bench/reference/granite_hybrid.py``), and the decode
program's bytes (``bench/work_hybrid.py``) for its roofline.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax

from bench import generate, weights as bench_weights
from bench.drivers import lm_serve
from bench.reference import granite_hybrid


def program_config(cfg: dict):
    """The program's ArchConfig for a configuration file: its registered
    architecture with every size the file states applied."""
    from repro.configs.base import get_config
    base = get_config(cfg["program_arch"])
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    assert cfg["mamba_n_heads"] * cfg["mamba_d_head"] == \
        cfg["mamba_expand"] * d
    assert cfg["position_embedding_type"] == "nope"
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=d // heads,
        d_ff=cfg["shared_intermediate_size"], vocab_size=cfg["vocab_size"],
        rope="none", norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        layer_types=tuple(cfg["layer_types"]),
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_state=cfg["mamba_d_state"], mamba_groups=cfg["mamba_n_groups"],
        mamba_conv=cfg["mamba_d_conv"], mamba_chunk=cfg["mamba_chunk_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        dtype=bench_weights.DTYPES[cfg["torch_dtype"]], source=cfg["source"])


def make_engine(ctx, params):
    """The engine as the launcher builds it, every program shape of the
    cell's traffic warmed up."""
    from repro.launch.serve import build_engine, describe_plan
    from repro.models.model import build_model
    cfg, eng_shape = ctx.config, ctx.config["engine"]
    model = build_model(program_config(cfg))
    bench_weights.check_layout(params,
                               jax.eval_shape(model.init, jax.random.key(0)))
    eng, _, plan = build_engine(model, params, batch=eng_shape["batch"],
                                max_seq=eng_shape["max_seq"],
                                prefill_chunk=eng_shape.get("prefill_chunk"))
    ctx.note(f"engine: batch {eng.B}, max_seq {eng.max_seq}, block "
             f"{eng.block_size}, chunk {eng.prefill_chunk}, pool "
             f"{eng.pool.total} blocks, state rows "
             f"{eng.metrics['state_bytes'] / 1e9:.2f} GB, kernel "
             f"{eng.use_kernel}; {describe_plan(plan)}")
    ctx.phase("engine")
    prompts, _, _ = generate.sizes(ctx.mix)
    shapes = lm_serve.warm_shapes(prompts, eng.prefill_chunk, eng.B,
                                  eng.max_seq)
    n = lm_serve.warm_up(eng, shapes, eng.prefill_chunk, cfg["vocab_size"])
    ctx.note(f"warm-up: {n} programs run, shapes {shapes}")
    ctx.phase("warm-up")
    return eng


def check(record: dict, params, cfg: dict, seed: int, *,
          control: bool = False) -> dict:
    """The reference's widest logit gap over a seeded sample of greedy
    requests (``control``: also the fp8 control's, on the same sample)."""
    sample = lm_serve._sample(record["reqs"], seed, cfg["check"])
    out = {"requests": len(sample),
           "tokens": sum(len(r.tokens) for r in sample)}
    gaps = [granite_hybrid.served_gap(params, cfg, r.spec.prompt, r.tokens)
            for r in sample]
    out["max_logit_gap"] = max(gaps) if gaps else float("inf")
    if control:
        out["control_gap"] = max(
            granite_hybrid.control_gap(params, cfg, r.spec.prompt, r.tokens)
            for r in sample)
    return out


def run(ctx) -> dict:
    """One run of a hybrid LM cell; returns the record the metrics read."""
    cfg = ctx.config
    params = granite_hybrid.weights(cfg, ctx.seed)
    ctx.phase("weights")
    eng = make_engine(ctx, params)
    record = lm_serve.serve(ctx, eng)
    del eng
    gc.collect()
    t0 = time.perf_counter()
    got = check(record, params, cfg, ctx.seed)
    ctx.note(f"reference over {got['requests']} greedy requests, "
             f"{got['tokens']} served tokens, "
             f"{time.perf_counter() - t0:.1f} s")
    record["checks"] = {"max_logit_gap": {
        "value": got["max_logit_gap"],
        "limit": cfg["check"]["max_logit_gap"]}}
    return record
