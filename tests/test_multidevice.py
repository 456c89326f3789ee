"""Multi-device behaviours (subprocess with 8 forced host devices):
shard_map EP-MoE == single-device MoE, sequence-sharded decode ==
unsharded decode, and mesh space-sharing parallel == sequential."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_in_subprocess(body: str) -> dict:
    """Run `body` with 8 host devices; it must print a JSON dict."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    # forced host devices exist only on the CPU backend; never let the
    # child reach for an accelerator the parent may hold
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_ep_moe_matches_single_device():
    res = run_in_subprocess("""
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.configs.base import get_config
        from repro.models import moe
        from repro.sharding.rules import ParallelPlan
        import dataclasses

        cfg = dataclasses.replace(get_config("grok-1-314b").reduced(),
                                  capacity_factor=8.0)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        plan = ParallelPlan.make(mesh, cfg, "train")
        p = moe.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
        local, _ = moe.moe_ffn_local(
            x.reshape(-1, cfg.d_model), p, cfg)
        local = local.reshape(x.shape)
        dist, _ = jax.jit(lambda x, p: moe.moe_ffn(x, p, cfg, plan))(x, p)
        err = float(jnp.max(jnp.abs(dist - local)))
        print(json.dumps({"err": err, "mode": plan.moe_mode}))
    """)
    assert res["err"] < 2e-4, res


@pytest.mark.slow
def test_ep_moe_kimi_mode_matches():
    res = run_in_subprocess("""
        from repro.configs.base import get_config
        from repro.models import moe
        from repro.sharding.rules import ParallelPlan
        import dataclasses

        cfg = dataclasses.replace(get_config("kimi-k2-1t-a32b").reduced(),
                                  capacity_factor=8.0)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        plan = ParallelPlan.make(mesh, cfg, "train")
        p = moe.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
        local, _ = moe.moe_ffn_local(x.reshape(-1, cfg.d_model), p, cfg)
        dist, _ = jax.jit(lambda x, p: moe.moe_ffn(x, p, cfg, plan))(x, p)
        err = float(jnp.max(jnp.abs(dist - local.reshape(x.shape))))
        print(json.dumps({"err": err, "mode": plan.moe_mode}))
    """)
    assert res["mode"] == "ep"
    assert res["err"] < 2e-4, res


@pytest.mark.slow
def test_sequence_sharded_decode_matches_unsharded():
    res = run_in_subprocess("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import get_config
        from repro.models.model import build_model
        from repro.sharding.rules import ParallelPlan

        cfg = get_config("qwen3-4b").reduced()
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        cache = m.init_cache(2, 64)
        tok = jnp.ones((2, 1), jnp.int32)
        ref, _ = jax.jit(m.decode_step)(params, tok, cache, jnp.int32(32))

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        plan = ParallelPlan.make(mesh, cfg, "decode")
        c_sh = jax.tree_util.tree_map_with_path(
            lambda path, x: jax.device_put(
                x, NamedSharding(mesh, plan.cache_spec(("cache",) + tuple(
                    str(getattr(k, "key", k)) for k in path), x.shape))),
            cache)
        out, _ = jax.jit(lambda p, t, c, l: m.decode_step(p, t, c, l, plan)
                         )(params, tok, c_sh, jnp.int32(32))
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        print(json.dumps({"err": err}))
    """)
    assert res["err"] < 2e-4, res


@pytest.mark.slow
def test_multimodel_space_sharing_parallel_equals_sequential():
    res = run_in_subprocess("""
        from repro.core.multimodel import ModelService, MultiModelServer

        def mk(i):
            w = jnp.eye(16) * (i + 1)
            return ModelService(f"m{i}", lambda p, b: b @ p, w)

        server = MultiModelServer([mk(i) for i in range(4)])
        groups = {s.name: [str(d) for d in s.submesh.devices.ravel()]
                  for s in server.services.values()}
        disjoint = len({d for g in groups.values() for d in g}) == \
            sum(len(g) for g in groups.values())
        batches = {f"m{i}": jnp.ones((4, 16)) for i in range(4)}
        par, t_par = server.serve_parallel(batches)
        seq, t_seq = server.serve_sequential(batches)
        same = all(bool(jnp.allclose(par[k], seq[k])) for k in par)
        print(json.dumps({"disjoint": disjoint, "same": same}))
    """)
    assert res["disjoint"] and res["same"]


@pytest.mark.slow
def test_weight_stationary_moe_decode_matches_local():
    """moe_decode_ffn (token-gather, weight-stationary; §Perf kimi-k2)
    must agree with the single-device oracle under 2-D sharded weights."""
    res = run_in_subprocess("""
        import dataclasses
        from repro.configs.base import get_config
        from repro.models import moe
        from repro.sharding.rules import ParallelPlan

        cfg = dataclasses.replace(get_config("grok-1-314b").reduced(),
                                  capacity_factor=8.0)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        plan = ParallelPlan.make(mesh, cfg, "decode")
        plan = dataclasses.replace(plan, weight_fsdp=("data",))
        assert plan.kind == "decode"
        p = moe.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, cfg.d_model))
        local, _ = moe.moe_ffn_local(x.reshape(-1, cfg.d_model), p, cfg)
        local = local.reshape(x.shape)
        dist, _ = jax.jit(lambda x, p: moe.moe_ffn(x, p, cfg, plan))(x, p)
        err = float(jnp.max(jnp.abs(dist - local)))
        print(json.dumps({"err": err, "mode": plan.moe_mode}))
    """)
    assert res["err"] < 2e-4, res


@pytest.mark.slow
def test_weight_stationary_moe_decode_ep_matches_local():
    """Same check in EP mode (experts divide the model axis)."""
    res = run_in_subprocess("""
        import dataclasses
        from repro.configs.base import get_config
        from repro.models import moe
        from repro.sharding.rules import ParallelPlan

        cfg = dataclasses.replace(get_config("kimi-k2-1t-a32b").reduced(),
                                  capacity_factor=8.0)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        plan = ParallelPlan.make(mesh, cfg, "decode")
        plan = dataclasses.replace(plan, weight_fsdp=("data",))
        assert plan.moe_mode == "ep", plan.moe_mode
        p = moe.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, cfg.d_model))
        local, _ = moe.moe_ffn_local(x.reshape(-1, cfg.d_model), p, cfg)
        local = local.reshape(x.shape)
        dist, _ = jax.jit(lambda x, p: moe.moe_ffn(x, p, cfg, plan))(x, p)
        err = float(jnp.max(jnp.abs(dist - local)))
        print(json.dumps({"err": err, "mode": plan.moe_mode}))
    """)
    assert res["err"] < 2e-4, res
