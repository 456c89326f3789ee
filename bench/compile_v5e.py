#!/usr/bin/env python3
"""Compile a language-model cell's serving programs for a described TPU
v5e, with nothing attached and nothing run: the admission, decode and
chunk-window programs at the configuration's full width and the cell's
engine shape (batch, tokens per slot, KV block, chunk width). Prints
each program's memory plan and whether the Pallas kernel is in it. A
program the chip's compiler refuses, or one that does not fit 16 GB,
shows here before any chip time is spent.

    JAX_PLATFORMS=cpu python3 bench/compile_v5e.py deepseek-7b [--blocks N]

``--blocks`` sets the pool's size (default: one ``max_seq`` stripe per
slot, the size the engine starts from before it shrinks to fit).
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--blocks", type=int, default=None)
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    from bench import spec
    from bench.drivers.lm_serve import program_config
    from repro.models.model import build_model
    from repro.serve.engine import (DEFAULT_PREFILL_CHUNK, paged_program_args,
                                    paged_programs)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    cfg = spec.config(args.config)
    eng = cfg["engine"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    model = build_model(program_config(cfg))
    put = lambda t: jax.tree.map(                          # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
    params = put(jax.eval_shape(model.init, jax.random.key(0)))
    per_slot = -(-eng["max_seq"] // eng["block_size"])
    blocks = args.blocks or eng["batch"] * per_slot + 1
    caches = put(jax.eval_shape(
        lambda: model.init_paged_cache(blocks, eng["block_size"])))
    width = eng.get("prefill_chunk") or DEFAULT_PREFILL_CHUNK
    a = paged_program_args(params, caches, batch=eng["batch"],
                           blocks_per_slot=per_slot, width=width,
                           sharding=one)
    jax.default_backend = lambda: "tpu"      # the kernel's compiled branch
    progs = paged_programs(model, block_size=eng["block_size"],
                           use_kernel=True)
    gb = 1e9
    for name in ("admit", "decode", "chunk"):
        c = progs[name].lower(*a[name]).compile()
        ma = c.memory_analysis()
        live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        print(f"{args.config} {name}: args {ma.argument_size_in_bytes / gb:.2f}"
              f" GB, temps {ma.temp_size_in_bytes / gb:.2f} GB, live "
              f"{live / gb:.2f} GB, kernel "
              f"{'tpu_custom_call' in c.as_text()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
