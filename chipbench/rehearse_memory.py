"""Compile a configuration's fused engine steps for a described TPU v5e and
print what each needs in device memory.  Run by hand, on a machine with no
chip; nothing runs, so the numbers are the compiler's, not a measurement:

  JAX_PLATFORMS=cpu PYTHONPATH=src python chipbench/rehearse_memory.py \
      --config granite-3-8b.pp2 --slots 16 32 --cache-len 512 --T 9 64

For each (slots, T) it prints the compiled step's argument, output and
temporary bytes, and the sizes the rollout keeps beside it: the weights,
the slot caches, and one request's KV blob at the export bucket.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def fused_jit(steps, T):
    """The jitted function behind ``StepFunctions.fused_step(T)`` (the
    getter returns a counting wrapper around it)."""
    counted = steps.fused_step(T)
    for cell in counted.__closure__ or ():
        if hasattr(cell.cell_contents, "lower"):
            return cell.cell_contents
    raise RuntimeError("no jitted function behind fused_step")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, nargs="+", required=True)
    ap.add_argument("--cache-len", type=int, required=True)
    ap.add_argument("--T", type=int, nargs="+", default=[9, 64])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.cells import load_config, model_config
    from repro.engine.engine import StepFunctions
    from repro.models import init_cache, init_params

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = model_config(load_config(args.config))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree)

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    params = on_chip(jax.eval_shape(
        lambda k: init_params(cfg, k)[0], jax.random.PRNGKey(0)))
    steps = StepFunctions(cfg)
    out = {"config": cfg.name, "weights_bytes": nbytes(params), "rows": []}
    one = jax.eval_shape(lambda: init_cache(cfg, 1, args.cache_len))
    out["cache_bytes_per_slot"] = nbytes(one)
    for B in args.slots:
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, B, args.cache_len)))
        for T in args.T:
            def s(shape, dt):
                return jax.ShapeDtypeStruct(shape, dt, sharding=chip)
            i32, b = jnp.int32, jnp.bool_
            compiled = fused_jit(steps, T).lower(
                params, cache, s((B, T), i32), s((B, T), i32),
                s((B, T), b), s((B, T, 2), jnp.uint32),
                s((B,), jnp.float32), s((B,), b), s((B,), i32),
                s((B,), i32)).compile()
            m = compiled.memory_analysis()
            row = {"slots": B, "T": T,
                   "argument_bytes": m.argument_size_in_bytes,
                   "output_bytes": m.output_size_in_bytes,
                   "alias_bytes": m.alias_size_in_bytes,
                   "temp_bytes": m.temp_size_in_bytes,
                   "cache_bytes": nbytes(cache)}
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
