"""CLI: serve GRPO groups through the Seer rollout subsystem (divided
rollout + context-aware scheduling + grouped SD).

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --groups 6 \
      --group-size 8 --max-new-tokens 48

``--full`` serves the published config instead of the tiny variant
(random weights from ``--seed``; the model must fit the device):

  PYTHONPATH=src python -m repro.launch.serve --full --arch zamba2-1.2b \
      --groups 4 --group-size 8 --prompt-len 512 --max-new-tokens 256 \
      --cache-len 2048 --chunk 64 --temperature 0

Reports the device JAX ran on, the rollout's counters, and two host
clocks kept apart: the whole run's wall seconds and the part of it spent
tracing and compiling.  Neither is a device metric.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional


def init_params_on_device(cfg, seed: int):
    """Random weights for ``cfg`` from ``seed``, made in one compiled
    program on the default device (no host copy of the weights).

    They are stored in the compute dtype (``cfg.dtype``), as a serving
    engine holds them: every matmul casts its weight to that dtype
    anyway (``models.common.lin``), and float32 weights would double the
    largest resident buffer (4.4 GB for zamba2-1.2b, which with two
    instances' caches, the pool's blobs and a step's temporaries does not
    fit a 16 GB v5e)."""
    import dataclasses

    import jax

    from repro.models import init_params
    served = dataclasses.replace(cfg, param_dtype=cfg.dtype)
    return jax.jit(lambda k: init_params(served, k)[0])(
        jax.random.PRNGKey(seed))


def make_traffic(cfg, *, groups: int, group_size: int, prompt_len: int,
                 max_new_tokens: int, temperature: float, seed: int,
                 min_new_tokens: Optional[int] = None):
    """``groups`` GRPO groups of ``group_size`` samples, each group
    sharing one random prompt of ``prompt_len`` in-vocabulary tokens.

    There is no stop token: a request decodes exactly its budget.  With
    ``min_new_tokens`` each request's budget is drawn from ``seed`` in
    ``[min_new_tokens, max_new_tokens]``, as samples of one prompt end
    at different lengths; otherwise every budget is ``max_new_tokens``
    (requests then run in lockstep, and the scheduler's deterministic
    placement can hand every chunk back to the instance it left)."""
    import numpy as np

    from repro.core import make_groups
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(3, cfg.vocab_size, size=prompt_len).tolist()
               for _ in range(groups)]
    out = make_groups(prompts, group_size, max_new_tokens=max_new_tokens,
                      temperature=temperature, stop_token=None, seed=seed)
    if min_new_tokens is not None:
        for g in out:
            for r in g.requests:
                r.max_new_tokens = int(
                    rng.integers(min_new_tokens, max_new_tokens + 1))
    return out


class CompileClock:
    """Host seconds JAX spends tracing, lowering and compiling (and
    reading the persistent cache) while the block runs, from JAX's own
    monitoring events.  JAX compiles on the dispatching thread, so these
    seconds are part of the block's wall time."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def _listen(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += event.endswith("backend_compile_duration")

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


def serve(cfg, params, groups, **rollout_kw):
    """Roll ``groups`` out once through :class:`SeerRollout`.

    ``rollout_kw`` goes to the rollout (``n_instances``, ``max_slots``,
    ``cache_len``, ``chunk_size``, ``tp``, ``devices``, ...); a shared
    ``steps`` (:class:`StepFunctions`) reuses compiled step shapes
    across calls.  Returns ``(RolloutResult, report)``."""
    import jax

    from repro.core import SeerRollout

    ro = SeerRollout(cfg, params, **rollout_kw)
    steps = ro.steps
    syncs0 = steps.host_syncs
    with CompileClock() as clock:
        t0 = time.perf_counter()
        res = ro.run(groups)
        wall = time.perf_counter() - t0
    engine_steps = sum(i.steps_run for i in ro.instances)
    s = res.stats
    dev = jax.devices()[0]
    report = {
        "arch": cfg.name,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "requests": sum(g.size for g in groups),
        "tokens": s.tokens, "engine_steps": engine_steps,
        "chunks": s.chunks, "migrations": s.migrations,
        "drafted": s.drafted, "accepted": s.accepted,
        "mean_acceptance": s.mean_acceptance,
        "host_syncs_per_step":
            (steps.host_syncs - syncs0) / max(engine_steps, 1),
        "host_wall_seconds": wall,
        "host_compile_seconds": clock.seconds,
        "compiles": clock.compiles,
        "pool": res.pool_stats, "dgds": res.dgds_stats,
        "ctx": res.ctx_stats,
    }
    return res, report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--full", action="store_true",
                    help="the published config, not the tiny variant")
    ap.add_argument("--groups", type=int, default=6)
    ap.add_argument("--group-size", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=6)
    ap.add_argument("--max-new-tokens", type=int, default=48)
    ap.add_argument("--instances", type=int, default=2)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=512)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--policy", default="seer",
                    choices=["seer", "fifo", "nocontext", "sfs", "lfs"])
    ap.add_argument("--no-spec-decode", action="store_true")
    ap.add_argument("--multipath", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from repro.configs import get_config, get_tiny_config
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else get_tiny_config(args.arch)
    params = init_params_on_device(cfg, args.seed)
    groups = make_traffic(cfg, groups=args.groups,
                          group_size=args.group_size,
                          prompt_len=args.prompt_len,
                          max_new_tokens=args.max_new_tokens,
                          temperature=args.temperature, seed=args.seed)
    _, report = serve(cfg, params, groups, n_instances=args.instances,
                      max_slots=args.slots, cache_len=args.cache_len,
                      chunk_size=args.chunk, policy=args.policy,
                      spec_decode=not args.no_spec_decode,
                      multipath_top_k=args.multipath)
    report["policy"] = args.policy
    print(json.dumps(report, indent=1, default=float))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, default=float)


if __name__ == "__main__":
    main()
