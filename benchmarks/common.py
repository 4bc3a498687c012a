"""Shared benchmark plumbing.

Simulated experiments run the Table-3 workloads at 1/SCALE (requests and
instances scaled together, preserving per-instance load and therefore the
throughput *ratios* the paper reports).  Each benchmark prints a table and
returns a JSON-able record; ``benchmarks.run`` writes results/bench/*.json
and the roll-up used by EXPERIMENTS.md.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional

# the tp benchmark needs a multi-device CPU mesh; the flag only works
# if set before the FIRST jax import in the process (tests get this
# from conftest.py — standalone `python benchmarks/common.py` runs get
# it here).  A user XLA_FLAGS forcing a device count wins.
_FORCE = "--xla_force_host_platform_device_count=8"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_FORCE + " " + _flags).strip()

import numpy as np

from repro.configs import get_config
from repro.core.simulator import ClusterSimulator, SimConfig
from repro.data.workload import (KIMI_K2, MOONLIGHT, QWEN2_VL_72B, Workload,
                                 WorkloadSpec, make_workload)

RESULTS_DIR = os.environ.get("BENCH_OUT", "results/bench")

# Per-workload deployment calibration (Table 3 geometry at 1/SCALE).
# kv_capacity reflects the paper's memory-constrained regimes: capacity is
# a small multiple of the max-length request so concurrency is KV-bound.
SCALE = 8
DEPLOY = {
    "moonlight": dict(cfg="moonshot-v1-16b-a3b", chips=1,
                      kv_tokens=150_000, slots=48),
    "qwen2-vl-72b": dict(cfg="llama-3.2-vision-11b", chips=8,
                         kv_tokens=120_000, slots=64),
    "kimi-k2": dict(cfg="deepseek-moe-16b", chips=32,
                    kv_tokens=400_000, slots=64),
}
SPECS = {"moonlight": MOONLIGHT, "qwen2-vl-72b": QWEN2_VL_72B,
         "kimi-k2": KIMI_K2}


def scaled_spec(name: str, scale: int = SCALE) -> WorkloadSpec:
    s = SPECS[name]
    return dataclasses.replace(
        s, n_requests=max(s.group_size * 8, s.n_requests // scale),
        n_instances=max(2, s.n_instances // scale))


def run_sim(workload_name: str, wl: Workload, *, mode: str,
            policy: str = "fifo", sd: str = "none", **kw):
    dep = DEPLOY[workload_name]
    spec = wl.spec
    sim = SimConfig(mode=mode, policy=policy, sd=sd,
                    max_slots=dep["slots"],
                    chips_per_instance=dep["chips"],
                    kv_capacity_tokens=dep["kv_tokens"], **kw)
    cfg = get_config(dep["cfg"])
    return ClusterSimulator(cfg, spec, sim).run(wl)


def workload(name: str, seed: int = 0, scale: int = SCALE) -> Workload:
    return make_workload(scaled_spec(name, scale), seed=seed)


def save_result(name: str, record: dict) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    record = dict(record)
    record["benchmark"] = name
    record["timestamp"] = time.strftime("%Y-%m-%d %H:%M:%S")
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump(record, f, indent=1, default=float)


# ---------------------------------------------------------------------------
# BENCH_rollout.json — machine-readable rollout perf trajectory
# ---------------------------------------------------------------------------

BENCH_ROLLOUT = "BENCH_rollout.json"


def update_bench_rollout(section: str, record: dict) -> dict:
    """Merge ``record`` under ``section`` of RESULTS_DIR/BENCH_rollout.json.

    One file, sections per contributor (engine / phase_split /
    e2e_throughput), so the perf trajectory of the rollout hot path is
    tracked in a single machine-readable artifact from PR to PR.
    """
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, BENCH_ROLLOUT)
    doc: dict = {"benchmark": "rollout"}
    if os.path.exists(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict):
                doc = loaded
        except (OSError, ValueError):
            pass
    doc[section] = record
    doc["timestamp"] = time.strftime("%Y-%m-%d %H:%M:%S")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=float)
    return doc


def bench_engine_rollout(n_requests: int = 16, n_instances: int = 2,
                         max_slots: int = 4, prompt_len: int = 96,
                         max_new_tokens: int = 8, prefill_chunk: int = 16,
                         seed: int = 5) -> dict:
    """Admission-heavy real-engine rollout (tiny model): long prompts,
    short decode, so admission prefill dominates.  Runs the sequential
    seed path (sync prefill) and the batched mixed-step path on identical
    workloads and reports tokens/s, engine forward invocations,
    prefill-wasted-row fraction and admission latency for each.
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.core.request import make_groups
    from repro.core.rollout import SeerRollout

    cfg = get_tiny_config("granite-3-8b")
    from repro.models import init_params
    params, _ = init_params(cfg, jax.random.PRNGKey(1))
    group_size = 2
    prompts = [[(13 * g + j) % (cfg.vocab_size - 2) + 1
                for j in range(prompt_len)]
               for g in range(n_requests // group_size)]

    def one(mode: str) -> dict:
        ro = SeerRollout(
            cfg, params, n_instances=n_instances, max_slots=max_slots,
            cache_len=prompt_len + max_new_tokens + 32,
            chunk_size=1 << 20, prefill_chunk=prefill_chunk,
            prefill_mode=mode, policy="fifo", spec_decode=False,
            base_seed=7)
        # warm-up pass compiles the step shapes so the timed pass
        # measures steady-state throughput, not XLA compile time
        ro.run(make_groups(prompts[:1], group_size=group_size,
                           max_new_tokens=max_new_tokens, seed=seed))
        inv0 = ro.steps.invocations
        hs0 = ro.steps.host_syncs
        steps0 = sum(i.steps_run for i in ro.instances)
        for inst in ro.instances:
            inst.row_slots_total = inst.row_slots_active = 0
            inst.admits = 0
            inst.admit_seconds = 0.0
            inst.tail_fused_rows = 0
        groups = make_groups(prompts, group_size=group_size,
                             max_new_tokens=max_new_tokens, seed=seed)
        t0 = time.perf_counter()
        res = ro.run(groups)
        wall = time.perf_counter() - t0
        rows_total = sum(i.row_slots_total for i in ro.instances)
        rows_active = sum(i.row_slots_active for i in ro.instances)
        admits = sum(i.admits for i in ro.instances)
        admit_s = sum(i.admit_seconds for i in ro.instances)
        engine_steps = sum(i.steps_run for i in ro.instances) - steps0
        return {
            "forward_invocations": ro.steps.invocations - inv0,
            "engine_steps": engine_steps,
            "host_syncs_per_step":
                (ro.steps.host_syncs - hs0) / max(engine_steps, 1),
            "tail_fused_rows": sum(i.tail_fused_rows for i in ro.instances),
            "tokens_per_sec": res.stats.tokens / max(wall, 1e-9),
            "wall_seconds": wall,
            "prefill_wasted_row_frac":
                1.0 - rows_active / max(rows_total, 1),
            "admission_latency_s": admit_s / max(admits, 1),
            "responses": res.responses(),
        }

    sync = one("sync")
    batched = one("batched")
    token_exact = sync.pop("responses") == batched.pop("responses")
    from repro.engine import donation_supported
    return {
        "cache_donated": donation_supported(),
        "workload": {
            "n_requests": n_requests, "n_instances": n_instances,
            "max_slots": max_slots, "prompt_len": prompt_len,
            "max_new_tokens": max_new_tokens,
            "prefill_chunk": prefill_chunk,
        },
        "sync": sync,
        "batched": batched,
        "forward_invocation_ratio":
            sync["forward_invocations"] / max(batched["forward_invocations"],
                                              1),
        "token_exact": token_exact,
    }


def bench_engine_migration(n_requests: int = 12, n_instances: int = 2,
                           max_slots: int = 2, prompt_len: int = 32,
                           max_new_tokens: int = 24, chunk_size: int = 8,
                           prefill_chunk: int = 16, seed: int = 5) -> dict:
    """Migration-heavy real-engine rollout (tiny model): small chunks
    force every request through several pool round-trips.  Runs the
    PR 2 per-slot migration path and the batched+overlapped path on
    identical workloads and reports migration device calls per migrated
    slot, bytes moved, host migration stall seconds and the fraction of
    exports dispatched while a step was in flight (overlap window).
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.core.request import make_groups
    from repro.core.rollout import SeerRollout

    cfg = get_tiny_config("granite-3-8b")
    from repro.models import init_params
    params, _ = init_params(cfg, jax.random.PRNGKey(1))
    group_size = 2
    # staggered prompt lengths so slots do NOT hit chunk boundaries in
    # lockstep: releases then interleave with live steps (the export
    # overlap window) and requeued chunks land on whichever instance
    # frees up first (cross-instance migrations)
    plens = [prompt_len + 7 * g for g in range(n_requests // group_size)]
    prompts = [[(11 * g + j) % (cfg.vocab_size - 2) + 1
                for j in range(plens[g])]
               for g in range(n_requests // group_size)]

    def one(prefill_mode: str, migration_mode: Optional[str]) -> dict:
        # seer scheduling spreads resumed chunks across instances
        # (cross-instance migrations), unlike fifo's submit-order
        # ping-back to the home instance
        # admit-into-draining and in-place renewal are pinned off: this
        # bench measures the PR 3 batched+overlapped export window and
        # migration volume; the takeover/renewal paths are measured by
        # bench_engine_topology
        ro = SeerRollout(
            cfg, params, n_instances=n_instances, max_slots=max_slots,
            cache_len=max(plens) + max_new_tokens + 32,
            chunk_size=chunk_size, prefill_chunk=prefill_chunk,
            prefill_mode=prefill_mode, migration_mode=migration_mode,
            admit_into_draining=False, final_chunk_inplace=False,
            policy="seer", spec_decode=False, base_seed=7)
        # warm-up on the full workload compiles every step + migration
        # batch shape so the timed pass measures steady-state cost, not
        # XLA compile time
        ro.run(make_groups(prompts, group_size=group_size,
                           max_new_tokens=max_new_tokens, seed=seed))
        mig_calls0 = ro.steps.migration_calls
        pool0 = dict(ro.pool.stats())
        for inst in ro.instances:
            inst.slots_exported = inst.slots_imported = 0
            inst.export_overlapped_slots = 0
            inst.migration_bytes_out = inst.migration_bytes_in = 0
            inst.migration_host_seconds = 0.0
            inst.steps_run = 0
        groups = make_groups(prompts, group_size=group_size,
                             max_new_tokens=max_new_tokens, seed=seed)
        t0 = time.perf_counter()
        res = ro.run(groups)
        wall = time.perf_counter() - t0
        steps_run = sum(i.steps_run for i in ro.instances)
        exported = sum(i.slots_exported for i in ro.instances)
        imported = sum(i.slots_imported for i in ro.instances)
        overlapped = sum(i.export_overlapped_slots for i in ro.instances)
        pool = ro.pool.stats()
        return {
            "migrations": res.stats.migrations,
            "chunks": res.stats.chunks,
            "engine_steps": steps_run,
            "migrations_per_step":
                res.stats.migrations / max(steps_run, 1),
            "slots_exported": exported,
            "slots_imported": imported,
            "migration_device_calls":
                ro.steps.migration_calls - mig_calls0,
            "device_calls_per_migrated_slot":
                (ro.steps.migration_calls - mig_calls0)
                / max(exported + imported, 1),
            "export_overlap_fraction": overlapped / max(exported, 1),
            "pool_bytes_moved_mb":
                (pool["bytes_moved_gb"] - pool0["bytes_moved_gb"]) * 1024,
            "migration_stall_seconds":
                sum(i.migration_host_seconds for i in ro.instances),
            "tokens_per_sec": res.stats.tokens / max(wall, 1e-9),
            "wall_seconds": wall,
            "responses": res.responses(),
        }

    sync = one("sync", None)
    perslot = one("batched", "perslot")
    batched = one("batched", "batched")
    resp = {k: m.pop("responses") for k, m in
            (("sync", sync), ("perslot", perslot), ("batched", batched))}
    return {
        "workload": {
            "n_requests": n_requests, "n_instances": n_instances,
            "max_slots": max_slots, "prompt_len": prompt_len,
            "max_new_tokens": max_new_tokens, "chunk_size": chunk_size,
            "prefill_chunk": prefill_chunk,
        },
        "sync": sync,
        "perslot": perslot,
        "batched": batched,
        "token_exact":
            resp["sync"] == resp["perslot"] == resp["batched"],
        "device_call_ratio":
            perslot["device_calls_per_migrated_slot"]
            / max(batched["device_calls_per_migrated_slot"], 1e-9),
    }


def bench_engine_topology(n_requests: int = 16, n_instances: int = 4,
                          n_nodes: int = 2, max_slots: int = 2,
                          prompt_len: int = 24, max_new_tokens: int = 20,
                          chunk_size: int = 6, prefill_chunk: int = 8,
                          seed: int = 5) -> dict:
    """Cross-node topology micro-benchmark (tiny model): 2 nodes x 2
    instances, small chunks, so resumed chunks constantly choose
    between a same-node and a cross-node placement.  Runs the sync
    oracle, topology-blind batched placement and topology-aware batched
    placement on identical workloads; reports cross-node fabric bytes
    and fetches, modeled pool transfer seconds, in-place final-chunk
    renewals (eviction-aware export) and token-exactness across all
    three paths.

    Two slots per instance matter: with a single slot the overlapped
    scheduling tick (admissions ride behind the in-flight step and a
    second pass fills just-flushed slots) almost always faces exactly
    one open instance per decision, and topology-aware vs -blind
    placement degenerate to the same choice.
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.core.request import make_groups
    from repro.core.rollout import SeerRollout
    from repro.engine import StepFunctions

    cfg = get_tiny_config("granite-3-8b")
    from repro.models import init_params
    params, _ = init_params(cfg, jax.random.PRNGKey(1))
    group_size = 2
    # staggered prompt lengths: releases interleave with live steps and
    # requeued chunks must pick an instance while their home node is
    # sometimes busy — the placement decision the bench measures
    plens = [prompt_len + 5 * g for g in range(n_requests // group_size)]
    prompts = [[(11 * g + j) % (cfg.vocab_size - 2) + 1
                for j in range(plens[g])]
               for g in range(n_requests // group_size)]
    steps = StepFunctions(cfg)     # shared: compiles amortize over runs

    def one(prefill_mode: str, topology_aware: bool) -> dict:
        # placement-aware export is pinned off: it moves fabric bytes
        # to the export leg (export_placed_remote_bytes), so leaving it
        # on would let the aware-vs-blind cross_node_bytes comparison
        # measure relabeled traffic instead of placement-ranking wins;
        # the feature is measured by its own test and pool stats
        ro = SeerRollout(
            cfg, params, n_instances=n_instances, max_slots=max_slots,
            cache_len=max(plens) + max_new_tokens + 32,
            chunk_size=chunk_size, prefill_chunk=prefill_chunk,
            prefill_mode=prefill_mode, n_nodes=n_nodes,
            topology_aware=topology_aware,
            placement_aware_export=False, final_chunk_inplace=True,
            policy="seer", spec_decode=False, base_seed=7, steps=steps)
        groups = make_groups(prompts, group_size=group_size,
                             max_new_tokens=max_new_tokens, seed=seed)
        # warm-up compiles the step/migration shapes
        ro.run(make_groups(prompts, group_size=group_size,
                           max_new_tokens=max_new_tokens, seed=seed))
        pool0 = dict(ro.pool.stats())
        # instance counters are lifetime totals: snapshot after warm-up
        # so the record reflects the timed run only
        takeovers0 = sum(i.takeover_admits for i in ro.instances)
        exported0 = sum(i.slots_exported for i in ro.instances)
        overlapped0 = sum(i.export_overlapped_slots for i in ro.instances)
        t0 = time.perf_counter()
        res = ro.run(groups)
        wall = time.perf_counter() - t0
        pool = ro.pool.stats()
        exported = sum(i.slots_exported for i in ro.instances) - exported0
        overlapped = sum(i.export_overlapped_slots
                         for i in ro.instances) - overlapped0
        return {
            "migrations": res.stats.migrations,
            "chunks": res.stats.chunks,
            "inplace_renewals": res.stats.inplace_renewals,
            "takeover_admits":
                sum(i.takeover_admits for i in ro.instances) - takeovers0,
            "cross_node_bytes":
                pool["cross_node_bytes"] - pool0["cross_node_bytes"],
            "cross_node_fetches":
                pool["cross_node_fetches"] - pool0["cross_node_fetches"],
            "pool_bytes_moved_mb":
                (pool["bytes_moved_gb"] - pool0["bytes_moved_gb"]) * 1024,
            "pool_transfer_seconds":
                pool["transfer_seconds"] - pool0["transfer_seconds"],
            "export_overlap_fraction": overlapped / max(exported, 1),
            "tokens_per_sec": res.stats.tokens / max(wall, 1e-9),
            "wall_seconds": wall,
            "responses": res.responses(),
        }

    sync = one("sync", False)
    blind = one("batched", False)
    aware = one("batched", True)
    resp = {k: m.pop("responses") for k, m in
            (("sync", sync), ("blind", blind), ("aware", aware))}
    return {
        "workload": {
            "n_requests": n_requests, "n_instances": n_instances,
            "n_nodes": n_nodes, "max_slots": max_slots,
            "prompt_len": prompt_len, "max_new_tokens": max_new_tokens,
            "chunk_size": chunk_size, "prefill_chunk": prefill_chunk,
        },
        "sync": sync,
        "blind": blind,
        "aware": aware,
        "token_exact":
            resp["sync"] == resp["blind"] == resp["aware"],
        "cross_node_bytes_ratio":
            blind["cross_node_bytes"]
            / max(aware["cross_node_bytes"], 1),
    }


def bench_engine_tree(n_groups: int = 3, group_size: int = 4,
                      n_instances: int = 1, max_slots: int = 4,
                      prompt_len: int = 12, max_new_tokens: int = 48,
                      prefill_chunk: int = 8, top_k: int = 3,
                      vocab: int = 12, cst_lookup_max: int = 2,
                      seed: int = 5) -> dict:
    """Tree-speculation micro-benchmark on the grouped CST workload.

    Groups of ``group_size`` requests share a prompt at temperature 1.0
    over a small vocabulary with a short CST lookup, so drafting
    contexts collide across the group and the CST sees several
    continuations per match — moderate trunk accuracy with real
    rank-2/3 mass, the regime where verifying the side branches pays
    (with a long unambiguous lookup the trunk is near-perfect and
    linear already wins; the ROADMAP notes this explicitly).  A warm-up
    iteration populates the DGDS CST with every member's stream
    (cross-RL-step context reuse); the acceptance profile is then reset
    at the iteration boundary (``reset_acceptance_profile`` — stale β
    from the cold iteration would pin γ at 0) and the timed iteration
    measures, at the SAME MBA draft-token budget γ per request:

    * ``linear``   — best-path drafts, single-chain verify (the oracle),
    * ``tree_top1``— tree mode restricted to one path: must be
      token-exact with ``linear`` (the spec_mode switch is free),
    * ``tree``     — multi-path drafts merged into token trees; side
      branches rescue steps the trunk loses, raising accepted
      tokens/forward with no extra forwards and no extra host syncs.
    """
    import dataclasses as _dc

    import jax
    from repro.configs import get_tiny_config
    from repro.core.request import make_groups
    from repro.core.rollout import SeerRollout

    cfg = _dc.replace(get_tiny_config("granite-3-8b"), vocab_size=vocab)
    from repro.models import init_params
    params, _ = init_params(cfg, jax.random.PRNGKey(1))
    prompts = [[(13 * g + j) % (cfg.vocab_size - 2) + 1
                for j in range(prompt_len)] for g in range(n_groups)]

    def one(spec_mode: str, k: int) -> dict:
        ro = SeerRollout(
            cfg, params, n_instances=n_instances, max_slots=max_slots,
            cache_len=prompt_len + max_new_tokens + 32,
            chunk_size=1 << 20, prefill_chunk=prefill_chunk,
            policy="seer", spec_decode=True, spec_mode=spec_mode,
            multipath_top_k=k, cst_lookup_max=cst_lookup_max,
            base_seed=7)
        groups = make_groups(prompts, group_size=group_size,
                             max_new_tokens=max_new_tokens,
                             temperature=1.0, seed=seed)
        # warm-up: compiles step shapes AND populates the grouped CST
        # with every member's stream (the cross-RL-step context reuse
        # the paper's DGDS is built for); the acceptance profile resets
        # at the iteration boundary
        ro.run(groups)
        ro.reset_acceptance_profile()
        groups = make_groups(prompts, group_size=group_size,
                             max_new_tokens=max_new_tokens,
                             temperature=1.0, seed=seed)
        hs0 = ro.steps.host_syncs
        steps0 = sum(i.steps_run for i in ro.instances)
        nodes0 = sum(i.tree_nodes for i in ro.instances)
        bnodes0 = sum(i.tree_branch_nodes for i in ro.instances)
        t0 = time.perf_counter()
        res = ro.run(groups)
        wall = time.perf_counter() - t0
        engine_steps = sum(i.steps_run for i in ro.instances) - steps0
        return {
            "engine_steps": engine_steps,
            "drafted": res.stats.drafted,
            "accepted": res.stats.accepted,
            "mean_acceptance": res.stats.mean_acceptance,
            "drafted_per_step": res.stats.drafted / max(engine_steps, 1),
            "accepted_per_step":
                res.stats.accepted / max(engine_steps, 1),
            "tokens_per_step": res.stats.tokens / max(engine_steps, 1),
            "tree_nodes":
                sum(i.tree_nodes for i in ro.instances) - nodes0,
            "tree_branch_nodes":
                sum(i.tree_branch_nodes for i in ro.instances) - bnodes0,
            "host_syncs_per_step":
                (ro.steps.host_syncs - hs0) / max(engine_steps, 1),
            "branch_beta": list(ro.ctx.branch_beta),
            "tokens_per_sec": res.stats.tokens / max(wall, 1e-9),
            "wall_seconds": wall,
            "responses": res.responses(),
        }

    linear = one("linear", 1)
    tree1 = one("tree", 1)
    tree = one("tree", top_k)
    resp = {k: m.pop("responses") for k, m in
            (("linear", linear), ("tree_top1", tree1), ("tree", tree))}
    return {
        "workload": {
            "n_groups": n_groups, "group_size": group_size,
            "n_instances": n_instances, "max_slots": max_slots,
            "prompt_len": prompt_len, "max_new_tokens": max_new_tokens,
            "prefill_chunk": prefill_chunk, "top_k": top_k,
        },
        "linear": linear,
        "tree_top1": tree1,
        "tree": tree,
        "token_exact":
            resp["linear"] == resp["tree_top1"] == resp["tree"],
        "accepted_per_step_ratio":
            tree["accepted_per_step"]
            / max(linear["accepted_per_step"], 1e-9),
    }


def bench_train_overlap(n_groups: int = 3, group_size: int = 2,
                        max_new_tokens: int = 8, iterations: int = 3,
                        n_instances: int = 2, max_slots: int = 2,
                        seed: int = 3) -> dict:
    """Bounded-staleness rollout<->train overlap on a tiny RL pipeline.

    Three modes over the same workload (n_groups * group_size requests
    per iteration on n_instances * max_slots slots — deliberately
    non-tiling, so the final admission wave leaves idle slots = tail
    bubbles the streaming loop can pack):

    * ``sync``      — the strict barrier loop (rollout → train →
      refresh), the oracle,
    * ``stream_s0`` — the streaming loop at ``staleness_bound=0``:
      injection can never fire, so it must be token- AND loss-exact
      with ``sync`` (``staleness0_token_exact`` gates it),
    * ``stream_s1`` — ``staleness_bound=1``: next-iteration prompts
      inject into tail bubbles, finished iterations train mid-stream,
      and the in-flight weight refresh re-anchors live slots; the
      ledger proves no trained token exceeded the bound.

    A divided-mode simulator run of the same shape reports the
    barrier-stall seconds the overlap reclaims at cluster scale.
    """
    import dataclasses as _dc

    from repro.data.tasks import make_task
    from repro.training.loop import RLConfig, RLTrainer
    from repro.configs import get_tiny_config

    cfg = _dc.replace(get_tiny_config("granite-3-8b"), vocab_size=32)
    task = make_task("copy", 32, prompt_len=4,
                     response_len=max_new_tokens, content_vocab=8)

    def one(**kw):
        rl = RLConfig(n_groups=n_groups, group_size=group_size,
                      max_new_tokens=max_new_tokens,
                      iterations=iterations, n_instances=n_instances,
                      max_slots=max_slots, cache_len=128,
                      chunk_size=max_new_tokens, seed=seed,
                      log=lambda s: None, **kw)
        tr = RLTrainer(cfg, task, rl)
        responses: Dict[str, list] = {}
        orig_submit = tr.rewards.submit

        def submit(rid, prompt, gen):
            responses[rid] = list(gen)
            return orig_submit(rid, prompt, gen)

        tr.rewards.submit = submit
        t0 = time.perf_counter()
        hist = tr.run()
        wall = time.perf_counter() - t0
        steps = sum(i.steps_run for i in tr.rollout.instances)
        total_led = tr.ledger.total_tokens()
        rec = {
            "wall_seconds": wall,
            "losses": [h.loss for h in hist],
            "mean_rewards": [h.mean_reward for h in hist],
            "tokens": sum(h.tokens for h in hist),
            "host_syncs_per_step":
                tr.rollout.steps.host_syncs / max(steps, 1),
            "max_staleness": tr.ledger.max_staleness,
            "stale_token_frac":
                (1.0 - tr.ledger.total_tokens(0) / total_led)
                if total_led else 0.0,
        }
        return rec, responses, tr

    sync, sync_resp, _ = one()
    s0, s0_resp, _ = one(async_overlap=True, staleness_bound=0)
    s1, s1_resp, tr1 = one(async_overlap=True, staleness_bound=1)
    # unified stats surface: per-stream RolloutStats snapshots (plain
    # dicts), summed by key instead of ad-hoc attribute reads
    snaps = [r.stats.snapshot() for r in tr1.stream_results]
    overlap = {"streams": len(snaps)}
    for key in ("overlap_steps", "reclaimed_rows", "refreshes",
                "injected_groups", "reval_tokens", "reval_accepted"):
        overlap[key] = sum(s[key] for s in snaps)

    # cluster-scale barrier stall (divided-mode sim, same shape idea):
    # how many instance-seconds the iteration barrier wastes, and what
    # the bounded-staleness overlap reclaims
    spec = _dc.replace(MOONLIGHT, n_requests=24, group_size=4,
                       n_instances=2, max_gen_length=4096,
                       mean_gen_length=1200)
    wl = make_workload(spec, seed=seed)
    skw = dict(mode="divided", policy="seer", max_slots=8,
               chips_per_instance=1, kv_capacity_tokens=40_000,
               chunk_size=512)
    scfg = get_config("yi-6b")
    r_sync = ClusterSimulator(scfg, spec, SimConfig(**skw)).run(wl)
    r_async = ClusterSimulator(
        scfg, spec, SimConfig(**skw, async_overlap=True)).run(wl)
    sim_barrier = {
        "barrier_stall_seconds":
            r_sync.extras["barrier_stall_seconds"],
        "barrier_stall_reclaimed":
            r_async.extras["barrier_stall_reclaimed"],
        "effective_speedup":
            r_sync.total_time
            / max(r_async.extras["effective_time"], 1e-9),
    }

    return {
        "workload": {
            "n_groups": n_groups, "group_size": group_size,
            "max_new_tokens": max_new_tokens, "iterations": iterations,
            "n_instances": n_instances, "max_slots": max_slots,
            "seed": seed,
        },
        "sync": sync,
        "stream_s0": s0,
        "stream_s1": s1,
        "staleness0_token_exact":
            sync_resp == s0_resp and sync["losses"] == s0["losses"],
        "overlap": overlap,
        "sim_barrier": sim_barrier,
    }


def bench_engine_faults(n_groups: int = 3, group_size: int = 2,
                        max_new_tokens: int = 14, n_instances: int = 3,
                        max_slots: int = 2, chunk_size: int = 5,
                        prefill_chunk: int = 8, seed: int = 5) -> dict:
    """Fault-tolerant divided rollout (tiny model, real engine): one
    deterministic fault schedule covering every recovery path — an
    instance crash, a short stall that waits out, a long stall the
    watchdog escalates to a crash, a pool fetch that fails past the
    retry budget (degrading to replay), and a corrupted blob caught by
    its checksum and recovered on retry.

    The faulted run must be **token-lossless**: every response
    bit-identical to a no-fault oracle on the same workload
    (``token_exact`` / ``tokens_lost == 0`` gate it), with recovery
    overhead bounded by the faulted requests' remaining decode budget
    and the 1-host-sync-per-step contract intact under faults.

    A divided-mode simulator run with ``fault_rate > 0`` reports the
    projected recovery overhead at cluster scale.
    """
    import dataclasses as _dc
    import jax
    from repro.configs import get_tiny_config
    from repro.core.faults import FaultEvent, FaultInjector
    from repro.core.request import make_groups
    from repro.core.rollout import SeerRollout
    from repro.models import init_params

    cfg = get_tiny_config("granite-3-8b")
    params, _ = init_params(cfg, jax.random.PRNGKey(1))
    # staggered prompts: slots hit chunk boundaries out of lockstep, so
    # the crash tick catches victims both AT a boundary (blob recovery)
    # and mid-chunk (replay recovery)
    plens = [6 + 4 * g for g in range(n_groups)]
    prompts = [[(7 * g + 3 * j) % (cfg.vocab_size - 2) + 1
                for j in range(plens[g])]
               for g in range(n_groups)]

    def make(injector=None, steps=None):
        # gamma_max=8 with spec_decode off: normal decode stays plain,
        # but crash replay re-feeds saved tokens as verify drafts in
        # bulk (8/step) instead of one re-decode step per token.
        # Takeover and in-place renewal are pinned off so every chunk
        # boundary is a pool round-trip: the fetch-fault and
        # blob-recovery paths this bench measures then fire on every
        # re-admission (the fuzz suite covers the takeover modes).
        return SeerRollout(
            cfg, params, n_instances=n_instances, max_slots=max_slots,
            cache_len=max(plens) + max_new_tokens + 32,
            chunk_size=chunk_size, prefill_chunk=prefill_chunk,
            admit_into_draining=False, final_chunk_inplace=False,
            policy="seer", spec_decode=False, gamma_max=8,
            base_seed=7, fault_injector=injector,
            watchdog_ticks=3, fetch_retries=3, steps=steps)

    def groups():
        return make_groups(prompts, group_size=group_size,
                           max_new_tokens=max_new_tokens, seed=seed)

    def one(ro, injector=None):
        # warm-up compiles every step shape (and, for the faulted pass,
        # runs fault-free: the injector arms only for the timed pass)
        ro.run(groups())
        ro.faults = injector
        hs0 = ro.steps.host_syncs
        steps0 = sum(i.steps_run for i in ro.instances)
        t0 = time.perf_counter()
        res = ro.run(groups())
        wall = time.perf_counter() - t0
        engine_steps = sum(i.steps_run for i in ro.instances) - steps0
        # unified stats surface: read the fault/recovery counters off
        # the RolloutStats snapshot (one consistent dict) rather than
        # attribute-by-attribute
        s = res.stats.snapshot()
        rec = {
            "engine_steps": engine_steps,
            "ticks": s["ticks"],
            "host_syncs_per_step":
                (ro.steps.host_syncs - hs0) / max(engine_steps, 1),
            "tokens_per_sec": s["tokens"] / max(wall, 1e-9),
            "wall_seconds": wall,
        }
        rec.update((k, s[k]) for k in (
            "instance_crashes", "watchdog_escalations", "stuck_ticks",
            "recovered_requests", "recovered_via_blob",
            "recovered_via_replay", "recovery_redecode_tokens",
            "recovery_replay_tokens", "faulted_remaining_tokens",
            "fetch_failures", "fetch_degraded", "corrupt_blobs",
            "fetch_backoff_seconds"))
        rec["responses"] = res.responses()
        return rec

    ro_o = make()
    oracle = one(ro_o)
    T = oracle["ticks"]
    schedule = [
        # late-run crash: victims mid-chunk past their first boundary,
        # so recovery resumes from the pooled blob and re-decodes only
        # the in-chunk tail
        FaultEvent(tick=max(2, (3 * T) // 5), kind="crash",
                   instance_id="inst1"),
        # short stall: waits out below watchdog_ticks, no escalation
        FaultEvent(tick=3, kind="stuck", instance_id="inst2", ticks=2),
        # long stall on live work: watchdog escalates to a crash
        FaultEvent(tick=max(4, T // 3), kind="stuck",
                   instance_id="inst0", ticks=8),
        # armed fetch faults persist until fetches consume them, and one
        # fetch's retry loop drains the queue back-to-back — so the
        # three fetch faults are spaced across ticks to land on three
        # DIFFERENT fetches: failures past the retry budget (degrade to
        # re-prefill) on the first re-admission wave ...
        FaultEvent(tick=2, kind="fetch_fail", count=3),
        # ... checksum-caught corruption (pool keeps the intact entry,
        # the retry fetch recovers without replay) mid-run ...
        FaultEvent(tick=max(3, T // 2), kind="corrupt", count=1),
        # ... and failures within the budget (retry succeeds) later
        FaultEvent(tick=max(4, T // 2 + 2), kind="fetch_fail", count=2),
    ]
    # a crashed instance stays dead, so the faulted pass needs a fresh
    # rollout; sharing the oracle's StepFunctions skips recompilation
    faulted = one(make(steps=ro_o.steps), FaultInjector(schedule))

    resp_o = oracle.pop("responses")
    resp_f = faulted.pop("responses")
    tokens_lost = 0
    for rid, toks in resp_o.items():
        got = resp_f.get(rid, [])
        tokens_lost += sum(1 for a, b in zip(toks, got) if a != b)
        tokens_lost += abs(len(toks) - len(got))
    extra_steps = faulted["engine_steps"] - oracle["engine_steps"]

    # cluster-scale projection: the same divided-mode sim shape as
    # bench_train_overlap, with the per-segment fault model on
    spec = _dc.replace(MOONLIGHT, n_requests=24, group_size=4,
                       n_instances=2, max_gen_length=4096,
                       mean_gen_length=1200)
    wl = make_workload(spec, seed=seed)
    skw = dict(mode="divided", policy="seer", max_slots=8,
               chips_per_instance=1, kv_capacity_tokens=40_000,
               chunk_size=512)
    scfg = get_config("yi-6b")
    r0 = ClusterSimulator(scfg, spec, SimConfig(**skw)).run(wl)
    rf = ClusterSimulator(
        scfg, spec,
        SimConfig(**skw, fault_rate=0.05, mttr_ticks=8)).run(wl)
    sim_faults = {
        "fault_rate": 0.05,
        "mttr_ticks": 8,
        "fault_events": rf.extras["fault_events"],
        "fault_lost_seconds": rf.extras["fault_lost_seconds"],
        "fault_downtime_seconds": rf.extras["fault_downtime_seconds"],
        "fault_recovery_seconds": rf.extras["fault_recovery_seconds"],
        "fault_overhead_frac": rf.extras["fault_overhead_frac"],
        "time_ratio": rf.total_time / max(r0.total_time, 1e-9),
    }

    return {
        "workload": {
            "n_groups": n_groups, "group_size": group_size,
            "max_new_tokens": max_new_tokens,
            "n_instances": n_instances, "max_slots": max_slots,
            "chunk_size": chunk_size, "prefill_chunk": prefill_chunk,
            "seed": seed, "watchdog_ticks": 3, "fetch_retries": 3,
        },
        "schedule": [
            {"tick": e.tick, "kind": e.kind,
             "instance_id": e.instance_id, "ticks": e.ticks,
             "count": e.count}
            for e in schedule
        ],
        "oracle": oracle,
        "faulted": faulted,
        "token_exact": resp_o == resp_f,
        "tokens_lost": tokens_lost,
        "recovery_extra_steps": extra_steps,
        "recovery_overhead_ratio":
            extra_steps / max(faulted["faulted_remaining_tokens"], 1),
        "sim_faults": sim_faults,
    }


def bench_engine_tp(n_new: int = 10, seed: int = 5) -> dict:
    """Tensor-parallel engine step (tiny models, forced-multi-device CPU
    mesh): one arch per family — dense transformer, MoE, SSM-hybrid —
    each run unmeshed (the 1-chip oracle), at tp=1 (degenerate mesh) and
    at tp=2 (head/ff column-parallel sharding).

    Correctness gates (scripts/check_bench.py): tp=1 must be
    bit-identical to the oracle (tokens, steps AND host syncs — its
    constraints are pure annotations), tp=2 must commit the exact oracle
    tokens under mixed plain + linear-spec decode while keeping the
    <=1-host-sync-per-step contract, the MoE path must model nonzero
    all-to-all collective bytes, and the simulator's per-instance cost
    model must agree with the engine rollout's at the same tp degree.
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.core.sdmodel import TPU_V5E, ForwardCostModel
    from repro.core.rollout import SeerRollout
    from repro.core.simulator import ClusterSimulator, SimConfig
    from repro.engine import EngineSeq, Instance, StepFunctions
    from repro.models import init_params

    FAMILIES = {"granite-3-8b": "dense", "mixtral-8x7b": "moe",
                "zamba2-1.2b": "hybrid"}
    TP = 2

    def drive(cfg, params, steps, tp):
        inst = Instance(cfg, params, steps, max_slots=2, cache_len=128,
                        gamma_max=4, prefill_chunk=8, base_seed=7, tp=tp)
        s0 = EngineSeq("r0", "g0", [2, 3, 4, 5, 6, 7], seed=3,
                       temperature=1.0, max_new_tokens=n_new)
        s1 = EngineSeq("r1", "g0", [5, 9, 2], seed=4, temperature=1.0,
                       max_new_tokens=n_new)
        slot0 = inst.admit(s0)
        inst.admit(s1)
        hs0 = steps.host_syncs
        it = 0
        t0 = time.perf_counter()
        while not (s0.finished and s1.finished):
            drafts = {slot0: [(s0.generated[-1] + 13) % cfg.vocab_size]
                      * 2} if (s0.generated and not s0.finished
                               and it % 2) else {}
            inst.run_step(drafts)
            it += 1
            assert it < 200
        return {
            "tokens": [list(s0.generated), list(s1.generated)],
            "engine_steps": it,
            "host_syncs": steps.host_syncs - hs0,
            "host_syncs_per_step": (steps.host_syncs - hs0) / max(it, 1),
            "wall_seconds": time.perf_counter() - t0,
        }

    archs = {}
    for arch, family in FAMILIES.items():
        cfg = get_tiny_config(arch)
        params, _ = init_params(cfg, jax.random.PRNGKey(1))
        steps = StepFunctions(cfg)
        ref = drive(cfg, params, steps, None)
        tp1 = drive(cfg, params, steps, 1)
        tp2 = drive(cfg, params, steps, TP)
        fwd1 = ForwardCostModel(cfg, TPU_V5E, tp=1)
        fwd2 = ForwardCostModel(cfg, TPU_V5E, tp=TP)
        archs[arch] = {
            "family": family,
            "tp1_bit_identical":
                tp1["tokens"] == ref["tokens"]
                and tp1["engine_steps"] == ref["engine_steps"]
                and tp1["host_syncs"] == ref["host_syncs"],
            "tp2_token_exact": tp2["tokens"] == ref["tokens"],
            "tp2_same_steps": tp2["engine_steps"] == ref["engine_steps"],
            "engine_steps": ref["engine_steps"],
            "host_syncs_per_step": {
                "oracle": ref["host_syncs_per_step"],
                "tp1": tp1["host_syncs_per_step"],
                "tp2": tp2["host_syncs_per_step"],
            },
            "wall_seconds": {"oracle": ref["wall_seconds"],
                             "tp1": tp1["wall_seconds"],
                             "tp2": tp2["wall_seconds"]},
            "collective_bytes_per_token": fwd2.collective_bytes(1),
            "modeled_step_time_s": {
                "tp1": fwd1.step_time(2, 1, 64.0),
                "tp2": fwd2.step_time(2, 1, 64.0),
            },
        }

    # sim <-> engine cost-model consistency: the rollout's per-instance
    # model (SeerRollout(tp=...)) and the simulator's (SimConfig.tp)
    # must be the same ForwardCostModel — scheduling decisions and
    # simulated timings at tp>1 then agree by construction
    cfg = get_tiny_config("granite-3-8b")
    params, _ = init_params(cfg, jax.random.PRNGKey(1))
    ro = SeerRollout(cfg, params, n_instances=1, max_slots=2,
                     cache_len=128, spec_decode=False, base_seed=7,
                     tp=TP)
    spec = dataclasses.replace(MOONLIGHT, n_requests=4, n_instances=1,
                               max_gen_length=512, mean_gen_length=128)
    sim = ClusterSimulator(cfg, spec, SimConfig(
        mode="divided", hw=TPU_V5E, chips_per_instance=1, tp=TP,
        kv_capacity_tokens=100_000))
    engine_t = ro.sd_model.fwd.step_time(2, 1, 64.0)
    sim_t = sim.fwd.step_time(2, 1, 64.0)

    moe_cb = archs["mixtral-8x7b"]["collective_bytes_per_token"]
    return {
        "workload": {"n_new": n_new, "seed": seed, "tp": TP,
                     "archs": sorted(FAMILIES)},
        "archs": archs,
        "tp1_token_exact":
            all(a["tp1_bit_identical"] for a in archs.values()),
        "tp2_token_exact":
            all(a["tp2_token_exact"] and a["tp2_same_steps"]
                for a in archs.values()),
        "moe_collective_bytes":
            moe_cb["all_gather"] + moe_cb["all_to_all"],
        "engine_step_time_s": engine_t,
        "sim_step_time_s": sim_t,
        "sim_engine_ratio": sim_t / max(engine_t, 1e-30),
    }


def bench_serving(n_groups: int = 12, group_size: int = 2,
                  prompt_len: int = 10, gen_mean: int = 10,
                  seed: int = 11) -> dict:
    """Open-loop serving benchmark: trace-driven arrivals under SLO-aware
    admission, at 1x (headroom) and 2x the measured sustainable rate.

    Phases (all deterministic — seeded arrivals, seeded prompts,
    modeled-delay shedding):

    1. *calibrate capacity*: run the same offered groups closed-loop;
       ``sustainable_rate`` = groups / ticks.  The same run doubles as a
       closed-loop-equivalence check: a t=0 trace fed through
       ``run_stream(arrivals=...)`` must reproduce the legacy fixed-list
       run bit-exactly (tokens, engine steps, host syncs).
    2. *calibrate the SLO deadline*: an open-loop run at 0.75x
       sustainable with no deadline records the modeled admission delay
       of every offer; the deadline is 1.5x the largest observed delay
       (so the 1x run never sheds, and a genuinely overloaded run must).
    3. *gated runs*: 1x (= 0.75x sustainable, with headroom) and 2x
       sustainable under that deadline, plus a repeat of the 2x run —
       shedding decisions and latency percentiles must be bit-identical
       (the overload-determinism invariant check_bench gates).
    4. *cluster scale*: the same ArrivalSpec machinery through
       ``SimConfig.arrival`` on a scaled-down Moonlight deployment —
       p50/p99/p999 in modeled seconds, shed only at 2x.
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.core.rollout import SeerRollout
    from repro.core.workload import (ArrivalFeed, ArrivalSpec,
                                     LengthSampler, PoissonArrivals,
                                     TenantSpec, TraceArrivals, serve)
    from repro.engine import StepFunctions
    from repro.models import init_params

    cfg = get_tiny_config("granite-3-8b")
    params, _ = init_params(cfg, jax.random.PRNGKey(1))
    steps = StepFunctions(cfg)
    tenants = (TenantSpec("a", weight=2.0, token_rate=120.0),
               TenantSpec("b", weight=1.0, token_rate=120.0))
    lengths = LengthSampler(prompt_len=prompt_len, gen_mean=gen_mean,
                            gen_sigma=0.0)
    chunk = 16

    def rollout() -> SeerRollout:
        return SeerRollout(cfg, params, n_instances=2, max_slots=2,
                           cache_len=128, chunk_size=chunk,
                           base_seed=0, steps=steps)

    def proc(rate: float) -> PoissonArrivals:
        return PoissonArrivals(rate, n_groups, seed=seed,
                               tenants=tenants, lengths=lengths)

    def feed_for(process, groups=None) -> ArrivalFeed:
        return ArrivalFeed(process, vocab_size=cfg.vocab_size,
                           group_size=group_size, ticks_per_second=1.0,
                           seed=seed, groups=groups)

    def build_groups(trace):
        builder = feed_for(TraceArrivals(trace))
        return [builder._build_group(a) for a in trace]

    def open_run(rate: float, deadline: Optional[float]) -> dict:
        ro = rollout()
        feed = feed_for(proc(rate))
        hs0 = steps.host_syncs
        t0 = time.perf_counter()
        rep = serve(ro, feed, slo_deadline_s=deadline)
        wall = time.perf_counter() - t0
        res = rep.pop("result")
        rep.update(
            rate_groups_per_tick=rate,
            engine_steps=res.stats.steps,
            idle_ticks=res.stats.idle_ticks,
            offer_delay_max=res.stats.offer_delay_max,
            host_syncs_per_step=(steps.host_syncs - hs0)
            / max(res.stats.steps, 1),
            wall_seconds=wall)
        return rep

    # 1) capacity calibration + closed-loop equivalence.  Lengths are
    # deterministic (no jitter/sigma), so any rate's trace offers the
    # exact same groups — the closed-loop run measures pure capacity.
    cal_trace = proc(1.0).trace()
    ro = rollout()
    hs0 = steps.host_syncs
    res_cl = ro.run(build_groups(cal_trace))
    cl_syncs = steps.host_syncs - hs0
    sustainable = n_groups / max(res_cl.stats.ticks, 1)

    t0_trace = [dataclasses.replace(a, t=0.0) for a in cal_trace]
    ro_eq = rollout()
    eq_groups = build_groups(cal_trace)
    feed_eq = feed_for(TraceArrivals(t0_trace), groups=eq_groups)
    hs0 = steps.host_syncs
    rep_eq = serve(ro_eq, feed_eq)
    res_eq = rep_eq.pop("result")
    equivalent = (res_eq.responses() == res_cl.responses()
                  and res_eq.stats.steps == res_cl.stats.steps
                  and steps.host_syncs - hs0 == cl_syncs)

    # 2) deadline calibration: deadline-free run at 1x (0.75x sustainable
    # keeps headroom — "sustainable" is measured with every group
    # available from tick 0, which a trickled arrival stream can't beat)
    rate_1x = 0.75 * sustainable
    rate_2x = 2.0 * sustainable
    ro_probe = rollout()
    floor = ro_probe._queue_cost_per_token * chunk
    cal = open_run(rate_1x, None)
    deadline = 1.5 * max(cal["offer_delay_max"], floor)

    # 3) gated runs
    one_x = open_run(rate_1x, deadline)
    two_x = open_run(rate_2x, deadline)
    two_x_rep = open_run(rate_2x, deadline)
    deterministic = (
        two_x_rep["shed_indices"] == two_x["shed_indices"]
        and two_x_rep["latency_ticks"] == two_x["latency_ticks"]
        and two_x_rep["admitted_groups"] == two_x["admitted_groups"])

    # weight-normalized per-tenant goodput spread at 1x (nothing shed,
    # so fairness is purely the arrival process's weighted draw)
    w = {ts.name: ts.weight for ts in tenants}
    norm = [pt["goodput_tokens"] / w[name]
            for name, pt in one_x["per_tenant"].items()
            if pt["arrived"] > 0]
    spread = max(norm) / max(min(norm), 1e-9) if norm else float("inf")

    # 4) cluster scale through SimConfig.arrival (divided mode)
    dep = DEPLOY["moonlight"]
    spec = dataclasses.replace(MOONLIGHT, n_requests=64, n_instances=4)
    wl = make_workload(spec, seed=seed)
    scfg = get_config(dep["cfg"])
    simbase = dict(mode="divided", policy="seer", sd="none",
                   max_slots=4, chips_per_instance=dep["chips"],
                   kv_capacity_tokens=dep["kv_tokens"])

    def sim_run(arr: Optional[ArrivalSpec]):
        sim = ClusterSimulator(scfg, spec, SimConfig(arrival=arr,
                                                     **simbase))
        return sim.run(wl)

    closed = sim_run(None)
    sus_sim = wl.n_groups / max(closed.total_time, 1e-9)
    sim_tenants = (("a", 2.0, 1e9), ("b", 1.0, 1e9))
    cal_sim = sim_run(ArrivalSpec(rate=0.75 * sus_sim, seed=seed,
                                  tenants=sim_tenants))
    sim_deadline = 1.5 * max(
        cal_sim.extras["serving"]["offer_delay_max"], 1e-9)

    def sim_serving(rate: float) -> dict:
        r = sim_run(ArrivalSpec(rate=rate, seed=seed,
                                tenants=sim_tenants,
                                slo_deadline_s=sim_deadline))
        return r.extras["serving"]

    sim_1x = sim_serving(0.75 * sus_sim)
    sim_2x = sim_serving(2.0 * sus_sim)
    sim_2x_rep = sim_serving(2.0 * sus_sim)
    sim_det = (sim_2x_rep["shed_indices"] == sim_2x["shed_indices"]
               and sim_2x_rep["latency_s"] == sim_2x["latency_s"])

    return {
        "workload": {"n_groups": n_groups, "group_size": group_size,
                     "prompt_len": prompt_len, "gen_mean": gen_mean,
                     "seed": seed, "arch": "granite-3-8b",
                     "tenants": [[ts.name, ts.weight, ts.token_rate]
                                 for ts in tenants]},
        "closed_loop": {"ticks": res_cl.stats.ticks,
                        "engine_steps": res_cl.stats.steps,
                        "tokens": res_cl.stats.tokens,
                        "host_syncs_per_step":
                            cl_syncs / max(res_cl.stats.steps, 1)},
        "closed_loop_equivalent": equivalent,
        "sustainable_rate_groups_per_tick": sustainable,
        "slo_deadline_s": deadline,
        "one_x": one_x,
        "two_x": two_x,
        "deterministic": deterministic,
        "tenant_goodput_spread": spread,
        "sim": {
            "workload": {"spec": "moonlight", "n_requests": 64,
                         "n_instances": 4, "max_slots": 4, "seed": seed},
            "sustainable_rate_groups_per_sec": sus_sim,
            "slo_deadline_s": sim_deadline,
            "one_x": sim_1x,
            "two_x": sim_2x,
            "deterministic": sim_det,
        },
    }


def bench_observability(n_groups: int = 3, group_size: int = 2,
                        max_new_tokens: int = 14, n_instances: int = 2,
                        max_slots: int = 2, chunk_size: int = 5,
                        prefill_chunk: int = 8, seed: int = 5) -> dict:
    """Flight-recorder benchmark: the tracing layer's standing
    invariants on a real-engine rollout, plus a fault+overload serving
    run's tail-latency attribution and the engine-vs-simulator schema
    match.

    Gates (scripts/check_bench.py):

    * tracing **off** is the absence of the feature: a traced run's
      tokens, engine steps and host syncs are bit-identical to an
      untraced run of the same seeded workload;
    * tracing **on** adds zero host syncs (the per-step ratio is
      unchanged — every hook records host-side metadata only);
    * span conservation: every finished request's phase spans tile its
      wall interval exactly, in ticks and in wall seconds;
    * trace determinism: two traced runs of the same (seed, config)
      record identical ticks, names and args (the engine tier's seconds
      are wall seconds, so they are left out), and the Chrome JSON
      export round-trips losslessly;
    * a seeded fault + overload serving run yields a tail attribution
      with shed requests and a nonzero ``recovery`` phase;
    * the simulator emits the same event schema (keys and phase
      vocabulary) as the engine tier.
    """
    import dataclasses as _dc
    import json as _json
    import jax
    from repro.configs import get_tiny_config
    from repro.core.faults import FaultEvent, FaultInjector
    from repro.core.request import make_groups
    from repro.core.rollout import SeerRollout
    from repro.core.workload import (LengthSampler, PoissonArrivals,
                                     TenantSpec, serve)
    from repro.engine import StepFunctions
    from repro.models import init_params
    from repro.obs import (PHASES, Tracer, tail_attribution,
                           timelines_from_events)
    from repro.obs.trace import SCHEMA_KEYS, schema_keys

    cfg = get_tiny_config("granite-3-8b")
    params, _ = init_params(cfg, jax.random.PRNGKey(1))
    steps = StepFunctions(cfg)
    plens = [6 + 4 * g for g in range(n_groups)]
    prompts = [[(7 * g + 3 * j) % (cfg.vocab_size - 2) + 1
                for j in range(plens[g])] for g in range(n_groups)]

    def make(tracer=None, injector=None, **kw):
        kwargs = dict(
            n_instances=n_instances, max_slots=max_slots,
            cache_len=max(plens) + max_new_tokens + 32,
            chunk_size=chunk_size, prefill_chunk=prefill_chunk,
            admit_into_draining=False, final_chunk_inplace=False,
            policy="seer", spec_decode=False, gamma_max=8, base_seed=7,
            fault_injector=injector, watchdog_ticks=3, fetch_retries=3,
            steps=steps, tracer=tracer)
        kwargs.update(kw)
        return SeerRollout(cfg, params, **kwargs)

    def groups():
        return make_groups(prompts, group_size=group_size,
                           max_new_tokens=max_new_tokens, seed=seed)

    def one(tracer=None):
        ro = make(tracer)
        hs0 = steps.host_syncs
        st0 = sum(i.steps_run for i in ro.instances)
        res = ro.run(groups())
        engine_steps = sum(i.steps_run for i in ro.instances) - st0
        return res, engine_steps, steps.host_syncs - hs0

    # -- trace-off bit-identity + zero extra host syncs ----------------
    res_off, steps_off, syncs_off = one()
    tr = Tracer()
    res_on, steps_on, syncs_on = one(tracer=tr)
    bit_identical = (res_off.responses() == res_on.responses()
                     and steps_off == steps_on
                     and syncs_off == syncs_on)

    # -- conservation + determinism + chrome round-trip ----------------
    evs = tr.events()
    tls = timelines_from_events(evs)
    rep = tail_attribution(tls)
    tick_tiling = all(
        sum(b - a for _, a, b in tl.segments)
        == tl.end_tick - tl.submit_tick
        for tl in tls.values() if tl.finished)
    tr2 = Tracer()
    one(tracer=tr2)

    def untimed(events):
        return [{k: v for k, v in e.items() if k not in ("t0", "t1")}
                for e in events]

    deterministic = untimed(tr2.events()) == untimed(evs)
    roundtrip = Tracer.from_chrome(
        _json.loads(_json.dumps(tr.to_chrome()))) == evs
    engine_phases = sorted({e["name"] for e in evs
                            if e["cat"] == "request" and e["ph"] == "X"})

    # -- fault + overload serving run ----------------------------------
    tenants = (TenantSpec("a", weight=2.0, token_rate=200.0),
               TenantSpec("b", weight=1.0, token_rate=200.0))
    lengths = LengthSampler(prompt_len=8, gen_mean=10, gen_sigma=0.0)

    def feed():
        from repro.core.workload import ArrivalFeed
        return ArrivalFeed(
            PoissonArrivals(0.8, 10, seed=seed, tenants=tenants,
                            lengths=lengths),
            vocab_size=cfg.vocab_size, group_size=group_size,
            ticks_per_second=1.0, seed=seed)

    probe = serve(make(), feed())
    probe_res = probe.pop("result")
    # a deadline below the probe's worst modeled delay guarantees sheds
    # on the (identical) gated arrival trace
    deadline = 0.5 * max(probe_res.stats.offer_delay_max, 1e-9)
    crash_tick = max(2, probe["elapsed_ticks"] // 3)
    inj = FaultInjector([FaultEvent(tick=crash_tick, kind="crash",
                                    instance_id="inst0")])
    tr_ov = Tracer()
    rep_ov = serve(make(tracer=tr_ov, injector=inj), feed(),
                   slo_deadline_s=deadline)
    res_ov = rep_ov.pop("result")
    tls_ov = timelines_from_events(tr_ov.events())
    attribution = tail_attribution(tls_ov)

    # -- simulator: same schema on an equivalent divided workload ------
    spec = _dc.replace(MOONLIGHT, n_requests=48, group_size=4,
                       n_instances=2, max_gen_length=8192,
                       mean_gen_length=2000)
    wl = make_workload(spec, seed=seed)
    tr_sim = Tracer()
    sim = ClusterSimulator(
        get_config("yi-6b"), spec,
        SimConfig(mode="divided", policy="seer", max_slots=16,
                  chips_per_instance=1, kv_capacity_tokens=40_000,
                  chunk_size=512, fault_rate=0.02, seed=seed),
        tracer=tr_sim)
    sim.run(wl)
    sim_evs = tr_sim.events()
    sim_tls = timelines_from_events(sim_evs)
    sim_rep = tail_attribution(sim_tls)
    sim_phases = sorted({e["name"] for e in sim_evs
                         if e["cat"] == "request" and e["ph"] == "X"})

    return {
        "workload": {
            "n_groups": n_groups, "group_size": group_size,
            "max_new_tokens": max_new_tokens,
            "n_instances": n_instances, "max_slots": max_slots,
            "chunk_size": chunk_size, "prefill_chunk": prefill_chunk,
            "seed": seed, "arch": "granite-3-8b",
        },
        "trace_off_bit_identical": bit_identical,
        "host_syncs_per_step": {
            "untraced": syncs_off / max(steps_off, 1),
            "traced": syncs_on / max(steps_on, 1),
        },
        "events": len(evs),
        "span_conservation": rep["conserved"],
        "tick_tiling_exact": tick_tiling,
        "trace_deterministic": deterministic,
        "chrome_roundtrip": roundtrip,
        "attribution": rep,
        "overload_faults": {
            "slo_deadline_s": deadline,
            "crash_tick": crash_tick,
            "shed_groups": rep_ov["shed_groups"],
            "instance_crashes": res_ov.stats.snapshot()[
                "instance_crashes"],
            "attribution": attribution,
        },
        "schema": {
            "keys": sorted(SCHEMA_KEYS),
            "engine_keys": schema_keys(evs),
            "sim_keys": schema_keys(sim_evs),
            "match": schema_keys(evs) == schema_keys(sim_evs)
            == sorted(SCHEMA_KEYS),
            "engine_phases": engine_phases,
            "sim_phases": sim_phases,
            "phases_in_vocab":
                set(engine_phases) <= set(PHASES)
                and set(sim_phases) <= set(PHASES),
        },
        "sim": {"events": len(sim_evs),
                "span_conservation": sim_rep["conserved"],
                "requests": sim_rep["requests"]},
    }


_ENGINE_ROLLOUT_CACHE: Optional[dict] = None
_ENGINE_MIGRATION_CACHE: Optional[dict] = None
_ENGINE_TOPOLOGY_CACHE: Optional[dict] = None
_ENGINE_TREE_CACHE: Optional[dict] = None
_TRAIN_OVERLAP_CACHE: Optional[dict] = None
_ENGINE_FAULTS_CACHE: Optional[dict] = None
_ENGINE_TP_CACHE: Optional[dict] = None
_SERVING_CACHE: Optional[dict] = None
_OBSERVABILITY_CACHE: Optional[dict] = None


def ensure_observability_record() -> dict:
    """Run the flight-recorder benchmark once per process and write it
    to BENCH_rollout.json's 'observability' section."""
    global _OBSERVABILITY_CACHE
    if _OBSERVABILITY_CACHE is None:
        _OBSERVABILITY_CACHE = bench_observability()
        update_bench_rollout("observability", _OBSERVABILITY_CACHE)
    return _OBSERVABILITY_CACHE


def ensure_serving_record() -> dict:
    """Run the open-loop serving benchmark once per process and write
    it to BENCH_rollout.json's 'serving' section."""
    global _SERVING_CACHE
    if _SERVING_CACHE is None:
        _SERVING_CACHE = bench_serving()
        update_bench_rollout("serving", _SERVING_CACHE)
    return _SERVING_CACHE


def ensure_engine_tp_record() -> dict:
    """Run the tensor-parallel engine benchmark once per process and
    write it to BENCH_rollout.json's 'engine_tp' section."""
    global _ENGINE_TP_CACHE
    if _ENGINE_TP_CACHE is None:
        _ENGINE_TP_CACHE = bench_engine_tp()
        update_bench_rollout("engine_tp", _ENGINE_TP_CACHE)
    return _ENGINE_TP_CACHE


def ensure_engine_faults_record() -> dict:
    """Run the fault-injection benchmark once per process and write it
    to BENCH_rollout.json's 'engine_faults' section."""
    global _ENGINE_FAULTS_CACHE
    if _ENGINE_FAULTS_CACHE is None:
        _ENGINE_FAULTS_CACHE = bench_engine_faults()
        update_bench_rollout("engine_faults", _ENGINE_FAULTS_CACHE)
    return _ENGINE_FAULTS_CACHE


def ensure_train_overlap_record() -> dict:
    """Run the train-overlap benchmark once per process and write it to
    BENCH_rollout.json's 'train_overlap' section."""
    global _TRAIN_OVERLAP_CACHE
    if _TRAIN_OVERLAP_CACHE is None:
        _TRAIN_OVERLAP_CACHE = bench_train_overlap()
        update_bench_rollout("train_overlap", _TRAIN_OVERLAP_CACHE)
    return _TRAIN_OVERLAP_CACHE


def ensure_engine_tree_record() -> dict:
    """Run the tree-speculation micro-benchmark once per process and
    write it to BENCH_rollout.json's 'engine_tree' section."""
    global _ENGINE_TREE_CACHE
    if _ENGINE_TREE_CACHE is None:
        _ENGINE_TREE_CACHE = bench_engine_tree()
        update_bench_rollout("engine_tree", _ENGINE_TREE_CACHE)
    return _ENGINE_TREE_CACHE


def ensure_engine_topology_record() -> dict:
    """Run the topology micro-benchmark once per process and write it
    to BENCH_rollout.json's 'engine_topology' section."""
    global _ENGINE_TOPOLOGY_CACHE
    if _ENGINE_TOPOLOGY_CACHE is None:
        _ENGINE_TOPOLOGY_CACHE = bench_engine_topology()
        update_bench_rollout("engine_topology", _ENGINE_TOPOLOGY_CACHE)
    return _ENGINE_TOPOLOGY_CACHE


def ensure_engine_migration_record() -> dict:
    """Run the migration micro-benchmark once per process and write it
    to BENCH_rollout.json's 'engine_migration' section."""
    global _ENGINE_MIGRATION_CACHE
    if _ENGINE_MIGRATION_CACHE is None:
        _ENGINE_MIGRATION_CACHE = bench_engine_migration()
        update_bench_rollout("engine_migration", _ENGINE_MIGRATION_CACHE)
    return _ENGINE_MIGRATION_CACHE


def ensure_engine_rollout_record() -> dict:
    """Run the engine rollout micro-benchmark once per process and write
    it to BENCH_rollout.json's 'engine' section (several benchmarks call
    this; the real-engine run is shared)."""
    global _ENGINE_ROLLOUT_CACHE
    if _ENGINE_ROLLOUT_CACHE is None:
        _ENGINE_ROLLOUT_CACHE = bench_engine_rollout()
        update_bench_rollout("engine", _ENGINE_ROLLOUT_CACHE)
    return _ENGINE_ROLLOUT_CACHE


def table(rows: List[dict], cols: List[str], title: str = "") -> str:
    out = []
    if title:
        out.append(f"== {title}")
    widths = {c: max(len(c), *(len(_fmt(r.get(c))) for r in rows))
              for c in cols}
    out.append("  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        out.append("  ".join(_fmt(r.get(c)).ljust(widths[c]) for c in cols))
    s = "\n".join(out)
    print(s, flush=True)
    return s


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        if abs(v) >= 1000:
            return f"{v:,.0f}"
        return f"{v:.3g}"
    return str(v)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(
        description="benchmark plumbing; smoke modes only — full runs "
                    "go through benchmarks.run / scripts/check_bench.py")
    ap.add_argument(
        "--faults", action="store_true",
        help="fault-injection smoke: run bench_engine_faults once, "
             "print the recovery summary, exit nonzero unless recovery "
             "was token-lossless (does NOT write the bench baseline)")
    ap.add_argument(
        "--serving", action="store_true",
        help="open-loop serving smoke: run bench_serving once, print "
             "latency/goodput tables at 1x and 2x the sustainable rate, "
             "exit nonzero unless shedding is SLO-shaped and "
             "deterministic (does NOT write the bench baseline)")
    ap.add_argument(
        "--trace", action="store_true",
        help="flight-recorder smoke: run bench_observability once, "
             "print the tail-attribution table, exit nonzero unless "
             "tracing is bit-transparent (tokens/steps/host-syncs), "
             "spans conserve, traces are deterministic and engine/sim "
             "emit the same schema (does NOT write the bench baseline)")
    ap.add_argument(
        "--tp", action="store_true",
        help="tensor-parallel smoke: run bench_engine_tp once, print "
             "per-arch exactness + host-sync + collective summaries, "
             "exit nonzero unless tp=1 is bit-identical and tp=2 is "
             "token-exact (does NOT write the bench baseline)")
    ns = ap.parse_args()
    if ns.trace:
        from repro.obs import format_attribution
        rec = bench_observability()
        ov = rec["overload_faults"]
        print("== tail attribution (fault + overload serving run)",
              flush=True)
        print(format_attribution(ov["attribution"]), flush=True)
        table([{
            "bit_identical": rec["trace_off_bit_identical"],
            "syncs_untraced": rec["host_syncs_per_step"]["untraced"],
            "syncs_traced": rec["host_syncs_per_step"]["traced"],
            "conserved": rec["span_conservation"],
            "tick_exact": rec["tick_tiling_exact"],
            "deterministic": rec["trace_deterministic"],
            "schema_match": rec["schema"]["match"],
        }], ["bit_identical", "syncs_untraced", "syncs_traced",
             "conserved", "tick_exact", "deterministic",
             "schema_match"], title="flight-recorder invariants")
        ok = (rec["trace_off_bit_identical"]
              and rec["host_syncs_per_step"]["traced"]
              == rec["host_syncs_per_step"]["untraced"]
              and rec["span_conservation"]
              and rec["tick_tiling_exact"]
              and rec["trace_deterministic"]
              and rec["chrome_roundtrip"]
              and rec["schema"]["match"]
              and rec["schema"]["phases_in_vocab"]
              and rec["sim"]["span_conservation"]
              and ov["attribution"]["conserved"]
              and ov["shed_groups"] > 0
              and ov["attribution"]["phase_totals_s"].get(
                  "recovery", 0.0) > 0.0)
        print("trace smoke:", "PASS" if ok else "FAIL", flush=True)
        raise SystemExit(0 if ok else 1)
    if ns.serving:
        rec = bench_serving()
        rows = []
        for name in ("one_x", "two_x"):
            r = rec[name]
            rows.append(dict(
                rate=name, offered=r["offered_groups"],
                shed=r["shed_groups"],
                p50=r["latency_ticks"]["p50"],
                p99=r["latency_ticks"]["p99"],
                p999=r["latency_ticks"]["p999"],
                goodput=round(r["goodput_tokens_per_tick"], 3),
                q_peak=r["queue_depth_peak"],
                syncs=r["host_syncs_per_step"]))
        table(rows, ["rate", "offered", "shed", "p50", "p99", "p999",
                     "goodput", "q_peak", "syncs"],
              title="engine serving smoke (open-loop arrivals)")
        srows = []
        for name in ("one_x", "two_x"):
            r = rec["sim"][name]
            srows.append(dict(
                rate=name, offered=r["offered_groups"],
                shed=r["shed_groups"],
                p50_s=round(r["latency_s"]["p50"], 2),
                p99_s=round(r["latency_s"]["p99"], 2),
                goodput=round(r["goodput_tokens_per_sec"], 1),
                q_peak=r["queue_depth_peak"]))
        table(srows, ["rate", "offered", "shed", "p50_s", "p99_s",
                      "goodput", "q_peak"],
              title="simulator serving smoke (moonlight, tight slots)")
        two = rec["two_x"]
        ok = (rec["closed_loop_equivalent"]
              and rec["deterministic"]
              and rec["sim"]["deterministic"]
              and rec["one_x"]["shed_groups"] == 0
              and two["shed_groups"] > 0
              and two["latency_ticks"]["p99"] < float("inf")
              and rec["sim"]["one_x"]["shed_groups"] == 0
              and rec["sim"]["two_x"]["shed_groups"] > 0)
        print("closed-loop equivalent:",
              rec["closed_loop_equivalent"], flush=True)
        print("serving smoke:", "PASS" if ok else "FAIL", flush=True)
        raise SystemExit(0 if ok else 1)
    if ns.tp:
        rec = bench_engine_tp()
        table([
            dict(arch=a, family=r["family"],
                 tp1_bit_identical=r["tp1_bit_identical"],
                 tp2_token_exact=r["tp2_token_exact"],
                 syncs_tp2=r["host_syncs_per_step"]["tp2"],
                 ag_bytes=r["collective_bytes_per_token"]["all_gather"],
                 a2a_bytes=r["collective_bytes_per_token"]["all_to_all"])
            for a, r in rec["archs"].items()
        ], ["arch", "family", "tp1_bit_identical", "tp2_token_exact",
            "syncs_tp2", "ag_bytes", "a2a_bytes"],
            title="engine_tp smoke (tp=2 vs 1-chip oracle)")
        print("sim/engine step-time ratio:",
              f"{rec['sim_engine_ratio']:.6f}", flush=True)
        ok = rec["tp1_token_exact"] and rec["tp2_token_exact"] and \
            abs(rec["sim_engine_ratio"] - 1.0) < 1e-9
        print("tp exactness:", "PASS" if ok else "FAIL", flush=True)
        raise SystemExit(0 if ok else 1)
    if ns.faults:
        rec = bench_engine_faults()
        f = rec["faulted"]
        table([
            dict(run="oracle", **{k: rec["oracle"][k] for k in
                 ("engine_steps", "ticks", "host_syncs_per_step")}),
            dict(run="faulted", **{k: f[k] for k in
                 ("engine_steps", "ticks", "host_syncs_per_step")}),
        ], ["run", "engine_steps", "ticks", "host_syncs_per_step"],
            title="engine_faults smoke")
        table([{
            "crashes": f["instance_crashes"],
            "escalations": f["watchdog_escalations"],
            "rec_blob": f["recovered_via_blob"],
            "rec_replay": f["recovered_via_replay"],
            "fetch_degraded": f["fetch_degraded"],
            "corrupt": f["corrupt_blobs"],
            "tokens_lost": rec["tokens_lost"],
            "overhead": rec["recovery_overhead_ratio"],
        }], ["crashes", "escalations", "rec_blob", "rec_replay",
             "fetch_degraded", "corrupt", "tokens_lost", "overhead"],
            title="recovery")
        ok = rec["token_exact"] and rec["tokens_lost"] == 0
        print("token-lossless:", "PASS" if ok else "FAIL", flush=True)
        raise SystemExit(0 if ok else 1)
    ap.print_help()
