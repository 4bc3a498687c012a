"""Guard the rollout hot-path perf trajectory.

Runs the real-engine admission micro-benchmark fresh (or loads a fresh
``BENCH_rollout.json`` via ``--fresh``) and diffs its ``engine`` section
against the committed baseline in ``results/bench/BENCH_rollout.json``:

* the batched path must stay token-exact vs the sync reference,
* engine forward launches must not regress (fresh <= baseline + slack),
* the fused device step must keep <= 1 host sync per ``run_step``,
* cache-buffer donation must fire (no per-step full-cache copy) on
  backends that support it,
* tokens/s must stay within ``--min-tokens-ratio`` of the baseline
  (loose by default: wall-clock on shared CI boxes is noisy).

It also runs the migration-heavy micro-benchmark and diffs the
``engine_migration`` section: batched migration must stay token-exact
vs the sync and per-slot paths, issue fewer device calls per migrated
slot than the per-slot (PR 2) baseline measured in the same run, keep
that figure at or under the committed baseline, spend less host time
stalled on migration than the per-slot path, and dispatch a nonzero
fraction of exports inside the overlap window.

And the cross-node topology micro-benchmark (``engine_topology``
section): all divided-mode paths must stay token-exact vs the sync
oracle, cross-node migration must actually be charged on the 2-node
layout (cross_node_bytes > 0 under topology-blind placement), and
topology-aware placement must move strictly fewer fabric bytes than
topology-blind placement (and no more than the committed baseline,
with slack).

And the tree-speculation micro-benchmark (``engine_tree`` section):
tree mode with a single path must run the exact same steps and commit
the exact same tokens as the linear verify path; on the grouped CST
workload, multi-path token trees must accept strictly more tokens per
forward than linear at the same per-request draft budget, with
branching nodes actually verified, <= 1 host sync per step, and the
uplift ratio no worse than the committed baseline (with slack).

And the fault-injection benchmark (``engine_faults`` section): under a
deterministic schedule of instance crashes, stalls (one escalated by
the watchdog), fetch failures and a corrupted blob, recovery must be
**token-lossless** (every response bit-identical to the no-fault
oracle, ``tokens_lost == 0``), every recovery path must actually fire
(blob resume, rewind+replay, retry-degrade, checksum catch), recovery
overhead must stay under 2x the faulted requests' remaining decode
budget, and the 1-host-sync-per-step contract must hold under faults.

And the open-loop serving benchmark (``serving`` section): with seeded
Poisson arrivals feeding the stream loop, the arrivals-at-t0 path must
reproduce the legacy fixed-list run exactly; at the sustainable rate
nothing is shed, at 2x overload the SLO-aware admission sheds
some-but-not-all groups with finite p50/p99/p999 tail latency and
nonzero goodput; shedding must be bit-deterministic across repeat runs
(a pure function of seed + config), weight-normalized per-tenant
goodput spread must stay bounded, and the <=1-host-sync-per-step
contract must hold under open-loop arrivals.  The simulator mirror
must show the same overload shape deterministically.

Exit status 0 iff every check passes — invoked from the verify skill so
perf regressions fail tier-1 review, not just eyeballs.

Usage::

    PYTHONPATH=src python scripts/check_bench.py [--baseline PATH]
        [--fresh PATH] [--min-tokens-ratio 0.5] [--fwd-slack 0]
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _section(path: str, name: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if name not in doc:
        raise SystemExit(f"{path}: no {name!r} section")
    return doc[name]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline",
                    default=os.path.join("results", "bench",
                                         "BENCH_rollout.json"))
    ap.add_argument("--fresh", default=None,
                    help="path to a freshly produced BENCH_rollout.json; "
                         "omitted -> run the engine micro-benchmark now")
    ap.add_argument("--min-tokens-ratio", type=float, default=0.35,
                    help="fresh batched tokens/s must be >= this fraction "
                         "of the committed baseline (identical code "
                         "measures up to ~2.5x apart on a shared box "
                         "depending on load; the gate catches "
                         "order-of-magnitude regressions, the launch "
                         "counters catch the rest deterministically)")
    ap.add_argument("--fwd-slack", type=int, default=0,
                    help="allowed extra forward launches vs baseline")
    ap.add_argument("--cross-bytes-slack", type=float, default=1.25,
                    help="fresh topology-aware cross-node bytes must be "
                         "<= this multiple of the committed baseline")
    ap.add_argument("--tree-ratio-slack", type=float, default=0.9,
                    help="fresh tree accepted-per-step ratio (tree vs "
                         "linear) must be >= this fraction of the "
                         "committed baseline's ratio")
    ap.add_argument("--mig-stall-ratio", type=float, default=1.0,
                    help="fresh batched migration stall seconds must be "
                         "<= this fraction of the same run's per-slot "
                         "path")
    ap.add_argument("--tenant-spread", type=float, default=4.0,
                    help="weight-normalized per-tenant goodput spread "
                         "(max/min) at the sustainable rate must be <= "
                         "this bound")
    ap.add_argument("--recovery-overhead", type=float, default=2.0,
                    help="faulted-run extra engine steps must be <= this "
                         "multiple of the faulted requests' remaining "
                         "decode budget at crash time")
    args = ap.parse_args(argv)

    base = _section(args.baseline, "engine")
    base_mig = _section(args.baseline, "engine_migration")
    base_topo = _section(args.baseline, "engine_topology")
    base_tree = _section(args.baseline, "engine_tree")
    base_ovl = _section(args.baseline, "train_overlap")
    base_flt = _section(args.baseline, "engine_faults")
    base_tp = _section(args.baseline, "engine_tp")
    base_srv = _section(args.baseline, "serving")
    base_obs = _section(args.baseline, "observability")
    if args.fresh:
        fresh = _section(args.fresh, "engine")
        fresh_mig = _section(args.fresh, "engine_migration")
        fresh_topo = _section(args.fresh, "engine_topology")
        fresh_tree = _section(args.fresh, "engine_tree")
        fresh_ovl = _section(args.fresh, "train_overlap")
        fresh_flt = _section(args.fresh, "engine_faults")
        fresh_tp = _section(args.fresh, "engine_tp")
        fresh_srv = _section(args.fresh, "serving")
        fresh_obs = _section(args.fresh, "observability")
    else:
        # the benchmarks package lives at the repo root, one level up
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from benchmarks.common import (bench_engine_faults,
                                       bench_engine_migration,
                                       bench_engine_rollout,
                                       bench_engine_topology,
                                       bench_engine_tp,
                                       bench_engine_tree,
                                       bench_observability,
                                       bench_serving,
                                       bench_train_overlap)
        fresh = bench_engine_rollout()
        fresh_mig = bench_engine_migration()
        fresh_topo = bench_engine_topology()
        fresh_tree = bench_engine_tree()
        fresh_ovl = bench_train_overlap()
        fresh_flt = bench_engine_faults()
        fresh_tp = bench_engine_tp()
        fresh_srv = bench_serving()
        fresh_obs = bench_observability()

    if fresh.get("workload") != base.get("workload"):
        print("[check_bench] FAIL workload mismatch: fresh "
              f"{fresh.get('workload')} vs baseline {base.get('workload')} "
              "— numbers are not comparable")
        return 1

    fb, bb = fresh["batched"], base["batched"]
    checks = [
        ("token_exact", fresh.get("token_exact") is True,
         f"batched vs sync token-exact: {fresh.get('token_exact')}"),
        ("forward_invocations",
         fb["forward_invocations"]
         <= bb["forward_invocations"] + args.fwd_slack,
         f"{fb['forward_invocations']} <= "
         f"{bb['forward_invocations']} + {args.fwd_slack}"),
        ("host_syncs_per_step",
         fb.get("host_syncs_per_step", float("inf")) <= 1.0 + 1e-9,
         f"{fb.get('host_syncs_per_step')} <= 1"),
        ("cache_donated",
         fresh.get("cache_donated", False) or not _donation_supported(),
         f"donation fired: {fresh.get('cache_donated')}"),
        ("tokens_per_sec",
         fb["tokens_per_sec"]
         >= args.min_tokens_ratio * bb["tokens_per_sec"],
         f"{fb['tokens_per_sec']:.1f} >= {args.min_tokens_ratio} * "
         f"{bb['tokens_per_sec']:.1f}"),
    ]
    checks += _migration_checks(fresh_mig, base_mig, args)
    checks += _topology_checks(fresh_topo, base_topo, args)
    checks += _tree_checks(fresh_tree, base_tree, args)
    checks += _train_overlap_checks(fresh_ovl, base_ovl, args)
    checks += _fault_checks(fresh_flt, base_flt, args)
    checks += _tp_checks(fresh_tp, base_tp, args)
    checks += _serving_checks(fresh_srv, base_srv, args)
    checks += _observability_checks(fresh_obs, base_obs, args)
    ok = True
    for name, passed, detail in checks:
        status = "ok  " if passed else "FAIL"
        print(f"[check_bench] {status} {name}: {detail}")
        ok &= passed
    if not ok:
        print("[check_bench] rollout hot-path perf regressed vs "
              f"{args.baseline}")
    return 0 if ok else 1


def _migration_checks(fresh: dict, base: dict, args) -> list:
    """Gates on the migration-heavy micro-benchmark.

    The launch/stall comparisons run against the *same-run* per-slot
    path (apples-to-apples on this box); the committed baseline guards
    the batched path's launch count across PRs."""
    if fresh.get("workload") != base.get("workload"):
        return [("migration_workload", False,
                 f"fresh {fresh.get('workload')} vs baseline "
                 f"{base.get('workload')} — numbers are not comparable")]
    fb, fp = fresh["batched"], fresh["perslot"]
    bb = base["batched"]
    return [
        ("migration_token_exact", fresh.get("token_exact") is True,
         "batched vs perslot vs sync token-exact: "
         f"{fresh.get('token_exact')}"),
        ("migration_calls_per_slot",
         fb["device_calls_per_migrated_slot"]
         < fp["device_calls_per_migrated_slot"],
         f"batched {fb['device_calls_per_migrated_slot']:.2f} < "
         f"perslot {fp['device_calls_per_migrated_slot']:.2f}"),
        ("migration_calls_vs_baseline",
         fb["device_calls_per_migrated_slot"]
         <= bb["device_calls_per_migrated_slot"] + 1e-9,
         f"{fb['device_calls_per_migrated_slot']:.2f} <= "
         f"{bb['device_calls_per_migrated_slot']:.2f}"),
        ("migration_stall_seconds",
         fb["migration_stall_seconds"]
         <= args.mig_stall_ratio * fp["migration_stall_seconds"],
         f"batched {fb['migration_stall_seconds']:.4f}s <= "
         f"{args.mig_stall_ratio} * perslot "
         f"{fp['migration_stall_seconds']:.4f}s"),
        ("export_overlap_fraction",
         fb["export_overlap_fraction"] > 0.0,
         f"{fb['export_overlap_fraction']:.2f} > 0"),
    ]


def _topology_checks(fresh: dict, base: dict, args) -> list:
    """Gates on the cross-node topology micro-benchmark.

    Blind-vs-aware comparisons run within the same fresh run (identical
    box and workload); the committed baseline bounds the aware path's
    fabric traffic across PRs (scheduling is deterministic, so a real
    regression shows up as a byte-count jump, not noise)."""
    if fresh.get("workload") != base.get("workload"):
        return [("topology_workload", False,
                 f"fresh {fresh.get('workload')} vs baseline "
                 f"{base.get('workload')} — numbers are not comparable")]
    fa, fb = fresh["aware"], fresh["blind"]
    ba = base["aware"]
    return [
        ("topology_token_exact", fresh.get("token_exact") is True,
         "aware vs blind vs sync token-exact: "
         f"{fresh.get('token_exact')}"),
        ("cross_node_charged", fb["cross_node_bytes"] > 0,
         f"blind cross_node_bytes {fb['cross_node_bytes']} > 0 "
         "(2-node layout actually pays the fabric)"),
        ("topology_aware_reduces_cross_bytes",
         fa["cross_node_bytes"] < fb["cross_node_bytes"],
         f"aware {fa['cross_node_bytes']} < blind "
         f"{fb['cross_node_bytes']}"),
        ("cross_bytes_vs_baseline",
         fa["cross_node_bytes"]
         <= args.cross_bytes_slack * ba["cross_node_bytes"],
         f"aware {fa['cross_node_bytes']} <= {args.cross_bytes_slack} * "
         f"baseline {ba['cross_node_bytes']}"),
    ]


def _tree_checks(fresh: dict, base: dict, args) -> list:
    """Gates on the tree-speculation micro-benchmark.

    The tree-vs-linear comparisons run within the same fresh run
    (identical box, identical MBA draft budget per request); the
    committed baseline bounds the accepted-per-step uplift across PRs
    (the rollout is deterministic, so a regression shows up as a ratio
    drop, not noise)."""
    if fresh.get("workload") != base.get("workload"):
        return [("tree_workload", False,
                 f"fresh {fresh.get('workload')} vs baseline "
                 f"{base.get('workload')} — numbers are not comparable")]
    fl, f1, ft = fresh["linear"], fresh["tree_top1"], fresh["tree"]
    return [
        ("tree_token_exact", fresh.get("token_exact") is True,
         "linear vs tree_top1 vs tree token-exact: "
         f"{fresh.get('token_exact')}"),
        ("tree_top1_identical_steps",
         f1["engine_steps"] == fl["engine_steps"]
         and f1["accepted"] == fl["accepted"],
         f"tree_top1 ({f1['engine_steps']} steps, {f1['accepted']} acc)"
         f" == linear ({fl['engine_steps']}, {fl['accepted']})"),
        ("tree_accepts_more_per_step",
         ft["accepted_per_step"] > fl["accepted_per_step"],
         f"tree {ft['accepted_per_step']:.3f} > linear "
         f"{fl['accepted_per_step']:.3f} (equal per-request budget)"),
        ("tree_branches_verified", ft["tree_branch_nodes"] > 0,
         f"branch nodes {ft['tree_branch_nodes']} > 0"),
        ("tree_host_syncs_per_step",
         ft.get("host_syncs_per_step", float("inf")) <= 1.0 + 1e-9,
         f"{ft.get('host_syncs_per_step')} <= 1"),
        ("tree_ratio_vs_baseline",
         fresh["accepted_per_step_ratio"]
         >= args.tree_ratio_slack * base["accepted_per_step_ratio"],
         f"{fresh['accepted_per_step_ratio']:.3f} >= "
         f"{args.tree_ratio_slack} * "
         f"{base['accepted_per_step_ratio']:.3f}"),
    ]


def _train_overlap_checks(fresh: dict, base: dict, args) -> list:
    """Gates on the bounded-staleness train-overlap benchmark.

    The streaming loop at staleness_bound=0 must reproduce the sync
    barrier loop token- and loss-exactly (the standing oracle); at
    bound 1 the stream must actually reclaim barrier-stall work
    (next-iteration rows packed into tail bubbles, simulator stall
    seconds recovered) while honoring the 1-host-sync contract and the
    staleness bound the ledger enforces."""
    if fresh.get("workload") != base.get("workload"):
        return [("train_overlap_workload", False,
                 f"fresh {fresh.get('workload')} vs baseline "
                 f"{base.get('workload')} — numbers are not comparable")]
    s1 = fresh["stream_s1"]
    ovl = fresh["overlap"]
    sim = fresh["sim_barrier"]
    return [
        ("staleness0_token_exact",
         fresh.get("staleness0_token_exact") is True,
         "stream bound-0 vs sync token+loss exact: "
         f"{fresh.get('staleness0_token_exact')}"),
        ("overlap_reclaims_rows",
         ovl["reclaimed_rows"] > 0 and ovl["overlap_steps"] > 0,
         f"reclaimed rows {ovl['reclaimed_rows']} > 0 in "
         f"{ovl['overlap_steps']} overlap steps"),
        ("barrier_stall_reclaimed",
         sim["barrier_stall_reclaimed"] > 0.0,
         f"sim reclaimed {sim['barrier_stall_reclaimed']:.3f}s > 0 "
         f"(of {sim['barrier_stall_seconds']:.3f}s stall)"),
        ("overlap_host_syncs_per_step",
         s1.get("host_syncs_per_step", float("inf")) <= 1.0 + 1e-9,
         f"{s1.get('host_syncs_per_step')} <= 1"),
        ("staleness_bound_held",
         s1["max_staleness"] <= 1,
         f"max trained-token staleness {s1['max_staleness']} <= 1"),
    ]


def _fault_checks(fresh: dict, base: dict, args) -> list:
    """Gates on the fault-injection benchmark.

    Token-losslessness and path coverage are absolute properties of the
    fresh run (the fault schedule is deterministic, so "did the
    watchdog fire" is a yes/no fact, not a measurement); the committed
    baseline pins the workload shape so the numbers stay comparable
    across PRs."""
    if fresh.get("workload") != base.get("workload"):
        return [("faults_workload", False,
                 f"fresh {fresh.get('workload')} vs baseline "
                 f"{base.get('workload')} — numbers are not comparable")]
    f = fresh["faulted"]
    sim = fresh["sim_faults"]
    return [
        ("faults_token_exact", fresh.get("token_exact") is True,
         "faulted vs no-fault oracle token-exact: "
         f"{fresh.get('token_exact')}"),
        ("faults_tokens_lost", fresh.get("tokens_lost") == 0,
         f"tokens lost to faults: {fresh.get('tokens_lost')} == 0"),
        ("faults_recovery_exercised",
         f["instance_crashes"] > 0 and f["watchdog_escalations"] > 0
         and f["recovered_via_blob"] > 0
         and f["recovered_via_replay"] > 0
         and f["fetch_degraded"] > 0 and f["corrupt_blobs"] > 0,
         f"crashes {f['instance_crashes']}, escalations "
         f"{f['watchdog_escalations']}, blob {f['recovered_via_blob']}, "
         f"replay {f['recovered_via_replay']}, degraded "
         f"{f['fetch_degraded']}, corrupt {f['corrupt_blobs']} all > 0"),
        ("faults_recovery_overhead",
         fresh["recovery_extra_steps"]
         <= args.recovery_overhead
         * max(f["faulted_remaining_tokens"], 1),
         f"{fresh['recovery_extra_steps']} extra steps <= "
         f"{args.recovery_overhead} * {f['faulted_remaining_tokens']} "
         "remaining tokens"),
        ("faults_host_syncs_per_step",
         f.get("host_syncs_per_step", float("inf")) <= 1.0 + 1e-9,
         f"{f.get('host_syncs_per_step')} <= 1 (under faults)"),
        ("faults_sim_overhead_charged",
         sim["fault_events"] > 0 and sim["fault_overhead_frac"] > 0.0,
         f"sim fault events {sim['fault_events']} > 0, overhead frac "
         f"{sim['fault_overhead_frac']:.4f} > 0"),
    ]


def _tp_checks(fresh: dict, base: dict, args) -> list:
    """Gates on the tensor-parallel engine benchmark.

    Exactness is an absolute property of the fresh run: tp=1 must be
    bit-identical to the unmeshed 1-chip oracle (tokens, steps AND
    host-sync count) and tp=2 must commit exactly the oracle's tokens
    on every arch family, with the <=1-host-sync-per-step contract
    intact under sharding.  The MoE path must model nonzero collective
    bytes (the all-to-all term exists), and the simulator's cost model
    must agree with the engine rollout's at the same tp degree."""
    if fresh.get("workload") != base.get("workload"):
        return [("tp_workload", False,
                 f"fresh {fresh.get('workload')} vs baseline "
                 f"{base.get('workload')} — numbers are not comparable")]
    archs = fresh["archs"]
    worst_sync = max(a["host_syncs_per_step"]["tp2"]
                     for a in archs.values())
    moe = next(a for a in archs.values() if a["family"] == "moe")
    a2a = moe["collective_bytes_per_token"]["all_to_all"]
    ratio = fresh["sim_engine_ratio"]
    return [
        ("tp1_token_exact", fresh.get("tp1_token_exact") is True,
         "tp=1 bit-identical to 1-chip oracle on " +
         ", ".join(f"{a}({r['family']}): {r['tp1_bit_identical']}"
                   for a, r in archs.items())),
        ("tp2_token_exact", fresh.get("tp2_token_exact") is True,
         "tp=2 token-exact (same tokens, same steps) on " +
         ", ".join(f"{a}: {r['tp2_token_exact']}"
                   for a, r in archs.items())),
        ("tp_host_syncs_per_step", worst_sync <= 1.0 + 1e-9,
         f"worst tp=2 host syncs/step {worst_sync} <= 1"),
        ("tp_moe_collective_bytes", a2a > 0,
         f"MoE all-to-all bytes/token {a2a} > 0 at tp=2"),
        ("tp_sim_engine_consistency", abs(ratio - 1.0) <= 1e-9,
         f"sim/engine modeled step-time ratio {ratio:.9f} == 1"),
    ]


def _serving_checks(fresh: dict, base: dict, args) -> list:
    """Gates on the open-loop serving benchmark.

    Shedding decisions are a pure function of (seed, config) — the
    benchmark repeats the 2x-overload run and demands bit-identical
    shed indices and latencies, so determinism is a yes/no fact of the
    fresh run.  The SLO deadline is self-calibrated from a deadline-
    free run at the sustainable rate, so the graceful-overload shape
    (admit everything at 1x, shed some-but-not-all at 2x with finite
    tail latency) holds across boxes; the committed baseline pins the
    workload so the numbers stay comparable across PRs."""
    if fresh.get("workload") != base.get("workload"):
        return [("serving_workload", False,
                 f"fresh {fresh.get('workload')} vs baseline "
                 f"{base.get('workload')} — numbers are not comparable")]
    one, two = fresh["one_x"], fresh["two_x"]
    lat2 = two["latency_ticks"]
    s1, s2 = fresh["sim"]["one_x"], fresh["sim"]["two_x"]
    worst_sync = max(one["host_syncs_per_step"],
                     two["host_syncs_per_step"])
    return [
        ("serving_closed_loop_equivalent",
         fresh.get("closed_loop_equivalent") is True,
         "arrivals-at-t0 stream == legacy fixed-list run (tokens, "
         f"steps, host syncs): {fresh.get('closed_loop_equivalent')}"),
        ("serving_shed_only_when_overloaded",
         one["shed_groups"] == 0 and two["shed_groups"] > 0,
         f"1x shed {one['shed_groups']} == 0, 2x shed "
         f"{two['shed_groups']} > 0"),
        ("serving_p99_finite_under_overload",
         0.0 < lat2["p50"] <= lat2["p99"] <= lat2["p999"] < float("inf"),
         f"2x latency ticks p50 {lat2['p50']} <= p99 {lat2['p99']} <= "
         f"p999 {lat2['p999']} all finite"),
        ("serving_goodput_under_overload",
         two["goodput_tokens_per_tick"] > 0.0,
         f"2x goodput {two['goodput_tokens_per_tick']:.3f} tok/tick "
         "> 0 (graceful, not collapsed)"),
        ("serving_deterministic", fresh.get("deterministic") is True,
         "repeat 2x run bit-identical (shed indices, latencies, "
         f"admits): {fresh.get('deterministic')}"),
        ("serving_tenant_goodput_spread",
         fresh["tenant_goodput_spread"] <= args.tenant_spread,
         f"weight-normalized spread {fresh['tenant_goodput_spread']:.2f}"
         f" <= {args.tenant_spread}"),
        ("serving_host_syncs_per_step", worst_sync <= 1.0 + 1e-9,
         f"worst open-loop host syncs/step {worst_sync} <= 1"),
        ("serving_sim_overload_shape",
         s1["shed_groups"] == 0 and s2["shed_groups"] > 0
         and s2["latency_s"]["p99"] < float("inf"),
         f"sim 1x shed {s1['shed_groups']} == 0, 2x shed "
         f"{s2['shed_groups']} > 0, 2x p99 "
         f"{s2['latency_s']['p99']:.2f}s finite"),
        ("serving_sim_deterministic",
         fresh["sim"].get("deterministic") is True,
         "sim repeat 2x run bit-identical: "
         f"{fresh['sim'].get('deterministic')}"),
    ]


def _observability_checks(fresh: dict, base: dict, args) -> list:
    """Gates on the flight-recorder benchmark.

    Tracing is pure observation: a traced run must be bit-identical to
    an untraced one (tokens, engine steps, host syncs), and attaching
    the tracer must not change the host-syncs-per-step ratio — every
    hook records host-side metadata the rollout already holds.  The
    trace's ticks, names and args are a pure function of (seed,
    config): two traced runs record them identically (their wall
    seconds differ) and the Chrome export round-trips losslessly.
    Span conservation (phase spans tile each finished request's wall
    interval exactly) is what makes tail attribution trustworthy, and
    the seeded fault+overload run must actually produce a tail to
    attribute: shed requests and a nonzero recovery phase.  Engine and
    simulator tiers must emit the same event schema so one report tool
    reads both."""
    if fresh.get("workload") != base.get("workload"):
        return [("obs_workload", False,
                 f"fresh {fresh.get('workload')} vs baseline "
                 f"{base.get('workload')} — numbers are not comparable")]
    hs = fresh["host_syncs_per_step"]
    ov = fresh["overload_faults"]
    recovery_s = ov["attribution"]["phase_totals_s"].get("recovery", 0.0)
    schema = fresh["schema"]
    return [
        ("obs_trace_off_bit_identical",
         fresh.get("trace_off_bit_identical") is True,
         "traced run == untraced run (tokens, steps, host syncs): "
         f"{fresh.get('trace_off_bit_identical')}"),
        ("obs_zero_extra_host_syncs",
         hs["traced"] == hs["untraced"] and hs["traced"] <= 1.0 + 1e-9,
         f"host syncs/step traced {hs['traced']} == untraced "
         f"{hs['untraced']} <= 1"),
        ("obs_span_conservation",
         fresh.get("span_conservation") is True
         and fresh.get("tick_tiling_exact") is True,
         "phase spans tile wall intervals (seconds and ticks): "
         f"{fresh.get('span_conservation')}, "
         f"{fresh.get('tick_tiling_exact')}"),
        ("obs_trace_deterministic",
         fresh.get("trace_deterministic") is True
         and fresh.get("chrome_roundtrip") is True,
         "repeat run identical in ticks, names and args, and Chrome "
         "JSON round-trips: "
         f"{fresh.get('trace_deterministic')}, "
         f"{fresh.get('chrome_roundtrip')}"),
        ("obs_overload_attribution",
         ov["attribution"]["conserved"] and ov["shed_groups"] > 0
         and ov["instance_crashes"] > 0 and recovery_s > 0.0,
         f"fault+overload run: shed {ov['shed_groups']} > 0, crashes "
         f"{ov['instance_crashes']} > 0, recovery {recovery_s:.4f}s > 0, "
         f"conserved {ov['attribution']['conserved']}"),
        ("obs_schema_match",
         schema["match"] is True and schema["phases_in_vocab"] is True,
         "engine and sim emit the same event keys and in-vocab phases: "
         f"match={schema['match']}, "
         f"phases_in_vocab={schema['phases_in_vocab']}"),
        ("obs_sim_span_conservation",
         fresh["sim"]["span_conservation"] is True,
         f"sim conservation over {fresh['sim']['requests']} requests: "
         f"{fresh['sim']['span_conservation']}"),
    ]


def _donation_supported() -> bool:
    from repro.engine import donation_supported
    return donation_supported()


if __name__ == "__main__":
    sys.exit(main())
