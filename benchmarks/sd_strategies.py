"""Fig. 11: throughput and mean acceptance length of SD strategies.

All strategies run on the same divided+context scheduling substrate so the
comparison isolates the decoding mechanism, mirroring the paper's ablation
(single rollout iteration).  Strategies: none, SuffixDecoding (per-request
CST, γ=16), Seer grouped CST (adaptive MBA, γ_max=8), grouped+multipath
(k=4), grouped+tree (multi-path drafts verified as one token tree —
equal draft budget, branch rescues), dedicated 7B draft model (γ=3),
MTP (γ=1).  Paper: grouped SD wins throughput everywhere (up to 1.3×
over the best vanilla SD); grouped CST beats per-request CST acceptance
by ~+0.22; the draft model has the best acceptance but the worst
throughput (draft overhead).

The real-engine tree-verification micro-benchmark
(``bench_engine_tree``) also runs here so BENCH_rollout.json carries
its ``engine_tree`` section next to the simulated strategy sweep.
"""
from __future__ import annotations

from benchmarks.common import (ensure_engine_tree_record, run_sim,
                               save_result, table, workload)

STRATEGIES = [
    ("No SD", "none"),
    ("Suffix (per-req CST)", "suffix"),
    ("Draft model 7B", "draft_model"),
    ("MTP", "mtp"),
    ("Grouped (Seer)", "grouped"),
    ("Grouped+multipath", "grouped+multipath"),
    ("Grouped+tree", "grouped+tree"),
]


def run(workloads=("moonlight", "qwen2-vl-72b", "kimi-k2"), seed=0):
    rows, record = [], {}
    for w in workloads:
        wl = workload(w, seed=seed)
        res = {}
        for label, sd in STRATEGIES:
            res[label] = run_sim(w, wl, mode="divided", policy="seer",
                                 sd=sd)
        base = res["No SD"].tokens_per_sec
        for label, _ in STRATEGIES:
            r = res[label]
            rows.append({
                "workload": w, "strategy": label,
                "norm_thpt": r.tokens_per_sec / base,
                "acc_len": r.mean_acceptance_len,
            })
        best_vanilla = max(res[k].tokens_per_sec for k in
                           ("Suffix (per-req CST)", "Draft model 7B", "MTP"))
        record[w] = {
            "grouped_over_no_sd":
                res["Grouped (Seer)"].tokens_per_sec / base,
            "grouped_over_best_vanilla":
                res["Grouped (Seer)"].tokens_per_sec / best_vanilla,
            "acc_gain_grouped_vs_suffix":
                res["Grouped (Seer)"].mean_acceptance_len
                - res["Suffix (per-req CST)"].mean_acceptance_len,
            "paper_acc_gain": 0.22,
            "paper_max_speedup_over_vanilla": 1.3,
            "tree_over_multipath":
                res["Grouped+tree"].tokens_per_sec
                / res["Grouped+multipath"].tokens_per_sec,
        }
    txt = table(rows, ["workload", "strategy", "norm_thpt", "acc_len"],
                "Fig. 11 — SD strategies (throughput + acceptance)")
    save_result("sd_strategies", {"rows": rows, "record": record,
                                  "table": txt})
    ensure_engine_tree_record()
    return record


if __name__ == "__main__":
    run()
