"""Fig. 7 + Table 4: end-to-end rollout throughput vs baselines, and the
cumulative ablation (divided rollout -> +context sched -> +grouped SD).

Paper claims: Seer = 1.44-2.04x veRL; ablation ~1.4x / ~1.5x / 1.9-2.04x.
"""
from __future__ import annotations

from benchmarks.common import ensure_engine_rollout_record, run_sim, \
    save_result, table, update_bench_rollout, workload

SYSTEMS = [
    ("veRL (group)", dict(mode="group", policy="fifo")),
    ("RollFlash (request)", dict(mode="request", policy="fifo")),
    ("StreamRL-Oracle", dict(mode="streamrl", policy="fifo")),
    ("+Divided Rollout", dict(mode="divided", policy="nocontext")),
    ("+Context Sched.", dict(mode="divided", policy="seer")),
    ("+Grouped SD (Seer)", dict(mode="divided", policy="seer",
                                sd="grouped")),
]


def run(workloads=("moonlight", "qwen2-vl-72b", "kimi-k2"), seed=0):
    rows = []
    record = {}
    for w in workloads:
        wl = workload(w, seed=seed)
        base = None
        for label, kw in SYSTEMS:
            res = run_sim(w, wl, **kw)
            if base is None:
                base = res.tokens_per_sec
            rows.append({
                "workload": w, "system": label,
                "tokens/s": res.tokens_per_sec,
                "speedup": res.tokens_per_sec / base,
                "tail_frac": res.tail_frac,
                "preempt": res.preemptions,
                "idle": res.idle_frac,
            })
            record[f"{w}/{label}"] = {
                "tokens_per_sec": res.tokens_per_sec,
                "speedup": res.tokens_per_sec / base,
                "tail_frac": res.tail_frac,
                "preemptions": res.preemptions,
            }
    txt = table(rows, ["workload", "system", "tokens/s", "speedup",
                       "tail_frac", "preempt", "idle"],
                "Fig.7/Table 4 — rollout throughput + ablation")
    # paper-claim checks
    checks = {}
    for w in workloads:
        full = record[f"{w}/+Grouped SD (Seer)"]["speedup"]
        checks[w] = {"seer_speedup": full,
                     "paper_range": [1.44, 2.04],
                     "within_2x_band": 1.2 <= full <= 3.2}
    save_result("e2e_throughput", {"rows": rows, "checks": checks,
                                   "table": txt})
    ratio = ensure_engine_rollout_record()["forward_invocation_ratio"]
    update_bench_rollout("e2e_throughput", {
        "tokens_per_sec": {k: v["tokens_per_sec"]
                           for k, v in record.items()},
        "seer_speedup": {w: checks[w]["seer_speedup"] for w in checks},
        "engine_forward_invocation_ratio": ratio,
    })
    return record


if __name__ == "__main__":
    run()
