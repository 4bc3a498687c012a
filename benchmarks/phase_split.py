"""Table 1: time distribution across RL phases (rollout / training /
weight update).

Rollout time comes from the simulator (veRL group-mode baseline — Table 1
is measured on the pre-Seer production stack).  Training time is analytic:
GRPO backprop over every generated token at 6·N_active FLOPs/token on the
full cluster.  Weight update is the checkpoint-engine broadcast of the
bf16 parameters.  Paper: rollout 63-87%, training 10-31%, update 2-6%.
"""
from __future__ import annotations

from repro.configs import get_config
from repro.core.sdmodel import H800

from benchmarks.common import DEPLOY, SPECS, \
    ensure_engine_migration_record, ensure_engine_rollout_record, \
    ensure_train_overlap_record, run_sim, save_result, table, \
    update_bench_rollout, workload

TRAIN_MFU = 0.35                  # Megatron-style large-model training MFU
BCAST_BW = 25e9                   # checkpoint-engine effective bytes/s


def run(workloads=("moonlight", "qwen2-vl-72b", "kimi-k2"), seed=0):
    rows = []
    record = {}
    paper = {"moonlight": (84, 14, 2), "qwen2-vl-72b": (63, 31, 6),
             "kimi-k2": (87, 10, 3)}
    for w in workloads:
        wl = workload(w, seed=seed)
        res = run_sim(w, wl, mode="group", policy="fifo")
        cfg = get_config(DEPLOY[w]["cfg"])
        chips = DEPLOY[w]["chips"] * wl.spec.n_instances
        # fwd+bwd = 3x fwd = 6 FLOPs per active param per token
        train_flops = 6.0 * cfg.active_params() * res.tokens
        t_train = train_flops / (chips * H800.peak_flops * TRAIN_MFU)
        t_update = 2.0 * cfg.num_params() / BCAST_BW
        total = res.total_time + t_train + t_update
        split = (100 * res.total_time / total, 100 * t_train / total,
                 100 * t_update / total)
        rows.append({
            "workload": w, "rollout%": split[0], "train%": split[1],
            "update%": split[2],
            "paper": "/".join(str(x) for x in paper[w]),
        })
        record[w] = {"rollout_pct": split[0], "train_pct": split[1],
                     "update_pct": split[2], "paper_split": paper[w],
                     "rollout_dominates": split[0] > 50.0}
    txt = table(rows, ["workload", "rollout%", "train%", "update%", "paper"],
                "Table 1 — RL phase time split")
    save_result("phase_split", {"rows": rows, "record": record,
                                "table": txt})
    # rollout dominance is the motivation for the engine hot-path work;
    # track it next to the engine numbers in BENCH_rollout.json.  A
    # failed engine bench fails the run (the simulator results are
    # already saved above).
    ensure_engine_rollout_record()
    ensure_engine_migration_record()
    ensure_train_overlap_record()
    update_bench_rollout("phase_split", {
        w: {"rollout_pct": record[w]["rollout_pct"]} for w in record})
    return record


if __name__ == "__main__":
    run()
