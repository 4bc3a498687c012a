"""The window of a ``grpo`` mix: back-to-back synchronous GRPO iterations.

Each iteration is rolled out to its end through ``SeerRollout.run_stream``
before the next begins, as a synchronous RL trainer waits for its batch.
The window holds whole iterations: it begins no new one once the time
left is shorter than the last one took, so no long request is cut off and
left out of the tail.
"""
from __future__ import annotations

import math
import time

from chipbench import traffic_gen


def to_groups(it: traffic_gen.Iteration):
    from repro.core.request import Group, RolloutRequest
    return [Group(g[0].group_id, [
        RolloutRequest(req_id=r.req_id, group_id=r.group_id,
                       prompt=list(r.prompt), seed=r.seed,
                       max_new_tokens=r.max_new_tokens,
                       temperature=r.temperature, stop_token=None,
                       speculative=r.speculative) for r in g])
        for g in it.groups]


def run_iteration(ro, it):
    """Roll one iteration to its end; (requests, stats, t0, t1)."""
    groups = to_groups(it)
    t0 = time.monotonic()
    result = None
    for kind, payload in ro.run_stream(groups):
        if kind == "result":
            result = payload
    return ([r for g in groups for r in g.requests], result.stats, t0,
            time.monotonic())


def warm(ro, cell, seed: int, vocab: int) -> None:
    """One iteration of the window's shape, budgets capped so it ends
    sooner: every step, export and import shape the window uses."""
    mix = cell.mix
    run_iteration(ro, traffic_gen.warm_iteration(
        mix, seed, cell.groups, vocab=vocab, cap=mix["warm_budget"]))


def window(ro, cell, seed: int, vocab: int, deadline: float, readings: dict,
           log) -> list:
    """Iterations until ``deadline``; fills ``readings`` and returns every
    request served."""
    readings.update(latencies=[], iterations=[], tokens=0, drafted=0,
                    accepted=0, chunks=0, migrations=0)
    served, last, k = [], 0.0, 0
    while k == 0 or deadline - time.monotonic() >= last:
        it = traffic_gen.grpo_iteration(cell.mix, seed, k, cell.groups,
                                        vocab=vocab)
        steps0 = [inst.steps_run for inst in ro.instances]
        reqs, stats, t0, t1 = run_iteration(ro, it)
        per = [inst.steps_run - s for inst, s in zip(ro.instances, steps0)]
        last, k = t1 - t0, k + 1
        lat = sorted(r.t_finished - t0 for r in reqs if r.finished)
        readings["iterations"].append({"makespan": last, "finish": lat})
        readings["latencies"] += lat
        readings["tokens"] += stats.tokens
        readings["drafted"] += stats.drafted
        readings["accepted"] += stats.accepted
        readings["chunks"] += stats.chunks
        readings["migrations"] += stats.migrations
        served += reqs
        p90 = lat[math.ceil(0.9 * len(lat)) - 1] if lat else float("nan")
        log(f"iteration {k - 1}: {len(lat)} requests, {stats.tokens} "
            f"tokens, {sum(per)} steps {per}, {stats.accepted}/{stats.drafted} "
            f"drafts accepted, {stats.migrations}/{stats.chunks} chunks "
            f"migrated, {last:.3f} s, p90 {p90:.3f} s")
    return served
