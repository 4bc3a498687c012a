"""The grpo traffic generator."""
import numpy as np
import pytest

from chipbench import cells, traffic_gen

# the mix of the first cell, as BENCHMARK.json names it
MIX = cells.load_traffic(cells.benchmark()["workloads"][0]["traffic"])
SEEDS = [0, 1, 17, 2 ** 31 + 5, 2 ** 32 + 9]


def intra_group_share(lens):
    """Share of the variance of log budgets that lies between groups."""
    lg = np.log(lens)
    return float(np.var(lg.mean(axis=1)) / max(np.var(lg), 1e-12))


def lengths(mix, seed, groups=6):
    it = traffic_gen.grpo_iteration(mix, seed, 0, groups, vocab=1000)
    return np.array([[r.max_new_tokens for r in g] for g in it.groups])


@pytest.mark.parametrize("rho", [0.8, 0.0])
def test_every_seed_gives_the_same_total_and_longest(rho):
    mix = dict(MIX, lengths=dict(MIX["lengths"], rho=rho))
    ls = [lengths(mix, s) for s in SEEDS]
    assert len({int(x.sum()) for x in ls}) == 1
    assert len({int(x.max()) for x in ls}) == 1
    assert len({tuple(sorted(x.ravel())) for x in ls}) == 1
    # ... while the seed decides which request gets which budget
    assert len({x.tobytes() for x in ls}) > 1


def test_rho_sets_intra_group_correlation():
    hi = dict(MIX, lengths=dict(MIX["lengths"], rho=0.8, scale_divisor=1))
    lo = dict(MIX, lengths=dict(MIX["lengths"], rho=0.0, scale_divisor=1))
    share_hi = np.mean([intra_group_share(lengths(hi, s, 12))
                        for s in SEEDS])
    share_lo = np.mean([intra_group_share(lengths(lo, s, 12))
                        for s in SEEDS])
    assert share_hi > 0.5
    assert share_lo < 0.25


def test_budgets_have_the_mix_shape():
    mix = dict(MIX, lengths=dict(MIX["lengths"], scale_divisor=1))
    x = lengths(mix, 3, 12)
    L = MIX["lengths"]
    assert x.max() <= L["max"]
    assert 0.7 * L["mean"] < x.mean() < 1.3 * L["mean"]


def test_groups_share_a_prompt_and_requests_differ():
    it = traffic_gen.grpo_iteration(MIX, 2 ** 31 + 3, 1, 4, vocab=1000)
    assert len(it.groups) == 4
    for g in it.groups:
        assert len(g) == MIX["group_size"]
        assert all(r.prompt == g[0].prompt for r in g)
        assert len(g[0].prompt) == MIX["prompt_len"]
        assert sum(r.speculative for r in g) == 1
    assert it.groups[0][0].prompt != it.groups[1][0].prompt
    seeds = [r.seed for r in it.requests]
    assert len(set(seeds)) == len(seeds)
    assert all(0 <= s < 2 ** 31 for s in seeds)
    assert all(3 <= t < 1000 for r in it.requests for t in r.prompt)


def test_iterations_and_seeds_differ_and_repeat():
    # a seed repeats itself; another iteration or another seed serves the
    # same batch (prompts with their budgets) in another order
    def work(seed, index):
        it = traffic_gen.grpo_iteration(MIX, seed, index, 6, vocab=1000)
        return [(tuple(r.prompt), r.max_new_tokens) for r in it.requests]

    a = work(5, 0)
    assert work(5, 0) == a
    for other in (work(5, 1), work(2 ** 31 + 6, 0)):
        assert sorted(other) == sorted(a)
        assert other != a


def test_warm_iteration_caps_budgets():
    it = traffic_gen.warm_iteration(MIX, 7, 5, vocab=1000, cap=20)
    assert len(it.requests) == 5 * MIX["group_size"]
    assert max(r.max_new_tokens for r in it.requests) <= 20
