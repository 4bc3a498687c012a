"""The one generator behind every traffic mix.

A mix is a data file under ``traffic/``; this module reads its parameters
and turns ``--seed`` into requests.  One kind of mix exists so far:

* ``grpo``: back-to-back synchronous GRPO iterations.  Each iteration is
  ``groups`` groups of ``group_size`` samples; a group shares one random
  prompt.  Output budgets follow the paper's production shape (a lognormal
  with a latent group factor, mixed by ``rho``; ``data/workload.py``
  ``sample_lengths`` in the program), divided by ``scale_divisor``, with
  group and sample factors at stratified normal quantiles.

  Under greedy decoding with grouped speculation, how many engine steps a
  batch takes depends on what the model writes, so on the weights and the
  prompts, and not only on the budgets.  The batch (prompts and budgets)
  is therefore drawn once, from the mix's ``content_seed``, which also
  seeds the weights; every iteration serves that batch, and ``--seed``
  picks the order of its groups and of the budgets within each group, and
  the requests' sampling seeds.  Every seed and every iteration is the
  same work in another order.

Request seeds stay inside int32, which the engine's sampling keys use.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List

import numpy as np

_INV = NormalDist().inv_cdf


def strata(n: int) -> np.ndarray:
    """n standard-normal quantiles at the midpoints of n equal strata."""
    return np.array([_INV((i + 0.5) / n) for i in range(n)])


def budgets(mix: dict, groups: int, rng: np.random.Generator) -> np.ndarray:
    """(groups, group_size) output budgets for one iteration.

    ``rho`` > 0: each group draws one group factor (a permutation of the
    group strata) and every group holds all ``group_size`` sample strata
    in a seed-chosen order, so the multiset of budgets is the same for
    every seed.  ``rho`` == 0: no group factor; the sample strata are
    spread over all requests of the iteration in a seed-chosen order."""
    K = mix["group_size"]
    L = mix["lengths"]
    sigma, rho, div = L["sigma"], L["rho"], L["scale_divisor"]
    mu = math.log(L["mean"]) - sigma ** 2 / 2
    if rho > 0:
        zg = rng.permutation(strata(groups))[:, None]
        zi = np.stack([rng.permutation(strata(K)) for _ in range(groups)])
        z = math.sqrt(rho) * zg + math.sqrt(1 - rho) * zi
    else:
        z = rng.permutation(strata(groups * K)).reshape(groups, K)
    lens = np.exp(mu + sigma * z) / div
    hi = L["max"] // div
    return np.clip(np.round(lens), 1, hi).astype(np.int64)


@dataclass
class Spec:
    """One request as the generator makes it (no program types)."""
    req_id: str
    group_id: str
    prompt: List[int]
    max_new_tokens: int
    temperature: float
    seed: int
    speculative: bool = False


@dataclass
class Iteration:
    index: int
    groups: List[List[Spec]] = field(default_factory=list)

    @property
    def requests(self) -> List[Spec]:
        return [r for g in self.groups for r in g]


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFFFFFF, *stream])


def batch(mix: dict, groups: int, *, vocab: int):
    """The mix's one batch: ``groups`` prompts and a (groups, group_size)
    array of budgets, from ``content_seed``."""
    rng = _rng(mix["content_seed"], 1)
    lens = budgets(mix, groups, rng)
    prompts = [rng.integers(3, vocab, size=mix["prompt_len"]).tolist()
               for _ in range(groups)]
    return prompts, lens


def grpo_iteration(mix: dict, seed: int, index: int, groups: int, *,
                   vocab: int) -> Iteration:
    """Iteration ``index`` of a ``grpo`` mix (index < 0: warm-up): the
    mix's batch in the order that the seed picks for this iteration."""
    prompts, lens = batch(mix, groups, vocab=vocab)
    rng = _rng(seed, 1, index + 1_000)
    K = mix["group_size"]
    it = Iteration(index)
    for slot, g in enumerate(rng.permutation(groups)):
        gid = f"i{index}.g{slot}"
        ks = rng.permutation(K)
        it.groups.append([
            Spec(f"{gid}.r{k}", gid, prompts[g], int(lens[g, ks[k]]),
                 mix["temperature"], int(rng.integers(0, 2 ** 31 - 1)),
                 speculative=(k == 0))
            for k in range(K)])
    return it


def warm_iteration(mix: dict, seed: int, groups: int, *, vocab: int,
                   cap: int) -> Iteration:
    """The warm-up iteration: the same request count and prompt shape as
    a measured one, budgets capped at ``cap`` so it ends sooner."""
    it = grpo_iteration(mix, seed, -1, groups, vocab=vocab)
    for r in it.requests:
        r.max_new_tokens = min(r.max_new_tokens, cap)
    return it

