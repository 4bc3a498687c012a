"""The sampling keys come from compiled programs.

``position_keys`` must give the same bits as folding each row's seed and
each position into the base key one at a time, at every width, padded
or not, and a step whose keys and fused program were warmed the way the
benchmark's set-up warms them must compile nothing more.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import EngineSeq, Instance, StepFunctions
from repro.engine.sampling import KEY_COLUMNS, position_keys
from repro.launch.serve import CompileClock

_TOP = np.iinfo(np.int32).max


def _near_edges(rng, shape):
    """int32 values drawn from next to 0 and next to the int32 top."""
    low = rng.integers(0, 64, size=shape)
    high = _TOP - rng.integers(0, 64, size=shape)
    return np.where(rng.random(shape) < 0.5, low, high).astype(np.int32)


@pytest.mark.parametrize(
    "B,T", [(32, 1), (32, 9), (32, 64), (3, 5), (2, KEY_COLUMNS + 1)])
def test_position_keys_match_per_element_fold_in(B, T):
    rng = np.random.default_rng(B * 100 + T)
    base_key = jax.random.PRNGKey(7)
    seeds = _near_edges(rng, (B,))
    positions = _near_edges(rng, (B, T))
    seeds[0], positions[0, 0] = 0, 0
    seeds[-1], positions[-1, -1] = _TOP, _TOP

    got = np.asarray(position_keys(base_key, jnp.asarray(seeds),
                                   jnp.asarray(positions)))
    assert got.shape == (B, T, 2) and got.dtype == np.uint32
    want = np.empty((B, T, 2), np.uint32)
    for b in range(B):
        k = jax.random.fold_in(base_key, seeds[b])
        for t in range(T):
            want[b, t] = jax.random.key_data(
                jax.random.fold_in(k, positions[b, t]))
    np.testing.assert_array_equal(got, want)


def test_warmed_step_compiles_nothing(tiny_params_cache):
    """Warm each width as the benchmark's set-up does (keys from int32
    zeros, then the fused step on them); a request's dispatch and commit
    steps afterwards compile no program, keys included."""
    cfg, params = tiny_params_cache("granite-3-8b")
    steps = StepFunctions(cfg)
    inst = Instance(cfg, params, steps, max_slots=2, cache_len=64,
                    gamma_max=0, prefill_chunk=8, base_seed=7)
    B = inst.max_slots
    for T in (1, 2, 4, 8):
        z = jnp.zeros((B, T), jnp.int32)
        zb = jnp.zeros((B,), jnp.int32)
        keys = position_keys(inst.base_key, zb, z)
        *_, inst.cache = steps.fused_step(T)(
            inst.params, inst.cache, z, z, jnp.zeros((B, T), bool),
            keys, jnp.zeros((B,), jnp.float32), jnp.zeros((B,), bool),
            zb, zb)
    jax.block_until_ready(inst.cache)

    seq = EngineSeq("r0", "g0", list(range(2, 14)), seed=_TOP,
                    max_new_tokens=4)
    inst.admit(seq)
    before = dict(steps.invocations_by_kind)
    with CompileClock() as clock:
        for _ in range(8):
            if seq.finished:
                break
            inst.commit_step(inst.dispatch_step())
    assert seq.finished
    ran = {k for k, n in steps.invocations_by_kind.items()
           if n > before.get(k, 0)}
    assert {"fused:1", "fused:4", "fused:8"} <= ran
    assert clock.compiles == 0
