"""Equivalence tests for the §Perf code paths: the optimized variants
must be numerically identical to the general paths they replace."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.models import forward, init_cache, init_params
from repro.models.moe import moe_forward
from repro.models.transformer import set_remat_policy


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_tiny_config("granite-3-8b")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.mark.parametrize("arch", ["granite-3-8b", "llama-3.2-vision-11b",
                                  "zamba2-1.2b"])
def test_contiguous_update_matches_scatter(arch):
    """Prefill with the scalar-start DUS cache write == general scatter."""
    cfg = get_tiny_config(arch)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    B, T, S = 2, 16, 32
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    aux = None
    if cfg.arch_type == "vlm":
        aux = {"image_embeds": jnp.zeros(
            (B, cfg.num_image_tokens, cfg.d_model), cfg.dtype)}
    cache0 = init_cache(cfg, B, S)

    def run(contig):
        # cross-attn caches must be prebuilt for cached vlm forward
        c = dict(cache0)
        if cfg.arch_type == "vlm":
            from repro.models import build_cross_cache
            ck, cv = build_cross_cache(cfg, params, aux["image_embeds"])
            c["cross_k"], c["cross_v"] = ck, cv
        logits, new_cache, _ = forward(
            cfg, params, tokens, positions, c,
            contiguous_update=contig)
        return logits, new_cache

    la, ca = run(False)
    lb, cb = run(True)
    np.testing.assert_allclose(np.asarray(la, np.float32),
                               np.asarray(lb, np.float32), rtol=2e-2,
                               atol=2e-2)
    for key in ("k", "v", "slot_pos"):
        if key in ca:
            np.testing.assert_array_equal(np.asarray(ca[key]),
                                          np.asarray(cb[key]))


def test_ring_prefill_roll_matches_chunked():
    """Sliding-window prefill past the window: the roll-based whole-seq
    prefill must produce the same final ring cache as the engine's
    chunked prefill (chunks <= window, the reference semantics).  The
    general scatter is NOT a valid oracle here: overwritten ring slots
    zero out early queries' attention, which is exactly why the roll
    path computes attention over the pre-ring K/V instead."""
    cfg = dataclasses.replace(get_tiny_config("mixtral-8x7b"),
                              sliding_window=8)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    B, T, W = 2, 24, 8                 # T = 3 x window
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)

    # reference: 1-token chunks — exact windowed attention when the ring
    # size equals the window (larger chunks overwrite ring slots that
    # are still inside later queries' windows)
    ref = init_cache(cfg, B, T)
    for i in range(T):
        _, ref, _ = forward(cfg, params, tokens[:, i:i + 1],
                            positions[:, i:i + 1], ref)

    one = init_cache(cfg, B, T)
    _, one, _ = forward(cfg, params, tokens, positions, one,
                        contiguous_update=True)

    np.testing.assert_array_equal(np.asarray(ref["slot_pos"]),
                                  np.asarray(one["slot_pos"]))
    np.testing.assert_allclose(
        np.asarray(ref["k"], np.float32), np.asarray(one["k"], np.float32),
        rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(ref["v"], np.float32), np.asarray(one["v"], np.float32),
        rtol=2e-2, atol=2e-2)


def test_contiguous_update_nonzero_start(dense_setup):
    """Second prefill chunk starting at position 8 writes the right slots."""
    cfg, params = dense_setup
    B, S = 2, 32
    rng = np.random.default_rng(1)
    t1 = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 8)), jnp.int32)
    t2 = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 8)), jnp.int32)
    p1 = jnp.broadcast_to(jnp.arange(8), (B, 8)).astype(jnp.int32)
    p2 = p1 + 8

    def two_chunks(contig):
        cache = init_cache(cfg, B, S)
        _, cache, _ = forward(cfg, params, t1, p1, cache,
                              contiguous_update=contig)
        logits, cache, _ = forward(cfg, params, t2, p2, cache,
                                   contiguous_update=contig)
        return logits, cache

    la, ca = two_chunks(False)
    lb, cb = two_chunks(True)
    np.testing.assert_allclose(np.asarray(la, np.float32),
                               np.asarray(lb, np.float32), rtol=2e-2,
                               atol=2e-2)
    np.testing.assert_array_equal(np.asarray(ca["slot_pos"]),
                                  np.asarray(cb["slot_pos"]))


def test_moe_scatter_matches_psum():
    """psum_scatter MoE combine == full psum combine (on a real mesh)."""
    from jax.sharding import Mesh
    from repro.sharding import ShardCtx

    cfg = dataclasses.replace(
        get_tiny_config("mixtral-8x7b"), num_experts=2, moe_top_k=1)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    layer_moe = params["layers"]["moe"]
    p0 = jax.tree.map(lambda a: a[0], layer_moe)   # first layer's experts

    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    B, S, d = 2, 4, cfg.d_model
    x = jnp.asarray(np.random.default_rng(0).normal(size=(B, S, d)),
                    cfg.dtype)
    with mesh:
        y_psum, aux_a = moe_forward(
            x, p0, cfg, ShardCtx(mesh=mesh, seq_shard=False))
        y_scat, aux_b = moe_forward(
            x, p0, cfg, ShardCtx(mesh=mesh, seq_shard=True))
    np.testing.assert_allclose(np.asarray(y_psum, np.float32),
                               np.asarray(y_scat, np.float32),
                               rtol=2e-2, atol=2e-2)
    assert np.isfinite(float(aux_a)) and np.isfinite(float(aux_b))


def test_remat_policy_does_not_change_loss():
    from repro.training.grpo import GRPOConfig, grpo_loss, pack_experience
    cfg = dataclasses.replace(get_tiny_config("yi-6b"), vocab_size=64)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    responses = {f"g0.r{i}": rng.integers(3, 60, 8).tolist()
                 for i in range(4)}
    prompts = {k: [1, 5, 9] for k in responses}
    rewards = {k: float(rng.random()) for k in responses}
    logprobs = {k: (-rng.random(8)).tolist() for k in responses}
    batch = pack_experience(cfg, responses, prompts, rewards, logprobs,
                            4, 12, gcfg=GRPOConfig())

    def loss_of():
        loss, _ = grpo_loss(cfg, params, batch, gcfg=GRPOConfig())
        return float(loss)

    set_remat_policy("none")
    a = loss_of()
    set_remat_policy("dots")
    b = loss_of()
    set_remat_policy("none")
    assert a == pytest.approx(b, rel=1e-6)
