"""Compile the Pallas kernels for a described TPU v5e chip at published
model widths, with ``interpret=False``.

Nothing runs: the TPU compiler builds each kernel for a chip that is
described, not attached, and refuses what the chip would refuse (block
shapes off the (8, 128) tiling, primitives Mosaic cannot lower, more
VMEM than a kernel may use).  Interpret-mode tests cannot see any of
that.  The topology is described inside a fixture, never at import:
only one process may hold the TPU library, and every test worker
imports this module.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.spec_verify.kernel import (spec_verify_pallas,
                                              tree_verify_pallas)
from repro.kernels.ssd_scan.kernel import ssd_intra_chunk_pallas

# engine shapes of the one-chip rollout: 8 slots, a 2048-position
# cache, verify rows of 1 + gamma_max (8) columns, 512-token prompts
SLOTS, CACHE, VERIFY_T, PROMPT = 8, 2048, 9, 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_v5e(one_chip):
    """Compile ``fn`` at the given shapes for one v5e chip, with the
    persistent compilation cache off: an entry written for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def go(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    yield go
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


ATTN_ARCHS = ["zamba2-1.2b", "granite-3-8b"]


def _heads(arch):
    cfg = get_config(arch)
    return cfg, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_flash_attention_compiles(compile_v5e, arch):
    cfg, hq, hk, d = _heads(arch)
    dt = jnp.dtype(cfg.dtype)
    compile_v5e(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, window=cfg.sliding_window, interpret=False),
        ((1, PROMPT, hq, d), dt), ((1, PROMPT, hk, d), dt),
        ((1, PROMPT, hk, d), dt))


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_spec_verify_compiles(compile_v5e, arch):
    cfg, hq, hk, d = _heads(arch)
    dt = jnp.dtype(cfg.dtype)
    compile_v5e(
        lambda q, k, v, qp, kp: spec_verify_pallas(
            q, k, v, qp, kp, window=cfg.sliding_window, interpret=False),
        ((SLOTS, VERIFY_T, hq, d), dt), ((SLOTS, CACHE, hk, d), dt),
        ((SLOTS, CACHE, hk, d), dt), ((SLOTS, VERIFY_T), jnp.int32),
        ((SLOTS, CACHE), jnp.int32))


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_tree_verify_compiles(compile_v5e, arch):
    cfg, hq, hk, d = _heads(arch)
    dt = jnp.dtype(cfg.dtype)
    compile_v5e(
        lambda q, k, v, qp, kp, tm: tree_verify_pallas(
            q, k, v, qp, kp, tm, window=cfg.sliding_window,
            interpret=False),
        ((SLOTS, VERIFY_T, hq, d), dt), ((SLOTS, CACHE, hk, d), dt),
        ((SLOTS, CACHE, hk, d), dt), ((SLOTS, VERIFY_T), jnp.int32),
        ((SLOTS, CACHE), jnp.int32), ((SLOTS, VERIFY_T, CACHE), jnp.bool_))


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "mamba2-370m"])
def test_ssd_intra_chunk_compiles(compile_v5e, arch):
    cfg = get_config(arch)
    q, nh, p = cfg.ssm_chunk, cfg.ssm_nheads, cfg.ssm_head_dim
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    nc = max(1, PROMPT // q)
    f32 = jnp.float32
    compile_v5e(
        lambda x, dt, da, b, c: ssd_intra_chunk_pallas(
            x, dt, da, b, c, n_groups=g, interpret=False),
        ((1, nc, q, nh, p), f32), ((1, nc, q, nh), f32),
        ((1, nc, q, nh), f32), ((1, nc, q, g, n), f32),
        ((1, nc, q, g, n), f32))
