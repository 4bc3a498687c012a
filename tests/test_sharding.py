"""Unit tests for the logical-axis sharding rules (repro.sharding):
logical_to_spec guards, the params-tree NamedSharding builder, the
batch-axis divisibility guard, and the engine's token-exact
column-parallel spec."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_tiny_config
from repro.launch.mesh import auto_mesh
from repro.models import init_params
from repro.sharding import (ShardCtx, batch_axes, exact_col_spec,
                            head_axis, logical_to_spec, param_rules,
                            param_sharding, shape_tree)


def mesh_2x2():
    return auto_mesh((2, 2), ("data", "model"))


def sctx_2x2(**kw):
    return ShardCtx(mesh=mesh_2x2(), **kw)


# ---------------- logical_to_spec -------------------------------------------


def test_logical_to_spec_basic_tp_rule():
    mesh = mesh_2x2()
    rules = {"embed": None, "ff": "model"}
    spec = logical_to_spec(("embed", "ff"), rules, mesh, (8, 16))
    assert spec == P(None, "model")


def test_logical_to_spec_divisibility_guard_replicates():
    """A dim that does not divide the mesh axis stays replicated
    (whisper's 6 heads on a 4-way axis, yi's odd kv count, ...)."""
    mesh = mesh_2x2()
    rules = {"heads": "model"}
    assert logical_to_spec(("heads",), rules, mesh, (7,)) == P(None)
    assert logical_to_spec(("heads",), rules, mesh, (8,)) == P("model")


def test_logical_to_spec_drops_reused_axis():
    """Two dims of one leaf cannot both take the same mesh axis — the
    second occurrence is dropped (expert then eff fallback rule)."""
    mesh = mesh_2x2()
    rules = {"expert": "model", "eff": "model"}
    spec = logical_to_spec(("expert", "embed", "eff"), rules, mesh,
                           (2, 8, 4))
    assert spec == P("model", None, None)
    # expert not divisible -> eff picks the axis up instead
    spec = logical_to_spec(("expert", "embed", "eff"), rules, mesh,
                           (3, 8, 4))
    assert spec == P(None, None, "model")


def test_logical_to_spec_multi_axis_tuple():
    mesh = auto_mesh((2, 2, 1), ("pod", "data", "model"))
    rules = {"batch": ("pod", "data")}
    spec = logical_to_spec(("batch", "seq"), rules, mesh, (8, 4))
    assert spec == P(("pod", "data"), None)


# ---------------- params-tree builder ---------------------------------------


def test_param_sharding_tree_matches_params():
    cfg = get_tiny_config("granite-3-8b")
    params, axes = init_params(cfg, jax.random.PRNGKey(0))
    sctx = sctx_2x2()
    shardings = param_sharding(axes, sctx, train=False, params_shapes=shape_tree(params))
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    assert len(flat_p) == len(flat_s)
    assert all(isinstance(s, NamedSharding) for s in flat_s)
    # the attention out-proj first dim carries "heads" under the
    # Megatron rules -> sharded over the model axis when divisible
    wq_spec = shardings["layers"]["attn"]["wq"].spec
    assert "model" in jax.tree.leaves(tuple(wq_spec))


def test_param_rules_fsdp_only_in_train():
    sctx = sctx_2x2(fsdp="data")
    assert param_rules(sctx, train=True)["embed"] == "data"
    assert param_rules(sctx, train=False)["embed"] is None


# ---------------- batch/head guards -----------------------------------------


def test_batch_axes_divisibility_guard():
    sctx = sctx_2x2()                  # dp=("data",) of size 2
    assert batch_axes(sctx, 4) == ("data",)
    assert batch_axes(sctx, 3) is None
    assert batch_axes(None, 4) is None


def test_batch_axes_empty_dp_returns_none():
    """The engine's ShardCtx has dp=() — batch constrains must be
    no-ops, not P(()) (which jax rejects)."""
    sctx = sctx_2x2(dp=())
    assert batch_axes(sctx, 4) is None


def test_batch_axes_prefix_fallback():
    mesh = auto_mesh((2, 2, 1), ("pod", "data", "model"))
    sctx = ShardCtx(mesh=mesh, dp=("pod", "data"))
    assert batch_axes(sctx, 4) == ("pod", "data")
    assert batch_axes(sctx, 2) == ("pod",)   # 2 % 4 != 0 -> prefix


def test_head_axis_guard():
    sctx = sctx_2x2()                  # tp size 2
    assert head_axis(sctx, 4) == "model"
    assert head_axis(sctx, 3) is None
    assert head_axis(None, 4) is None


# ---------------- token-exact column-parallel spec ---------------------------


def test_exact_col_spec_shards_only_last_output_dims():
    sctx = sctx_2x2()
    # column-parallel weights: last dim is a contraction OUTPUT
    assert exact_col_spec(("embed", "heads"), (8, 4), sctx) == \
        P(None, "model")
    assert exact_col_spec(("embed", "ff"), (8, 16), sctx) == \
        P(None, "model")
    assert exact_col_spec(("expert", "embed", "eff"), (2, 8, 4), sctx) \
        == P(None, None, "model")
    assert exact_col_spec(("embed", "vocab"), (8, 32), sctx) == \
        P(None, "model")
    # row-parallel counterparts replicate: sharding their first dim
    # would shard the reduction and break bitwise exactness
    assert exact_col_spec(("heads", "embed"), (4, 8), sctx) == \
        P(None, None)
    assert exact_col_spec(("ff", "embed"), (16, 8), sctx) == \
        P(None, None)
    assert exact_col_spec(("vocab", "embed"), (32, 8), sctx) == \
        P(None, None)
    assert exact_col_spec(("norm",), (8,), sctx) == P(None)


def test_exact_col_spec_divisibility_guard():
    sctx = sctx_2x2()
    assert exact_col_spec(("embed", "heads"), (8, 3), sctx) == \
        P(None, None)


# ---------------- shard_map on an engine-style mesh ---------------------------


def test_shard_map_resolves_on_this_build():
    """``jax.shard_map`` over a 2-device ``Auto`` mesh: each device sees
    its own shard, and a collective inside the body reduces across
    them (the MoE ep path's in_specs/psum contract)."""
    mesh = auto_mesh((2,), ("model",))

    def f(x):
        return x * 2, jax.lax.psum(x.sum(keepdims=True), "model")

    g = jax.shard_map(f, mesh=mesh, in_specs=P("model"),
                      out_specs=(P("model"), P()), check_vma=False)
    doubled, total = g(jnp.arange(4.0))
    assert doubled.tolist() == [0.0, 2.0, 4.0, 6.0]
    assert total.tolist() == [6.0]
    assert doubled.sharding.spec == P("model")
