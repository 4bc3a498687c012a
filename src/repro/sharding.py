"""Logical-axis based sharding rules.

Params carry logical axis names (see models/common.Builder).  A RuleSet maps
logical names to mesh axes with divisibility guards: if a dim does not divide
the mesh axis size it is replicated (e.g. whisper's 6 heads or yi's 4 kv
heads on a 16-way model axis).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Logical axis vocabulary used by model init:
#   layers        stacked-layer axis (never sharded)
#   embed         d_model rows (FSDP target in train mode)
#   heads, kv     attention head dims (merged H*hd)
#   ff            MLP hidden
#   vocab         embedding rows / logits
#   expert        MoE expert axis
#   eff           per-expert hidden
#   state, conv, ssm_in   mamba dims (replicated)
#   batch, seq, cache_seq activation/cache axes


@dataclass(frozen=True)
class ShardCtx:
    mesh: Mesh
    dp: tuple = ("data",)          # mesh axes carrying the batch dim
    tp: str = "model"              # tensor/expert-parallel mesh axis
    fsdp: Optional[str] = None     # mesh axis for param FSDP (train mode)
    seq_shard: bool = True         # Megatron-style residual seq sharding
    exact: bool = False            # token-exact sharded execution (engine):
                                   # column-parallel contractions only, with
                                   # explicit all-gathers before every
                                   # sharded-input matmul, and the dense
                                   # (no capacity-drop) MoE combine — every
                                   # FP reduction keeps the single-device
                                   # order, so tp>1 is bitwise-identical

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp]

    def dp_size(self) -> int:
        s = 1
        for a in self.dp:
            s *= self.mesh.shape[a]
        return s


def _mesh_axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s


def logical_to_spec(axes: tuple, rules: dict, mesh: Mesh,
                    shape: tuple) -> P:
    """Map one leaf's logical axes to a PartitionSpec with guards."""
    out = []
    used = set()
    for dim, name in zip(shape, axes):
        mesh_axes = rules.get(name)
        if mesh_axes is None:
            out.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        # drop axes already used by another dim of this leaf
        mesh_axes = tuple(a for a in mesh_axes if a not in used)
        size = _mesh_axis_size(mesh, mesh_axes)
        if mesh_axes and size > 0 and dim % size == 0:
            out.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
            used.update(mesh_axes)
        else:
            out.append(None)
    return P(*out)


def param_rules(sctx: ShardCtx, train: bool) -> dict:
    tp = sctx.tp
    rules = {
        "heads": tp, "kv": tp, "ff": tp, "vocab": tp,
        # expert-parallel when E divides the axis; logical_to_spec's
        # used-axis bookkeeping makes "eff" the tensor-parallel fallback
        # (e.g. Mixtral's 8 experts on a 16-way axis shard d_ff instead)
        "expert": tp, "eff": tp,
        "embed": None, "state": None, "conv": None, "ssm_in": None,
        "layers": None, "norm": None,
    }
    if train and sctx.fsdp:
        rules["embed"] = sctx.fsdp
    return rules


def param_sharding(params_axes, sctx: ShardCtx, train: bool,
                   params_shapes) -> dict:
    """Tree of NamedShardings matching the params tree."""
    rules = param_rules(sctx, train)

    def one(axes, shape):
        spec = logical_to_spec(axes, rules, sctx.mesh, shape)
        return NamedSharding(sctx.mesh, spec)

    return jax.tree.map(
        one, params_axes, params_shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def shape_tree(params) -> dict:
    return jax.tree.map(lambda x: tuple(x.shape), params)


# -------- activation constraint helpers ------------------------------------

def constrain(x, sctx: Optional[ShardCtx], *spec_axes):
    if sctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(sctx.mesh, P(*spec_axes)))


def batch_axes(sctx: Optional[ShardCtx], batch_size: int):
    """Mesh axes for the batch dim, guarded on divisibility."""
    if sctx is None:
        return None
    axes = tuple(a for a in sctx.dp)
    if not axes:
        return None
    size = _mesh_axis_size(sctx.mesh, axes)
    if size and batch_size % size == 0:
        return axes
    # try progressively smaller prefixes
    for k in range(len(axes) - 1, 0, -1):
        sub = axes[:k]
        if batch_size % _mesh_axis_size(sctx.mesh, sub) == 0:
            return sub
    return None


def seq_axis(sctx: Optional[ShardCtx], seq_len: int):
    if sctx is None or not sctx.seq_shard:
        return None
    if seq_len % sctx.tp_size == 0:
        return sctx.tp
    return None


def head_axis(sctx: Optional[ShardCtx], n_heads: int):
    """Mesh axis for an attention-head dim, guarded on divisibility
    (e.g. 4 kv heads on a 16-way axis stay replicated)."""
    if sctx is None:
        return None
    if n_heads % sctx.tp_size == 0:
        return sctx.tp
    return None


# -------- token-exact (engine) param rules ----------------------------------
# The engine's tp mesh must produce the *same tokens* as the 1-chip
# oracle.  Floating-point reductions are order-sensitive, so any matmul
# whose contraction dim is sharded (row-parallel + psum) drifts by an
# ulp and flips sampled tokens.  Column-parallel matmuls — only the
# *output* dim sharded — keep every output element's reduction identical
# to the single-device computation, hence bitwise-exact.  So the exact
# rules shard a weight dim iff it is the leaf's LAST dim and one of the
# contraction-output axes below; the row-parallel counterparts (wo, wd)
# stay replicated, and the model code all-gathers the matching
# activations before those matmuls (see transformer._self_attn/_mlp).

_EXACT_COL_AXES = frozenset({"heads", "kv", "ff", "eff", "vocab"})


def exact_col_spec(axes: tuple, shape: tuple, sctx: ShardCtx) -> P:
    """Column-parallel-only PartitionSpec for one param leaf."""
    out = [None] * len(shape)
    if axes and axes[-1] in _EXACT_COL_AXES \
            and shape[-1] % sctx.tp_size == 0:
        out[-1] = sctx.tp
    return P(*out)
