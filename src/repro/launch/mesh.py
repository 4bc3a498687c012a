"""Production mesh construction + sharding contexts.

``make_production_mesh`` is a function (never module-level) so importing
this module touches no jax device state — the dry-run sets
``xla_force_host_platform_device_count=512`` *before* first jax init.

Single pod: (data=16, model=16) = 256 chips (one TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
pure data parallelism (gradient all-reduce crosses DCN/ICI between pods).
"""
from __future__ import annotations

from functools import lru_cache
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.sharding import ShardCtx


def _check_devices(needed: int, what: str) -> None:
    have = jax.device_count()
    if needed > have:
        raise ValueError(
            f"{what} needs {needed} devices but jax sees only {have}; "
            "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={needed} (or more) before the first jax import "
            "(tests/conftest.py does this for tier-1)")


def auto_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    ``make_mesh`` defaults to ``Explicit`` axes, and the model code's
    activation annotations (``sharding.constrain`` →
    ``with_sharding_constraint``) accept only ``Auto`` axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    _check_devices(int(np.prod(shape)), f"production mesh {shape}")
    return auto_mesh(shape, axes)


def make_shard_ctx(mesh: Mesh, *, train: bool,
                   seq_shard_prefill: bool = False) -> ShardCtx:
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return ShardCtx(mesh=mesh, dp=dp, tp="model",
                    fsdp="data" if train else None,
                    seq_shard=train or seq_shard_prefill)


def small_mesh(n_model: Optional[int] = None) -> Mesh:
    """Debug mesh over whatever devices exist (tests, CPU)."""
    n = len(jax.devices())
    m = n_model or 1
    _check_devices(m, f"small mesh (model={m})")
    return auto_mesh((n // m, m), ("data", "model"))


# -------- per-instance engine meshes ----------------------------------------

@lru_cache(maxsize=None)
def engine_mesh(tp: int, devices: Optional[tuple] = None) -> Mesh:
    """1-D tensor-parallel mesh for one rollout Instance.

    The engine shards over KV heads only (no data axis: the slot batch
    is tiny and rides replicated), so the mesh is just ``(tp,)`` over
    the ``model`` axis, built over ``devices`` (default: the first
    ``tp`` devices).  Cached per (degree, devices) — every instance on
    the same devices shares one Mesh object, so StepFunctions
    compilations are shared too.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if devices is None:
        _check_devices(tp, f"engine mesh (tp={tp})")
        devices = tuple(jax.devices()[:tp])
    elif len(devices) != tp:
        raise ValueError(
            f"engine mesh (tp={tp}) given {len(devices)} devices")
    return auto_mesh((tp,), ("model",), devices=devices)


def make_engine_shard_ctx(mesh: Mesh) -> ShardCtx:
    """ShardCtx for the engine hot path: KV heads / column-parallel
    weight outputs over ``model``, batch and sequence replicated
    (dp=()/seq_shard=False make the decode-path batch ``constrain``
    calls no-ops), and ``exact`` execution — column-parallel-only
    contractions plus the dense (no capacity-drop) MoE combine, so a
    tp>1 step samples bitwise the same tokens as the 1-chip oracle.
    """
    return ShardCtx(mesh=mesh, dp=(), tp="model", fsdp=None,
                    seq_shard=False, exact=True)
