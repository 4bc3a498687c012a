"""Speculative-verify attention Pallas TPU kernel.

The Seer-specific compute hot-spot: scoring γ+1 draft tokens against a
long KV cache in one pass.  At decode batch sizes the MXU is starved —
this kernel turns the (1, D)x(D, S) matvec of plain decode into a
(γ+1, D)x(D, S) matmul *without* re-streaming the KV cache per draft
token: KV blocks stream HBM→VMEM once and all γ+1 queries hit the MXU
together.  That is the TPU-native version of the paper's observation that
"parallel verification of n tokens is faster than serial generation of n
tokens due to reduced memory access".

Tiling: grid = (B*Hq, nk), kv innermost; the whole (γ+1, D) query tile
(tiny: ≤ 16x128 padded to sublane multiples) stays resident in VMEM with
the online-softmax accumulators; KV streams in (block_k, D) tiles, 128-
aligned.  Slot validity and causality come from per-slot absolute
positions (`k_pos`, −1 = empty), matching the engine's ring-buffer cache —
masking is data-dependent, not structural, so the same kernel serves
full-cache decode, sliding-window decode and verify.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _verify_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, window: int,
                   n_k: int, tree_ref=None):
    """Shared online-softmax body.  With ``tree_ref`` (the (T, bk) int8
    ancestor-mask tile of a tree-verify call) the positional mask is
    additionally AND-ed with it — sibling draft nodes share a position,
    so causality alone cannot keep a node from attending a rejected
    sibling's cache row."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale               # (T, D)
    k = k_ref[0].astype(jnp.float32)                       # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    qp = qpos_ref[...]                                     # (T, 1)
    kp = kpos_ref[...]                                     # (1, bk)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (T, bk)
    mask = jnp.logical_and(kp >= 0, kp <= qp)
    if window:
        mask = jnp.logical_and(mask, kp > qp - window)
    if tree_ref is not None:
        mask = jnp.logical_and(mask, tree_ref[0] != 0)     # (T, bk)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = corr * l_scr[...] + p.sum(-1, keepdims=True)
    acc_scr[...] = corr * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _tree_kernel(qpos_ref, kpos_ref, tree_ref, q_ref, k_ref, v_ref,
                 o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                 window: int, n_k: int):
    _verify_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, scale=scale, window=window,
                   n_k=n_k, tree_ref=tree_ref)


def spec_verify_pallas(q, k, v, q_pos, k_pos, *, window: int = 0,
                       block_k: int = 128, interpret: bool):
    """q: (B,T,Hq,D); k,v: (B,S,Hk,D); q_pos: (B,T); k_pos: (B,S)."""
    return _verify_call(q, k, v, q_pos, k_pos, None, window=window,
                        block_k=block_k, interpret=interpret)


def tree_verify_pallas(q, k, v, q_pos, k_pos, tree_mask, *,
                       window: int = 0, block_k: int = 128,
                       interpret: bool):
    """Tree-verify attention: one fused pass over a draft token tree.

    Same contract as :func:`spec_verify_pallas` plus ``tree_mask``
    (B, T, S) — per-query-node allowed cache slots (committed prefix +
    tree ancestors), AND-ed with the positional mask.  The (T, block_k)
    mask tile streams alongside each KV block, so the extra operand
    costs T*block_k int8 bytes of VMEM per tile — negligible next to
    the (block_k, D) KV tiles it rides with, and the MXU work is
    unchanged: verifying a tree of N nodes prices exactly like a linear
    chain of N drafts.
    """
    return _verify_call(q, k, v, q_pos, k_pos,
                        tree_mask.astype(jnp.int8), window=window,
                        block_k=block_k, interpret=interpret)


def _verify_call(q, k, v, q_pos, k_pos, tree_mask, *, window: int,
                 block_k: int, interpret: bool):
    B, T, Hq, D = q.shape
    S, Hk = k.shape[1], k.shape[2]
    assert Hq % Hk == 0
    rep = Hq // Hk
    block_k = min(block_k, S)
    pk = (-S) % block_k
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pk)), constant_values=-1)
        if tree_mask is not None:
            tree_mask = jnp.pad(tree_mask, ((0, 0), (0, 0), (0, pk)))
    Sp = S + pk
    n_k = Sp // block_k

    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hk, Sp, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hk, Sp, D)

    def q_map(bh, ki):
        return (bh, 0, 0)

    def kv_map(bh, ki):
        b = bh // Hq
        h = bh % Hq
        return (b * Hk + h // rep, ki, 0)

    def qpos_map(bh, ki):
        return (bh // Hq, 0, 0)

    def kpos_map(bh, ki):
        return (bh // Hq, 0, ki)

    def tree_map(bh, ki):
        return (bh // Hq, 0, ki)

    # positions ride as a (T, 1) column and a (1, block_k) row: the
    # mask is their broadcast compare, and each block's last two dims
    # are whole or 128-aligned, as the TPU lowering requires
    in_specs = [
        pl.BlockSpec((None, T, 1), qpos_map),
        pl.BlockSpec((None, 1, block_k), kpos_map),
    ]
    operands = [q_pos.reshape(B, T, 1), k_pos.reshape(B, 1, Sp)]
    if tree_mask is None:
        kernel = functools.partial(_verify_kernel, scale=D ** -0.5,
                                   window=window, n_k=n_k)
    else:
        kernel = functools.partial(_tree_kernel, scale=D ** -0.5,
                                   window=window, n_k=n_k)
        in_specs.append(pl.BlockSpec((1, T, block_k), tree_map))
        operands.append(tree_mask)
    in_specs += [
        pl.BlockSpec((1, T, D), q_map),
        pl.BlockSpec((1, block_k, D), kv_map),
        pl.BlockSpec((1, block_k, D), kv_map),
    ]
    operands += [qf, kf, vf]
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, T, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hq, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, D), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return out.reshape(B, Hq, T, D).transpose(0, 2, 1, 3)
