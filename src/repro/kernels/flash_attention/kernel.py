"""Flash attention Pallas TPU kernel (prefill / training hot path).

Canonical TPU tiling: grid = (B*Hq, nq, nk) with the kv axis innermost so
the online-softmax accumulators (m, l, acc) live in VMEM scratch across kv
iterations and the output tile is written once on the last kv step.

Block shapes are MXU-aligned (128 multiples on the q/kv token dims; head
dim D is the lane dim).  GQA is handled in the BlockSpec index maps: query
head h reads kv head h // (Hq // Hk) — no repeated KV materialisation in
HBM (the `jnp.repeat` the reference does is exactly the memory traffic
this kernel removes).

Causal + sliding-window masking is applied from absolute positions
(q_offset + global row, global col); `pl.when` skips fully-masked blocks'
FLOPs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, q_offset: int,
                  block_q: int, block_k: int, n_k: int, tk_valid: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + qi * block_q + jax.lax.iota(jnp.int32, block_q)
    k_pos = ki * block_k + jax.lax.iota(jnp.int32, block_k)

    # block-level skip: entirely above the diagonal / outside the window
    last_q = q_offset + qi * block_q + block_q - 1
    first_q = q_offset + qi * block_q
    first_k = ki * block_k
    last_k = first_k + block_k - 1
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, first_k <= last_q)
    if window:
        run = jnp.logical_and(run, last_k > first_q - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, D)
        k = k_ref[0].astype(jnp.float32)                    # (bk, D)
        v = v_ref[0].astype(jnp.float32)                    # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        mask = k_pos[None, :] < tk_valid
        if causal:
            mask = jnp.logical_and(mask, k_pos[None, :] <= q_pos[:, None])
        if window:
            mask = jnp.logical_and(mask,
                                   k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                 # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
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
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                     # fully-masked rows
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, q_offset: int = 0,
                           causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool):
    """q: (B,Tq,Hq,D); k,v: (B,Tk,Hk,D) -> (B,Tq,Hq,D)."""
    B, Tq, Hq, D = q.shape
    Tk, Hk = k.shape[1], k.shape[2]
    assert Hq % Hk == 0, (Hq, Hk)
    rep = Hq // Hk
    block_q = min(block_q, Tq) if Tq >= 8 else Tq
    block_k = min(block_k, Tk)
    pq = (-Tq) % block_q
    pk = (-Tk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    Tqp, Tkp = Tq + pq, Tk + pk
    n_q, n_k = Tqp // block_q, Tkp // block_k

    qf = qp.transpose(0, 2, 1, 3).reshape(B * Hq, Tqp, D)
    kf = kp.transpose(0, 2, 1, 3).reshape(B * Hk, Tkp, D)
    vf = vp.transpose(0, 2, 1, 3).reshape(B * Hk, Tkp, D)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        b = bh // Hq
        h = bh % Hq
        return (b * Hk + h // rep, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=D ** -0.5, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k, n_k=n_k,
        tk_valid=Tk)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Tqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(B, Hq, Tqp, D).transpose(0, 2, 1, 3)
    return out[:, :Tq]
