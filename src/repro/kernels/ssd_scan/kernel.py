"""SSD (Mamba2) intra-chunk Pallas TPU kernel.

State-space duality splits the recurrence into an *intra-chunk* quadratic
term (dense (Q,Q)x(Q,P) matmuls — MXU work) and an *inter-chunk* first-
order state recurrence (tiny (P,N) updates — lax.scan at the ops level).
This kernel computes everything chunk-local in one VMEM residency:

  per (batch*head, chunk) grid cell, with Q=chunk len, P=head dim,
  N=state dim (128-aligned):
    cs       = cumsum(dA)                     (Q,)
    y_diag   = (C B^T ∘ exp(segsum) ∘ dt) x   (Q,P)   intra-chunk output
    S_local  = (B ∘ dt·exp(cs_Q - cs))^T x    (N,P)   chunk's state contrib
  exported cs lets the ops wrapper apply the carried state:
    y        = y_diag + (C S_in^T) ∘ exp(cs)
    S_out    = exp(cs_Q) S_in + S_local

Group→head broadcast (G SSM groups share B/C across nh//G heads) happens
in the BlockSpec index map — B/C tiles are never replicated in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, dA_ref, b_ref, c_ref,
                y_ref, s_ref, cs_ref, *, Q: int):
    x = x_ref[...].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[...].astype(jnp.float32)        # (1, Q) row
    dA = dA_ref[...].astype(jnp.float32)        # (1, Q) row
    Bm = b_ref[...].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[...].astype(jnp.float32)         # (Q, N)

    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # inclusive cumsum as a matmul with the upper-triangular ones
    # (Mosaic has no cumsum); HIGHEST keeps the sums at full f32
    cs = jax.lax.dot_general(dA, (rows <= cols).astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # (1,Q)
    seg = cs.T - cs                             # (Q, Q)
    L = jnp.where(rows >= cols, jnp.exp(seg), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    W = CB * L * dt
    y = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,P)
    total = cs[:, Q - 1:]                       # (1, 1)
    w_state = (dt * jnp.exp(total - cs)).T      # (Q, 1)
    S_loc = jax.lax.dot_general(Bm * w_state, x,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (N,P)
    y_ref[...] = y
    s_ref[...] = S_loc
    cs_ref[...] = cs


def ssd_intra_chunk_pallas(xc, dtc, dAc, Bc, Cc, *, n_groups: int,
                           interpret: bool):
    """Intra-chunk terms for all chunks at once.

    xc:  (b, nc, Q, nh, P) f32     dtc/dAc: (b, nc, Q, nh)
    Bc/Cc: (b, nc, Q, G, N) f32
    returns y_diag (b,nc,Q,nh,P), S_local (b,nc,nh,N,P), cs (b,nc,Q,nh)
    """
    b, nc, Q, nh, P = xc.shape
    G, N = Bc.shape[3], Bc.shape[4]
    Hg = nh // G

    xf = xc.transpose(0, 3, 1, 2, 4).reshape(b * nh, nc, Q, P)
    # per-step planes ride as (1, Q) rows so each block's last two dims
    # are whole, as the TPU lowering requires
    dtf = dtc.transpose(0, 3, 1, 2).reshape(b * nh, nc, 1, Q)
    dAf = dAc.transpose(0, 3, 1, 2).reshape(b * nh, nc, 1, Q)
    Bf = Bc.transpose(0, 3, 1, 2, 4).reshape(b * G, nc, Q, N)
    Cf = Cc.transpose(0, 3, 1, 2, 4).reshape(b * G, nc, Q, N)

    def h_map(bh, ci):
        return (bh, ci, 0)

    def g_map(bh, ci):
        bb = bh // nh
        h = bh % nh
        return (bb * G + h // Hg, ci, 0)

    kernel = functools.partial(_ssd_kernel, Q=Q)
    y, s, cs = pl.pallas_call(
        kernel,
        grid=(b * nh, nc),
        in_specs=[
            pl.BlockSpec((None, None, Q, P), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((None, None, 1, Q), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((None, None, 1, Q), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((None, None, Q, N),
                         lambda bh, ci: g_map(bh, ci) + (0,)),
            pl.BlockSpec((None, None, Q, N),
                         lambda bh, ci: g_map(bh, ci) + (0,)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, Q, P), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((None, None, N, P), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((None, None, 1, Q), lambda bh, ci: (bh, ci, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * nh, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((b * nh, nc, N, P), jnp.float32),
            jax.ShapeDtypeStruct((b * nh, nc, 1, Q), jnp.float32),
        ],
        interpret=interpret,
    )(xf, dtf, dAf, Bf, Cf)
    y = y.reshape(b, nh, nc, Q, P).transpose(0, 2, 3, 1, 4)
    s = s.reshape(b, nh, nc, N, P).transpose(0, 2, 1, 3, 4)
    cs = cs.reshape(b, nh, nc, Q).transpose(0, 2, 3, 1)
    return y, s, cs
