"""Mixture-of-Experts layer.

Three execution paths sharing the same router math:

* ``dense_all``  — every expert computed for every token, combined by router
                   weights.  Exact (no capacity drops); used on a single
                   device (engine tier / tests) where E is small.
* ``ep``         — shard_map expert-parallel: the mesh ``model`` axis holds
                   E/tp experts per device; tokens are replicated across the
                   model axis, each device fills a capacity-C slot buffer for
                   its local experts and partial outputs are psum-combined.
                   Comm per layer = one all-gather (implicit, via in_specs)
                   + one psum — the Megatron-SP-style AG+RS pair.
* ``tp``         — when E does not divide the model axis (e.g. Mixtral's 8
                   experts on a 16-way axis) the per-expert hidden dim is
                   sharded instead (tensor-parallel experts), same body.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import Builder, lin
from repro.sharding import ShardCtx, constrain


def init_moe(b: Builder, d: int, eff: int, n_expert: int, n_shared: int):
    b.param("router", (d, n_expert), ("embed", "expert"), scale=0.02)
    b.param("wg", (n_expert, d, eff), ("expert", "embed", "eff"))
    b.param("wu", (n_expert, d, eff), ("expert", "embed", "eff"))
    b.param("wd", (n_expert, eff, d), ("expert", "eff", "embed"),
            scale=1.0 / (eff ** 0.5))
    if n_shared:
        sf = n_shared * eff
        b.param("sg", (d, sf), ("embed", "ff"))
        b.param("su", (d, sf), ("embed", "ff"))
        b.param("sd", (sf, d), ("ff", "embed"), scale=1.0 / (sf ** 0.5))


def _route(x_f32, router, top_k):
    """x: (T,d) f32 -> (weights (T,k), ids (T,k), probs (T,E))."""
    logits = x_f32 @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, ids, probs


def _aux_loss(probs, ids, n_expert):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    T, k = ids.shape
    counts = jnp.zeros((n_expert,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(T * k, 1)
    p = jnp.mean(probs, axis=0)
    return n_expert * jnp.sum(f * p)


def _expert_ffn(buf, wg, wu, wd):
    """buf: (E_loc, C, d); weights (E_loc, d, f), (E_loc, f, d)."""
    dt = buf.dtype
    h = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd.astype(dt))


def _dense_hidden_axis(eff, sctx):
    """Mesh axis (or None) for the per-expert hidden dim of the dense
    path's (E, T, f) intermediates — matching the engine's exact
    column-parallel param rules (wg/wu shard their last dim ``eff``)."""
    if sctx is None:
        return None
    if eff % sctx.tp_size == 0:
        return sctx.tp
    return None


def moe_dense_all(x, p, cfg, sctx: Optional[ShardCtx] = None):
    """Exact MoE: all experts on all tokens.  With an ``sctx`` the
    all-expert up-projections run column-parallel (per-expert hidden dim
    sharded — reduction over ``d`` unsharded, bitwise-exact) and the
    intermediates are all-gathered before the down-projection so that
    reduction stays unsharded too: no capacity buffer, no dropped
    tokens, and the same tokens at any tp degree.  At tp=1 every
    constraint is a pure annotation (bit-identical to the unsharded
    path)."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    w, ids, probs = _route(xf.astype(jnp.float32), p["router"], cfg.moe_top_k)
    aux = _aux_loss(probs, ids, cfg.num_experts)
    f_ax = _dense_hidden_axis(p["wg"].shape[-1], sctx)
    # (E,T,f) all-expert intermediates, hidden dim sharded
    h = jnp.einsum("td,edf->etf", xf, p["wg"].astype(xf.dtype))
    u = jnp.einsum("td,edf->etf", xf, p["wu"].astype(xf.dtype))
    h = constrain(h, sctx, None, None, f_ax)
    u = constrain(u, sctx, None, None, f_ax)
    g = jax.nn.silu(h) * u
    # all-gather the hidden shards before the down-projection: its
    # reduction (over f) must stay unsharded for bitwise exactness
    g = constrain(g, sctx, None, None, None)
    y_all = jnp.einsum("etf,efd->etd", g, p["wd"].astype(xf.dtype))
    # combine selected experts
    onehot = jax.nn.one_hot(ids, cfg.num_experts, dtype=jnp.float32)  # (T,k,E)
    comb = jnp.einsum("tke,tk->te", onehot, w)                        # (T,E)
    y = jnp.einsum("te,etd->td", comb.astype(x.dtype), y_all)
    y = y + _shared(xf, p, sctx)
    return y.reshape(B, S, d), aux


def _shared(xf, p, sctx: Optional[ShardCtx] = None):
    if "sg" not in p:
        return 0.0
    g = jax.nn.silu(lin(xf, p["sg"])) * lin(xf, p["su"])
    # same all-gather-before-down-proj boundary as the routed experts
    g = constrain(g, sctx, None, None)
    return lin(g, p["sd"])


def _capacity(T, k, E_loc, factor):
    c = int(T * k * factor) // max(E_loc, 1) + 1
    return max(8, -(-c // 8) * 8)


def _moe_body(xf, router, wg, wu, wd, sg, su, sd, *, cfg, e0_fn, E_loc, C,
              tp_axis, out_shape=None, scatter=False):
    """Body shared by ep/tp paths; xf: (T,d) local tokens.

    ``scatter`` (requires ``out_shape=(Bl, Sl)``): combine partial expert
    outputs with psum_scatter along the sequence dim instead of a full
    psum — the Megatron AG+RS pattern.  The caller's residual stream is
    sequence-sharded (train / SP-prefill), so emitting the seq shard
    directly avoids materialising and all-reducing the full (T, d) output
    on every device (§Perf iteration 3b)."""
    T, d = xf.shape
    k = cfg.moe_top_k
    w, ids, probs = _route(xf.astype(jnp.float32), router, k)
    aux = _aux_loss(probs, ids, cfg.num_experts)

    e0 = e0_fn()
    eflat = ids.reshape(-1)                                  # (T*k,)
    local = (eflat >= e0) & (eflat < e0 + E_loc)
    le = jnp.where(local, eflat - e0, E_loc)                 # E_loc = trash row
    onehot = (le[:, None] == jnp.arange(E_loc)[None, :]).astype(jnp.int32)
    pos = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - onehot,
        jnp.minimum(le, E_loc - 1)[:, None], axis=1)[:, 0]   # rank in expert
    valid = local & (pos < C)
    slot = jnp.where(valid, le * C + pos, E_loc * C)         # OOB -> dropped

    # token index per slot, then gather rows (avoids (T*k, d) materialisation)
    tok_of_slot = jnp.full((E_loc * C,), T, jnp.int32)
    tok_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    tok_of_slot = tok_of_slot.at[slot].set(tok_idx, mode="drop")
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], 0)
    buf = xf_pad[tok_of_slot].reshape(E_loc, C, d)

    out_buf = _expert_ffn(buf, wg, wu, wd).reshape(E_loc * C, -1)
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((1, out_buf.shape[1]), out_buf.dtype)], 0)

    # combine: loop over k (bounded, small) to avoid (T*k, d) peaks
    slot_tk = slot.reshape(T, k)
    w_tk = jnp.where(valid.reshape(T, k), w, 0.0)

    def comb_step(y, j):
        rows = out_buf[slot_tk[:, j]]
        return y + rows.astype(jnp.float32) * w_tk[:, j][:, None], None

    y0 = jnp.zeros((T, out_buf.shape[1]), jnp.float32)
    y, _ = jax.lax.scan(comb_step, y0, jnp.arange(k))
    y = y.astype(xf.dtype)
    if sg is not None:
        y = y + lin(jax.nn.silu(lin(xf, sg)) * lin(xf, su), sd)
    if tp_axis is not None:
        if scatter:
            Bl, Sl = out_shape
            y = jax.lax.psum_scatter(
                y.reshape(Bl, Sl, -1), tp_axis,
                scatter_dimension=1, tiled=True)   # (Bl, Sl/tp, d)
        else:
            y = jax.lax.psum(y, tp_axis)
        aux = jax.lax.pmean(aux, tp_axis)
    return y, aux


@jax.named_scope("mlp")
def moe_forward(x, p, cfg, sctx: Optional[ShardCtx]):
    """x: (B,S,d) -> (y, aux)."""
    if sctx is None:
        return moe_dense_all(x, p, cfg)
    if sctx.exact:
        # engine hot path: token-exact sharded combine (no capacity
        # drops — acceptance inside the fused step must see the same
        # logits as the 1-chip oracle)
        return moe_dense_all(x, p, cfg, sctx)

    B, S, d = x.shape
    E, tp = cfg.num_experts, sctx.tp_size
    ep = E % tp == 0
    T_loc = (B // max(sctx.dp_size(), 1)) * S if B % max(sctx.dp_size(), 1) == 0 \
        else B * S
    E_loc = E // tp if ep else E
    # capacity is per-expert over this data-shard's tokens
    C = _capacity(T_loc, cfg.moe_top_k, E, cfg.capacity_factor)

    mesh = sctx.mesh
    dp = sctx.dp if B % max(sctx.dp_size(), 1) == 0 else ()
    x_spec = P(dp if dp else None, None, None)

    has_shared = "sg" in p
    if ep:
        wg_spec = P(sctx.tp, None, None)
        wd_spec = P(sctx.tp, None, None)
        e0_fn = lambda: jax.lax.axis_index(sctx.tp) * E_loc
    else:
        wg_spec = P(None, None, sctx.tp)
        wd_spec = P(None, sctx.tp, None)
        e0_fn = lambda: 0

    shared_specs = (P(None, sctx.tp), P(None, sctx.tp), P(sctx.tp, None)) \
        if has_shared else (P(), P(), P())

    # AG+RS combine: when the caller's residual is sequence-sharded
    # (training / SP-prefill), emit each device's seq shard via
    # psum_scatter instead of all-reducing the full (T, d) output.
    scatter = bool(sctx.seq_shard) and S % tp == 0

    def body(x_l, router, wg, wu, wd, sg, su, sd):
        Bl, Sl, _ = x_l.shape
        y, aux = _moe_body(
            x_l.reshape(-1, d), router, wg, wu, wd,
            sg if has_shared else None,
            su if has_shared else None,
            sd if has_shared else None,
            cfg=cfg, e0_fn=e0_fn, E_loc=E_loc, C=C, tp_axis=sctx.tp,
            out_shape=(Bl, Sl), scatter=scatter)
        if dp:
            aux = jax.lax.pmean(aux, dp)
        if scatter:
            return y, aux                       # (Bl, Sl/tp, d)
        return y.reshape(Bl, Sl, d), aux

    sg = p.get("sg", jnp.zeros((), x.dtype))
    su = p.get("su", jnp.zeros((), x.dtype))
    sd = p.get("sd", jnp.zeros((), x.dtype))

    y_spec = P(dp if dp else None, sctx.tp, None) if scatter else x_spec
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), wg_spec, wg_spec, wd_spec,
                  *shared_specs),
        out_specs=(y_spec, P()),
        check_vma=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"], sg, su, sd)
    return y, aux
