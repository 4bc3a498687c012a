"""Unified, config-driven model: dense / MoE / SSM / hybrid / VLM / enc-dec.

One ``forward`` covers all execution modes:

* training:        cache=None, full causal attention over the batch
* chunked prefill: cache given, T = chunk tokens appended
* decode:          cache given, T = 1
* spec-verify:     cache given, T = gamma+1 draft tokens scored in one pass

Caches are plain dicts of arrays (pytrees) so they can be donated, sharded
and checkpointed trivially.  Sliding-window configs use a ring-buffer cache
of size ``window``; ``slot_pos`` stores the absolute position held by each
slot so masking stays correct across wrap-around.

Layers are stacked with vmap at init and iterated with lax.scan (keeps HLO
small for the 512-device dry-run); the training path wraps the scan body in
jax.checkpoint (remat).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models.common import Builder, apply_rope, lin, rms_norm
from repro.models.mamba2 import init_mamba_block, mamba_block
from repro.models.moe import init_moe, moe_forward
from repro.sharding import (ShardCtx, batch_axes, constrain, head_axis,
                            seq_axis)


# Dry-run roofline support: XLA cost_analysis counts a while-loop body
# once, so scanned layer stacks under-report FLOPs/collectives.  The
# dry-run sets cfg.scan_unroll=True to fully unroll layer scans (bigger
# HLO, exact op counts); runtime keeps the compact scan.
_SCAN_UNROLL = False

# Remat policy for the training-path jax.checkpoint (perf knob, §Perf
# iteration 3).  None = full remat (save nothing, recompute everything).
_REMAT_POLICY = None
_POLICIES = {
    "none": None,
    # save matmul outputs -> backward skips recomputing the forward dots
    # (and, under FSDP, the all-gathers feeding them)
    "dots": "dots_with_no_batch_dims_saveable",
}


def set_scan_unroll(flag: bool) -> None:
    global _SCAN_UNROLL
    _SCAN_UNROLL = bool(flag)


def set_remat_policy(name: str) -> None:
    global _REMAT_POLICY
    key = _POLICIES[name]
    _REMAT_POLICY = getattr(jax.checkpoint_policies, key) if key else None


def _remat(body):
    return jax.checkpoint(body, policy=_REMAT_POLICY)


def _scan(body, init, xs):
    n = jax.tree.leaves(xs)[0].shape[0]
    return jax.lax.scan(body, init, xs,
                        unroll=n if _SCAN_UNROLL else 1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_attn(b: Builder, cfg: ModelConfig, cross: bool = False) -> None:
    d, hd = cfg.d_model, cfg.head_dim
    b.param("ln", (d,), ("norm",), init="ones")
    b.param("wq", (d, cfg.num_heads * hd), ("embed", "heads"))
    b.param("wk", (d, cfg.num_kv_heads * hd), ("embed", "kv"))
    b.param("wv", (d, cfg.num_kv_heads * hd), ("embed", "kv"))
    b.param("wo", (cfg.num_heads * hd, d), ("heads", "embed"),
            scale=1.0 / (cfg.num_heads * hd) ** 0.5)


def _init_mlp(b: Builder, cfg: ModelConfig, d_ff: Optional[int] = None) -> None:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    b.param("ln", (d,), ("norm",), init="ones")
    b.param("wg", (d, f), ("embed", "ff"))
    b.param("wu", (d, f), ("embed", "ff"))
    b.param("wd", (f, d), ("ff", "embed"), scale=1.0 / f ** 0.5)


def _init_dense_layer(b: Builder, cfg: ModelConfig) -> None:
    b.sub("attn", lambda s: _init_attn(s, cfg))
    b.sub("mlp", lambda s: _init_mlp(s, cfg))


def _init_moe_layer(b: Builder, cfg: ModelConfig) -> None:
    b.sub("attn", lambda s: _init_attn(s, cfg))
    b.param("ln2", (cfg.d_model,), ("norm",), init="ones")
    b.sub("moe", lambda s: init_moe(
        s, cfg.d_model, cfg.moe_d_ff or cfg.d_ff,
        cfg.num_experts, cfg.num_shared_experts))


def init_params(cfg: ModelConfig, key: jax.Array):
    """Returns (params, logical_axes) trees."""
    import numpy as np
    dtype = jnp.dtype(cfg.param_dtype)
    b = Builder(key, dtype)
    b.param("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
            init="embed")
    if not cfg.tie_embeddings:
        b.param("unembed", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                scale=1.0 / cfg.d_model ** 0.5)
    b.param("final_ln", (cfg.d_model,), ("norm",), init="ones")

    at = cfg.arch_type
    if at in ("dense",):
        b.stack("layers", cfg.num_layers, lambda s: _init_dense_layer(s, cfg))
    elif at == "moe":
        nd = cfg.first_dense_layers
        if nd:
            b.stack("dense_layers", nd, lambda s: _init_dense_layer(s, cfg))
        b.stack("layers", cfg.num_layers - nd,
                lambda s: _init_moe_layer(s, cfg))
    elif at == "ssm":
        b.stack("layers", cfg.num_layers, lambda s: init_mamba_block(s, cfg))
    elif at == "hybrid":
        every = cfg.hybrid_attn_every
        n_cells = cfg.num_layers // every
        tail = cfg.num_layers - n_cells * every
        b.stack("cells", n_cells, lambda s: s.stack(
            "ssm", every, lambda s2: init_mamba_block(s2, cfg)))
        if tail:
            b.stack("tail", tail, lambda s: init_mamba_block(s, cfg))
        # one weight-tied shared attention+mlp block (Zamba2-style)
        b.sub("shared_attn", lambda s: _init_attn(s, cfg))
        b.sub("shared_mlp", lambda s: _init_mlp(s, cfg))
    elif at == "vlm":
        every = cfg.cross_attn_every
        n_cells = cfg.num_layers // every
        b.stack("cells", n_cells, lambda s: (
            s.stack("self", every, lambda s2: _init_dense_layer(s2, cfg)),
            s.sub("cross", lambda s2: _init_attn(s2, cfg, cross=True)),
        ))
    elif at == "audio":
        b.stack("enc_layers", cfg.encoder_layers,
                lambda s: _init_dense_layer(s, cfg))
        b.stack("dec_layers", cfg.num_layers, lambda s: (
            s.sub("attn", lambda s2: _init_attn(s2, cfg)),
            s.sub("cross", lambda s2: _init_attn(s2, cfg, cross=True)),
            s.sub("mlp", lambda s2: _init_mlp(s2, cfg)),
        ))
    else:
        raise ValueError(at)
    return b.params, b.axes


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def cache_len_for(cfg: ModelConfig, requested: int) -> int:
    if cfg.sliding_window:
        return min(requested, cfg.sliding_window)
    return requested


def _n_attn_layers(cfg: ModelConfig) -> int:
    at = cfg.arch_type
    if at == "ssm":
        return 0
    if at == "hybrid":
        return cfg.num_layers // cfg.hybrid_attn_every
    return cfg.num_layers


def _n_ssm_layers(cfg: ModelConfig) -> int:
    if cfg.arch_type == "ssm":
        return cfg.num_layers
    if cfg.arch_type == "hybrid":
        return cfg.num_layers
    return 0


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> dict:
    """Zero-filled cache pytree.  Works under jax.eval_shape for the dry-run."""
    dt = jnp.dtype(dtype or cfg.dtype)
    S = cache_len_for(cfg, max_len)
    hd = cfg.head_dim
    cache: dict = {}
    n_attn = _n_attn_layers(cfg)
    if n_attn:
        cache["k"] = jnp.zeros((n_attn, batch, S, cfg.num_kv_heads, hd), dt)
        cache["v"] = jnp.zeros((n_attn, batch, S, cfg.num_kv_heads, hd), dt)
        cache["slot_pos"] = jnp.full((batch, S), -1, jnp.int32)
    n_ssm = _n_ssm_layers(cfg)
    if n_ssm:
        ch = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
        cache["conv"] = jnp.zeros((n_ssm, batch, cfg.ssm_conv - 1, ch), dt)
        cache["ssm"] = jnp.zeros(
            (n_ssm, batch, cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32)
    if cfg.arch_type == "vlm":
        n_cross = cfg.num_layers // cfg.cross_attn_every
        cache["cross_k"] = jnp.zeros(
            (n_cross, batch, cfg.num_image_tokens, cfg.num_kv_heads, hd), dt)
        cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
    if cfg.arch_type == "audio":
        cache["cross_k"] = jnp.zeros(
            (cfg.num_layers, batch, cfg.num_audio_frames,
             cfg.num_kv_heads, hd), dt)
        cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
    return cache


# ---------------------------------------------------------------------------
# sub-layer application
# ---------------------------------------------------------------------------


def _project_qkv(p, xn, cfg, positions=None):
    B, T, _ = xn.shape
    hd = cfg.head_dim
    q = lin(xn, p["wq"]).reshape(B, T, cfg.num_heads, hd)
    k = lin(xn, p["wk"]).reshape(B, T, cfg.num_kv_heads, hd)
    v = lin(xn, p["wv"]).reshape(B, T, cfg.num_kv_heads, hd)
    if positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


@jax.named_scope("attention")
def _self_attn(p, x, cfg, positions, slots, ck, cv, slot_pos, token_mask,
               causal=True, sctx=None, attn_allowed=None):
    """Returns (x_out, new_ck, new_cv).  ck/cv None => no-cache (training).

    ``attn_allowed`` (B,T,S) bool, when given, replaces the positional
    mask on the cache-scatter path — the tree-verify step precomputes
    per-query visibility (committed prefix + tree ancestors) because
    sibling draft nodes share absolute positions."""
    xn = rms_norm(x, p["ln"], cfg.rms_eps)
    q, k, v = _project_qkv(p, xn, cfg, positions)
    # engine tensor-parallel (exact mode): q/k/v and the KV cache shard
    # over heads, so the per-head attention below runs with zero
    # cross-device traffic; o is then all-gathered BEFORE the wo matmul
    # so that contraction's reduction dim stays unsharded — bitwise the
    # same output as the 1-chip path (row-parallel + psum would drift by
    # an ulp and flip sampled tokens).  Non-exact contexts (training /
    # production serve) keep their own GSPMD layout untouched.
    exact = sctx is not None and sctx.exact
    h_ax = head_axis(sctx, cfg.num_heads) if exact else None
    kv_ax = head_axis(sctx, cfg.num_kv_heads) if exact else None

    def con(t, *spec_axes):
        return constrain(t, sctx, *spec_axes) if exact else t

    q = con(q, None, None, h_ax, None)
    k = con(k, None, None, kv_ax, None)
    v = con(v, None, None, kv_ax, None)
    window = cfg.sliding_window
    B, T = x.shape[:2]
    if ck is None:
        kv_valid = token_mask if token_mask is not None else None
        o = attn_mod.attention(q, k, v, positions, positions, causal=causal,
                               window=window, kv_valid=kv_valid,
                               softcap=cfg.attn_logit_softcap)
        nk, nv = k, v
    elif slots is None:
        # contiguous cache write (production prefill): scalar-start DUS /
        # roll partitions cleanly; the general scatter below has
        # data-dependent batch indices, which SPMD can only handle by
        # replicating the full-batch K/V updates (observed: 128-256 GiB
        # of all-gather per prefill step before this path existed —
        # §Perf 1c/1e)
        S = ck.shape[1]
        if window and T >= S:
            # ring cache, whole-window prefill: the final ring holds the
            # last S tokens at slots (pos % S) — a roll of the tail, no
            # scatter.  Attention runs over the full pre-ring K/V (the
            # window mask on absolute positions handles causality).
            shift = (T - S) % S
            nk = con(jnp.roll(k[:, T - S:].astype(ck.dtype), shift,
                              axis=1), None, None, kv_ax, None)
            nv = con(jnp.roll(v[:, T - S:].astype(cv.dtype), shift,
                              axis=1), None, None, kv_ax, None)
            o = attn_mod.attention(q, k, v, positions, positions,
                                   causal=causal, window=window,
                                   softcap=cfg.attn_logit_softcap)
        else:
            start = positions[0, 0]
            zero = jnp.zeros((), start.dtype)
            nk = con(jax.lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (zero, start, zero, zero)),
                None, None, kv_ax, None)
            nv = con(jax.lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (zero, start, zero, zero)),
                None, None, kv_ax, None)
            kv_valid = slot_pos >= 0
            o = attn_mod.attention(q, nk, nv, positions, slot_pos,
                                   causal=causal, window=window,
                                   kv_valid=kv_valid,
                                   softcap=cfg.attn_logit_softcap)
        o = con(o, None, None, h_ax, None)
        o = con(o.reshape(B, T, -1), None, None, None)
        o = lin(o, p["wo"])
        return x + o, nk, nv
    else:
        bidx = jnp.arange(B)[:, None]
        nk = con(ck.at[bidx, slots].set(k.astype(ck.dtype), mode="drop"),
                 None, None, kv_ax, None)
        nv = con(cv.at[bidx, slots].set(v.astype(cv.dtype), mode="drop"),
                 None, None, kv_ax, None)
        kv_valid = slot_pos >= 0
        o = attn_mod.attention(q, nk, nv, positions, slot_pos,
                               causal=causal, window=window,
                               kv_valid=kv_valid,
                               softcap=cfg.attn_logit_softcap,
                               allowed_mask=attn_allowed)
    o = con(o, None, None, h_ax, None)
    o = con(o.reshape(B, T, -1), None, None, None)
    o = lin(o, p["wo"])
    return x + o, nk, nv


@jax.named_scope("attention")
def _cross_attn(p, x, cfg, kv_or_embeds, from_cache: bool, sctx=None):
    """Cross attention to static memory (image/audio embeddings)."""
    exact = sctx is not None and sctx.exact
    xn = rms_norm(x, p["ln"], cfg.rms_eps)
    B, T, _ = xn.shape
    hd = cfg.head_dim
    q = lin(xn, p["wq"]).reshape(B, T, cfg.num_heads, hd)
    if from_cache:
        k, v = kv_or_embeds
    else:
        mem = kv_or_embeds
        k = lin(mem, p["wk"]).reshape(B, mem.shape[1], cfg.num_kv_heads, hd)
        v = lin(mem, p["wv"]).reshape(B, mem.shape[1], cfg.num_kv_heads, hd)
    q_pos = jnp.zeros((B, T), jnp.int32)
    k_pos = jnp.zeros((B, k.shape[1]), jnp.int32)
    o = attn_mod.attention(q, k, v, q_pos, k_pos, causal=False, window=0)
    o = o.reshape(B, T, -1)
    if exact:
        # all-gather head shards before the row-parallel wo matmul so
        # its reduction dim stays unsharded (bitwise-exact; see
        # _self_attn)
        o = constrain(o, sctx, None, None, None)
    return x + lin(o, p["wo"]), k, v


@jax.named_scope("mlp")
def _mlp(p, x, cfg, sctx=None):
    exact = sctx is not None and sctx.exact
    xn = rms_norm(x, p["ln"], cfg.rms_eps)
    h = jax.nn.silu(lin(xn, p["wg"])) * lin(xn, p["wu"])
    if exact:
        # column-parallel up-projections leave h sharded on the hidden
        # dim; all-gather it before the down-projection so that
        # contraction's reduction stays unsharded (bitwise-exact)
        h = constrain(h, sctx, None, None,
                      head_axis(sctx, h.shape[-1]))
        h = constrain(h, sctx, None, None, None)
    return x + lin(h, p["wd"])


def _dense_layer(p, x, cfg, positions, slots, ck, cv, slot_pos, token_mask,
                 sctx=None, attn_allowed=None):
    x, nk, nv = _self_attn(p["attn"], x, cfg, positions, slots, ck, cv,
                           slot_pos, token_mask, sctx=sctx,
                           attn_allowed=attn_allowed)
    x = _mlp(p["mlp"], x, cfg, sctx)
    return x, nk, nv


def _moe_layer(p, x, cfg, positions, slots, ck, cv, slot_pos, token_mask,
               sctx, attn_allowed=None):
    x, nk, nv = _self_attn(p["attn"], x, cfg, positions, slots, ck, cv,
                           slot_pos, token_mask, sctx=sctx,
                           attn_allowed=attn_allowed)
    xn = rms_norm(x, p["ln2"], cfg.rms_eps)
    y, aux = moe_forward(xn, p["moe"], cfg, sctx)
    return x + y, nk, nv, aux


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array,
            positions: jax.Array, cache: Optional[dict] = None, *,
            aux_inputs: Optional[dict] = None,
            token_mask: Optional[jax.Array] = None,
            sctx: Optional[ShardCtx] = None,
            train: bool = False,
            contiguous_update: bool = False,
            slot_index: Optional[jax.Array] = None,
            within_mask: Optional[jax.Array] = None):
    """tokens/positions: (B, T) -> (logits (B,T,V), new_cache, aux_loss).

    cache=None  => full-sequence (training) forward.
    cache given => incremental forward appending T tokens; ``slots`` are
                   derived from positions (ring for sliding-window configs).

    Tree-verify inputs (both or neither):

    * ``slot_index`` (B,T) int32 — explicit cache slot per token,
      decoupling slots from positions.  Sibling draft nodes share a
      position but must occupy distinct cache rows; the engine lays the
      tree out after the anchor (slot = anchor_slot + node index).
    * ``within_mask`` (B,Tq,Tc) bool — within-step visibility: query
      column q may attend the cache row written by column c.  For tree
      rows this is the ancestor-or-self mask; for prefill/linear rows
      plain position causality (identical to what the positional mask
      computes, so non-tree rows are unchanged).  Combined here with
      cache validity + causality over *previously written* slots into
      one (B,T,S) allowed-mask shared by every attention layer.
    """
    B, T = tokens.shape
    has_cache = cache is not None
    new_cache = dict(cache) if has_cache else None

    x = params["embed"][tokens]  # (B,T,d)
    dtype = jnp.dtype(cfg.dtype)
    x = x.astype(dtype)
    dp = batch_axes(sctx, B)
    # residual-stream sequence sharding: training always (Megatron-style);
    # prefill when ShardCtx.seq_shard is set (§Perf iteration 1 — turns
    # per-layer full-activation all-reduces into AG+RS pairs)
    sq = seq_axis(sctx, T) if (train or T > 1) else None
    x = constrain(x, sctx, dp, sq, None)

    slots = None
    slot_pos = None
    if has_cache and "slot_pos" in cache:
        S = cache["slot_pos"].shape[1]
        ring = cfg.sliding_window > 0
        if contiguous_update and token_mask is None and \
                (not ring or T >= S):
            # production prefill: every row writes [start, start+T);
            # slots=None selects the scatter-free path in _self_attn
            # (scalar-start DUS, or a roll of the tail for ring caches
            # prefilled past the window)
            if ring:
                shift = (T - S) % S
                slot_pos = jnp.roll(positions[:, T - S:], shift, axis=1)
            else:
                start = positions[0, 0]
                slot_pos = jax.lax.dynamic_update_slice(
                    cache["slot_pos"], positions,
                    (jnp.zeros((), start.dtype), start))
            new_cache["slot_pos"] = slot_pos
        else:
            slots = slot_index if slot_index is not None else \
                (positions % S if ring else positions)
            # masked/padded tokens -> OOB slot, dropped by scatter
            if token_mask is not None:
                slots = jnp.where(token_mask, slots, S)
            slot_pos = cache["slot_pos"].at[
                jnp.arange(B)[:, None], slots].set(positions, mode="drop")
            new_cache["slot_pos"] = slot_pos

    attn_allowed = None
    if within_mask is not None and slots is not None:
        # one (B, T, S) allowed-mask shared by all attention layers:
        # previously cached rows obey validity + positional causality
        # (+ window); rows written by THIS step's columns obey the
        # caller's within-step mask instead — position alone cannot
        # separate sibling draft nodes at the same depth.
        S = slot_pos.shape[1]
        qp = positions[:, :, None]
        kp = slot_pos[:, None, :]
        base = (kp >= 0) & (kp <= qp)
        if cfg.sliding_window:
            base = base & (kp > qp - cfg.sliding_window)
        col_of_slot = jnp.full((B, S), -1, jnp.int32).at[
            jnp.arange(B)[:, None], slots].set(
            jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :],
                             (B, T)), mode="drop")
        idx = jnp.broadcast_to(
            jnp.clip(col_of_slot, 0, T - 1)[:, None, :], (B, T, S))
        ext = jnp.take_along_axis(within_mask, idx, axis=2)
        attn_allowed = jnp.where((col_of_slot >= 0)[:, None, :], ext, base)

    aux_total = jnp.zeros((), jnp.float32)
    at = cfg.arch_type

    if at in ("dense", "moe"):
        x, aux_total, new_cache = _decoder_stack(
            cfg, params, x, positions, slots, slot_pos, token_mask,
            new_cache if has_cache else None, sctx, train, attn_allowed)
    elif at == "ssm":
        x, new_cache = _ssm_stack(cfg, params["layers"], x, token_mask,
                                  new_cache if has_cache else None, train,
                                  key_prefix=None)
    elif at == "hybrid":
        x, new_cache, aux_total = _hybrid_stack(
            cfg, params, x, positions, slots, slot_pos, token_mask,
            new_cache if has_cache else None, sctx, train, attn_allowed)
    elif at == "vlm":
        x, new_cache = _vlm_stack(
            cfg, params, x, positions, slots, slot_pos, token_mask,
            new_cache if has_cache else None, aux_inputs, sctx, train,
            attn_allowed)
    elif at == "audio":
        x, new_cache = _audio_stack(
            cfg, params, x, positions, slots, slot_pos, token_mask,
            new_cache if has_cache else None, aux_inputs, sctx, train,
            attn_allowed)
    else:
        raise ValueError(at)

    x = rms_norm(x, params["final_ln"], cfg.rms_eps)
    x = constrain(x, sctx, dp, sq, None)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T.astype(dtype)
    else:
        logits = x @ params["unembed"].astype(dtype)
    return logits, new_cache, aux_total


# ---- dense / moe stack -----------------------------------------------------


def _decoder_stack(cfg, params, x, positions, slots, slot_pos, token_mask,
                   cache, sctx, train, attn_allowed=None):
    has_cache = cache is not None
    aux = jnp.zeros((), jnp.float32)
    layer_idx = 0

    def run_group(x, stacked, is_moe, k_sl, v_sl):
        def fn(p, x, *cl):
            ck, cv = (cl if has_cache else (None, None))
            if is_moe:
                xo, nk, nv, a = _moe_layer(p, x, cfg, positions, slots,
                                           ck, cv, slot_pos, token_mask,
                                           sctx, attn_allowed=attn_allowed)
            else:
                xo, nk, nv = _dense_layer(p, x, cfg, positions, slots,
                                          ck, cv, slot_pos, token_mask,
                                          sctx=sctx,
                                          attn_allowed=attn_allowed)
                a = jnp.zeros((), jnp.float32)
            if has_cache:
                return xo, (nk, nv, a)
            return xo, (a,)

        def body(carry, xs):
            out = fn(xs[0], carry, *xs[1:])
            return out[0], out[1]

        body_fn = _remat(body) if train else body
        xs = (stacked,) + ((k_sl, v_sl) if has_cache else ())
        x, ys = _scan(body_fn, x, xs)
        if has_cache:
            nk, nv, a = ys
            return x, nk, nv, jnp.sum(a)
        return x, None, None, jnp.sum(ys[0])

    nd = cfg.first_dense_layers if cfg.arch_type == "moe" else 0
    n_layers = cfg.num_layers
    new_cache = cache
    k_all = cache["k"] if has_cache else None
    v_all = cache["v"] if has_cache else None
    nk_parts, nv_parts = [], []

    if cfg.arch_type == "moe" and nd:
        ks = k_all[:nd] if has_cache else None
        vs = v_all[:nd] if has_cache else None
        x, nk, nv, a = run_group(x, params["dense_layers"], False, ks, vs)
        aux = aux + a
        if has_cache:
            nk_parts.append(nk)
            nv_parts.append(nv)

    main = params["layers"]
    ks = k_all[nd:] if has_cache else None
    vs = v_all[nd:] if has_cache else None
    x, nk, nv, a = run_group(x, main, cfg.arch_type == "moe", ks, vs)
    aux = aux + a
    if has_cache:
        nk_parts.append(nk)
        nv_parts.append(nv)
        new_cache = dict(new_cache)
        new_cache["k"] = jnp.concatenate(nk_parts, 0) if len(nk_parts) > 1 \
            else nk_parts[0]
        new_cache["v"] = jnp.concatenate(nv_parts, 0) if len(nv_parts) > 1 \
            else nv_parts[0]
    return x, aux, new_cache


# ---- ssm stack --------------------------------------------------------------


def _ssm_stack(cfg, stacked, x, token_mask, cache, train, key_prefix=None,
               conv_key="conv", ssm_key="ssm"):
    has_cache = cache is not None

    def body(carry, xs):
        x = carry
        p = xs[0]
        conv_c = xs[1] if has_cache else None
        ssm_c = xs[2] if has_cache else None
        xo, nconv, nssm = mamba_block(p, x, cfg, conv_c, ssm_c, token_mask)
        return xo, (nconv, nssm)

    body_fn = _remat(body) if train else body
    xs = (stacked,) + ((cache[conv_key], cache[ssm_key]) if has_cache else ())
    x, ys = _scan(body_fn, x, xs)
    if has_cache:
        cache = dict(cache)
        cache[conv_key], cache[ssm_key] = ys
    return x, cache


# ---- hybrid (Zamba2) stack ---------------------------------------------------


def _hybrid_stack(cfg, params, x, positions, slots, slot_pos, token_mask,
                  cache, sctx, train, attn_allowed=None):
    has_cache = cache is not None
    every = cfg.hybrid_attn_every
    n_cells = cfg.num_layers // every
    tail = cfg.num_layers - n_cells * every
    shared_attn = params["shared_attn"]
    shared_mlp = params["shared_mlp"]

    def cell_body(carry, xs):
        x = carry
        cell_p = xs[0]
        if has_cache:
            conv_c, ssm_c, ck, cv = xs[1:]
        else:
            conv_c = ssm_c = ck = cv = None

        def inner(c2, xs2):
            p2 = xs2[0]
            cc = xs2[1] if has_cache else None
            sc = xs2[2] if has_cache else None
            xo, nc, ns = mamba_block(p2, c2, cfg, cc, sc, token_mask)
            return xo, (nc, ns)

        xs2 = (cell_p["ssm"],) + ((conv_c, ssm_c) if has_cache else ())
        x, (nconv, nssm) = _scan(inner, x, xs2)
        # shared (weight-tied) attention + mlp block
        x, nk, nv = _self_attn(shared_attn, x, cfg, positions, slots,
                               ck, cv, slot_pos, token_mask, sctx=sctx,
                               attn_allowed=attn_allowed)
        x = _mlp(shared_mlp, x, cfg, sctx)
        if has_cache:
            return x, (nconv, nssm, nk, nv)
        return x, (nconv, nssm)

    body_fn = _remat(cell_body) if train else cell_body
    if has_cache:
        conv_cells = cache["conv"][:n_cells * every].reshape(
            (n_cells, every) + cache["conv"].shape[1:])
        ssm_cells = cache["ssm"][:n_cells * every].reshape(
            (n_cells, every) + cache["ssm"].shape[1:])
        xs = (params["cells"], conv_cells, ssm_cells, cache["k"], cache["v"])
    else:
        xs = (params["cells"],)
    x, ys = _scan(body_fn, x, xs)

    new_cache = dict(cache) if has_cache else None
    if has_cache:
        nconv, nssm, nk, nv = ys
        nconv = nconv.reshape((n_cells * every,) + nconv.shape[2:])
        nssm = nssm.reshape((n_cells * every,) + nssm.shape[2:])
        new_cache["k"], new_cache["v"] = nk, nv
    if tail:
        tail_cache = None
        if has_cache:
            tail_cache = {"conv": cache["conv"][n_cells * every:],
                          "ssm": cache["ssm"][n_cells * every:]}
        x, tail_cache = _ssm_stack(cfg, params["tail"], x, token_mask,
                                   tail_cache, train)
        if has_cache:
            nconv = jnp.concatenate([nconv, tail_cache["conv"]], 0)
            nssm = jnp.concatenate([nssm, tail_cache["ssm"]], 0)
    if has_cache:
        new_cache["conv"], new_cache["ssm"] = nconv, nssm
    return x, new_cache, jnp.zeros((), jnp.float32)


# ---- VLM (Llama-3.2-Vision) stack -------------------------------------------


def build_cross_cache(cfg: ModelConfig, params: dict, embeds: jax.Array):
    """Precompute cross-attention K/V from (stubbed) modality embeddings."""
    if cfg.arch_type == "vlm":
        cross_stacked = params["cells"]["cross"]
    elif cfg.arch_type == "audio":
        enc_out = encode_audio(cfg, params, embeds)
        cross_stacked = params["dec_layers"]["cross"]
        embeds = enc_out
    else:
        raise ValueError(cfg.arch_type)

    def one(p):
        B, Tm, _ = embeds.shape
        hd = cfg.head_dim
        k = lin(embeds, p["wk"]).reshape(B, Tm, cfg.num_kv_heads, hd)
        v = lin(embeds, p["wv"]).reshape(B, Tm, cfg.num_kv_heads, hd)
        return k, v

    k, v = jax.vmap(one)(cross_stacked)
    return k.astype(jnp.dtype(cfg.dtype)), v.astype(jnp.dtype(cfg.dtype))


def _vlm_stack(cfg, params, x, positions, slots, slot_pos, token_mask,
               cache, aux_inputs, sctx, train, attn_allowed=None):
    has_cache = cache is not None
    every = cfg.cross_attn_every
    n_cells = cfg.num_layers // every
    embeds = None
    if not has_cache:
        assert aux_inputs is not None and "image_embeds" in aux_inputs
        embeds = aux_inputs["image_embeds"].astype(x.dtype)

    def cell_body(carry, xs):
        x = carry
        cell_p = xs[0]
        if has_cache:
            ck, cv, xk, xv = xs[1:]
        else:
            ck = cv = xk = xv = None

        def inner(c2, xs2):
            p2 = xs2[0]
            c_k = xs2[1] if has_cache else None
            c_v = xs2[2] if has_cache else None
            xo, nk, nv = _dense_layer(p2, c2, cfg, positions, slots,
                                      c_k, c_v, slot_pos, token_mask,
                                      sctx=sctx, attn_allowed=attn_allowed)
            return xo, (nk, nv) if has_cache else (jnp.zeros(()),)

        xs2 = (cell_p["self"],) + ((ck, cv) if has_cache else ())
        x, inner_ys = _scan(inner, x, xs2)
        if has_cache:
            x, _, _ = _cross_attn(cell_p["cross"], x, cfg, (xk, xv), True,
                                  sctx)
            nk, nv = inner_ys
            return x, (nk, nv)
        x, _, _ = _cross_attn(cell_p["cross"], x, cfg, embeds, False, sctx)
        return x, (jnp.zeros(()),)

    body_fn = _remat(cell_body) if train else cell_body
    if has_cache:
        k_cells = cache["k"].reshape((n_cells, every) + cache["k"].shape[1:])
        v_cells = cache["v"].reshape((n_cells, every) + cache["v"].shape[1:])
        xs = (params["cells"], k_cells, v_cells,
              cache["cross_k"], cache["cross_v"])
    else:
        xs = (params["cells"],)
    x, ys = _scan(body_fn, x, xs)
    new_cache = dict(cache) if has_cache else None
    if has_cache:
        nk, nv = ys
        new_cache["k"] = nk.reshape((n_cells * every,) + nk.shape[2:])
        new_cache["v"] = nv.reshape((n_cells * every,) + nv.shape[2:])
    return x, new_cache


# ---- audio (Whisper) stack ---------------------------------------------------


def encode_audio(cfg: ModelConfig, params: dict, frames: jax.Array):
    """Bidirectional encoder over (stubbed) frame embeddings (B, Tf, d)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    B, Tf, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(Tf)[None, :], (B, Tf))

    def body(carry, p):
        x = carry
        x, _, _ = _self_attn(p["attn"], x, cfg, pos, None, None, None,
                             None, None, causal=False)
        x = _mlp(p["mlp"], x, cfg)
        return x, None

    x, _ = _scan(body, x, params["enc_layers"])
    return x


def _audio_stack(cfg, params, x, positions, slots, slot_pos, token_mask,
                 cache, aux_inputs, sctx, train, attn_allowed=None):
    has_cache = cache is not None
    enc_out = None
    if not has_cache:
        assert aux_inputs is not None and "audio_frames" in aux_inputs
        enc_out = encode_audio(cfg, params, aux_inputs["audio_frames"])

    def body(carry, xs):
        x = carry
        p = xs[0]
        if has_cache:
            ck, cv, xk, xv = xs[1:]
        else:
            ck = cv = xk = xv = None
        x, nk, nv = _self_attn(p["attn"], x, cfg, positions, slots,
                               ck, cv, slot_pos, token_mask, sctx=sctx,
                               attn_allowed=attn_allowed)
        if has_cache:
            x, _, _ = _cross_attn(p["cross"], x, cfg, (xk, xv), True, sctx)
        else:
            x, _, _ = _cross_attn(p["cross"], x, cfg, enc_out, False, sctx)
        x = _mlp(p["mlp"], x, cfg, sctx)
        if has_cache:
            return x, (nk, nv)
        return x, (jnp.zeros(()),)

    body_fn = _remat(body) if train else body
    if has_cache:
        xs = (params["dec_layers"], cache["k"], cache["v"],
              cache["cross_k"], cache["cross_v"])
    else:
        xs = (params["dec_layers"],)
    x, ys = _scan(body_fn, x, xs)
    new_cache = dict(cache) if has_cache else None
    if has_cache:
        new_cache["k"], new_cache["v"] = ys[0], ys[1]
    return x, new_cache
