"""Position-keyed sampling.

The RNG for the token at absolute position p of request r depends only on
(base_key, r_seed, p).  Consequently a speculative-verify forward and a
plain sequential decode sample *identical* tokens given identical prefixes —
speculative decoding is bitwise lossless even at temperature > 0, which is
the on-policy guarantee Seer's synchronous RL setting requires (§3.4).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


#: ``position_keys`` makes keys for at least this many positions a row
#: and keeps the first T: every step width up to it shares one compiled
#: key program.  Lowering threefry's unrolled rounds costs about a second
#: of host time per program on a TPU host, at every process start.
KEY_COLUMNS = 64


@jax.jit
def _keys(base_key: jax.Array, seeds: jax.Array,
          positions: jax.Array) -> jax.Array:
    def one(seed, pos_row):
        k = jax.random.fold_in(base_key, seed)
        return jax.vmap(lambda p: jax.random.key_data(
            jax.random.fold_in(k, p)))(pos_row)
    return jax.vmap(one)(seeds, positions)


@partial(jax.jit, static_argnums=1)
def _pad_columns(x: jax.Array, width: int) -> jax.Array:
    return jnp.pad(x, ((0, 0), (0, width - x.shape[1])))


@partial(jax.jit, static_argnums=1)
def _first_columns(x: jax.Array, n: int) -> jax.Array:
    return x[:, :n]


def position_keys(base_key: jax.Array, seeds: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """seeds: (B,), positions: (B,T) -> uint32 keys (B,T,2).

    Compiled launches only (pad, keys, slice), so nothing is traced on
    the host once a width has run; run eagerly, the nested vmaps would
    re-trace ``fold_in`` at every step."""
    T = positions.shape[1]
    width = KEY_COLUMNS
    while width < T:
        width <<= 1
    if width == T:
        return _keys(base_key, seeds, positions)
    return _first_columns(
        _keys(base_key, seeds, _pad_columns(positions, width)), T)


def sample_tokens(logits: jax.Array, keys: jax.Array,
                  temps: jax.Array,
                  row_valid: jax.Array = None) -> jax.Array:
    """logits (B,T,V) f32; keys (B,T,2) uint32; temps (B,).

    temp <= 0 -> greedy; else Gumbel-max sampling (exact categorical).

    ``row_valid`` (B,) bool marks rows whose samples are consumed.  In a
    mixed prefill/decode step, prefill rows carry chunk tokens whose
    "samples" are never used; they are forced greedy (no Gumbel draw from
    garbage keys) and returned as -1 so a stray consumer fails loudly.
    """
    B, T, V = logits.shape
    lf = logits.astype(jnp.float32)
    if row_valid is not None:
        temps = jnp.where(row_valid, temps, 0.0)

    def one(lrow, krow, temp):
        def pos(l, kd):
            key = jax.random.wrap_key_data(kd)
            g = jax.random.gumbel(key, (V,), jnp.float32)
            scaled = jnp.where(temp > 0, l / jnp.maximum(temp, 1e-6) + g, l)
            return jnp.argmax(scaled).astype(jnp.int32)
        return jax.vmap(pos)(lrow, krow)

    sampled = jax.vmap(one)(lf, keys, temps)
    if row_valid is not None:
        sampled = jnp.where(row_valid[:, None], sampled, -1)
    return sampled


def draft_acceptance(sampled: jax.Array, tokens: jax.Array,
                     anchor: jax.Array, n_drafts: jax.Array) -> jax.Array:
    """Longest accepted draft prefix per row, computed on device.

    Row layout: column ``anchor[i]`` of ``tokens`` holds the row's
    pending token and columns ``anchor+1 .. anchor+n_drafts`` its draft
    tokens.  ``sampled[i, anchor+j]`` is the token the model samples
    after consuming draft ``j-1`` (the pending token for ``j=0``), so
    draft ``j`` is accepted iff it equals that sample and every earlier
    draft was accepted — the same longest-prefix match the host-side
    reference path performs, but without a device sync.

    sampled/tokens: (B, T); anchor/n_drafts: (B,) int32 -> (B,) int32.
    """
    B, T = tokens.shape
    if T == 1:
        return jnp.zeros((B,), jnp.int32)
    j = jnp.arange(T - 1)
    d_cols = jnp.clip(anchor[:, None] + 1 + j[None, :], 0, T - 1)
    c_cols = jnp.clip(anchor[:, None] + j[None, :], 0, T - 1)
    d_tok = jnp.take_along_axis(tokens, d_cols, axis=1)
    chain = jnp.take_along_axis(sampled, c_cols, axis=1)
    ok = (d_tok == chain) & (j[None, :] < n_drafts[:, None])
    return jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                   axis=1).astype(jnp.int32)


def tree_acceptance(sampled: jax.Array, tokens: jax.Array,
                    parent: jax.Array, depth: jax.Array,
                    within: jax.Array, mask: jax.Array,
                    anchor: jax.Array) -> tuple:
    """Longest accepted *path* through a draft token tree, on device.

    Row layout (see ``TokenTree``): tree nodes occupy columns after the
    row's anchor; ``parent[b,c]`` is the column of node c's parent (the
    anchor's column for depth-1 nodes, -1 for non-node columns),
    ``depth[b,c]`` its depth from the anchor (0 = anchor / non-tree
    column), and ``within[b,c,c']`` the ancestor-or-self mask the
    attention step used.  A node is accepted iff its token equals the
    token the model sampled at its parent AND every ancestor is
    accepted — evaluated in closed form as "all ancestors' edges
    match", vectorised through the ancestor mask (no sequential scan).
    Children of one node carry distinct tokens (the tree builder
    dedups), so accepted nodes always form a single chain and the
    deepest accepted node identifies the winning path.

    Returns ``(n_accepted (B,), path_col (B,T), accepted (B,T))``:
    ``path_col[b,d]`` is the column of the accepted-path node at depth d
    (the anchor for d = 0 or d > n_accepted) — the gather indices that
    relayout the sampled/logprob chain path-major for the host — and
    ``accepted`` the per-node accept flags (the SSM replay mask).
    """
    B, T = tokens.shape
    node = (depth > 0) & mask
    par = jnp.clip(parent, 0, T - 1)
    edge_ok = jnp.where(
        parent >= 0,
        tokens == jnp.take_along_axis(sampled, par, axis=1), True)
    # accepted iff every within-visible column's edge holds (non-node
    # columns have parent -1 => edge_ok True, so the anchor and padding
    # never veto)
    acc = node & jnp.all(edge_ok[:, None, :] | ~within, axis=2)
    n_acc = jnp.max(jnp.where(acc, depth, 0), axis=1).astype(jnp.int32)
    d = jnp.arange(T, dtype=jnp.int32)[None, :]
    hit = acc[:, None, :] & (depth[:, None, :] == d[:, :, None]) \
        & (d[:, :, None] > 0)                                # (B,Td,Tc)
    has = jnp.any(hit, axis=2)
    path_col = jnp.where(has, jnp.argmax(hit, axis=2),
                         anchor[:, None]).astype(jnp.int32)
    return n_acc, path_col, acc


def token_logprobs_at(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """logprob of ``tokens`` under softmax(logits); (B,T,V),(B,T)->(B,T) f32."""
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    sel = jnp.take_along_axis(lf, tokens[..., None], axis=-1)[..., 0]
    return sel - logz
