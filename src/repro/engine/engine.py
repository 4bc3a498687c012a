"""Inference engine: one Seer "inference instance".

Slot-based continuous batching with static JAX shapes:

* a cache buffer of ``max_slots`` rows x ``cache_len`` positions
* batched chunked prefill: ``admit`` only *queues* prefill work; every
  step packs the next chunk of every still-prefilling slot into the same
  forward as the decode/verify rows (a mixed step), bounded by a
  Sarathi-style per-step prefill token budget.  Chunks are ordered
  shortest-remaining-prefill first so nearly-ready slots reach decode
  (and free their queue budget) sooner, and a tail chunk that fits the
  step with one column to spare is fused with the row's first decode
  token (saves one full step per admission).
* one jitted ``fused_step`` covering decode (T=1), speculative verify
  (T = gamma_max+1) and mixed prefill/decode (T = prefill_chunk); rows
  carry a token mask so each request may submit a different number of
  tokens, and a per-row sample mask so prefill rows never sample
* KV export/import per slot — the handle the global KV pool moves between
  instances (divided rollout's stateless chunk migration).  Blobs are
  trimmed to the live prefix ``[0, next_pos)`` along the position axis
  so pool accounting and migrations never carry dead bytes.

Device-resident step contract (the hot path)
--------------------------------------------

``prefill_mode="batched"`` steps are device-resident:

* **The cache pytree is donated.**  ``StepFunctions.fused_step`` /
  ``prefill`` are compiled with ``donate_argnums`` on the cache, so each
  step updates the KV buffers in place instead of copying
  ``max_slots x cache_len`` of cache every iteration.  Callers must not
  retain references to ``Instance.cache`` leaves across a step — after
  dispatch the previous arrays are invalid.  ``_export_kv`` materialises
  fresh slices (``jnp.take``), never aliases, so exported blobs survive
  donation.
* **Accept/commit runs on device.**  The longest-prefix draft-acceptance
  match, bonus-token select and the ``slot_pos`` rollback of rejected
  draft positions all happen inside the jitted step; the SSM
  accepted-prefix replay is a masked second forward under ``lax.cond``
  in the same jit rather than a host round-trip.
* **The host reads one tiny array block per step.**  ``dispatch_step``
  only enqueues device work (JAX async dispatch) and returns a
  :class:`StepTicket`; ``commit_step`` performs the single
  ``jax.device_get`` of ``(sampled, logprobs, n_accepted)`` — counted in
  ``StepFunctions.host_syncs`` — and folds the results into host state.
  Between a dispatch and its commit the instance must not admit or
  release slots (enforced).

KV migration (divided rollout's chunk moves)
--------------------------------------------

``migration_mode="batched"`` (default) makes blob movement through the
global pool a batched, compute-overlapped subsystem:

* **Batched export.**  ``release_async`` only *marks* a slot draining;
  ``flush_exports`` materialises every draining slot's blob in one
  jitted gather (``StepFunctions.export_batch``) that touches each
  cache leaf once regardless of how many slots migrate.  Each blob is
  trimmed (inside the same jit) to its own live prefix bucketed to a
  power-of-two ``prefill_chunk`` multiple, so compiled shapes stay
  log-bounded; entries past the slot's own ``next_pos`` carry
  ``slot_pos == -1``, are never attended, and are excluded from
  ``nbytes`` — pool accounting carries no dead bytes.
* **Overlapped export.**  The gather is enqueued *after* the next
  step's dispatch: the fused step never writes a draining slot's rows
  (they are masked out of the batch), and in-place donation preserves
  them, so the export legally reads the post-step cache while the host
  does commit bookkeeping.  ``export_overlapped_slots`` counts slots
  whose gather was dispatched with a step ticket in flight.
* **Batched import.**  ``admit`` with a blob only *queues* the import;
  ``dispatch_step`` flushes all pending imports in one jitted
  pad+scatter per source extent (``StepFunctions.import_batch``) before
  building the step batch, so K migrated arrivals cost one cache write
  per leaf, not K.
* **Admit-into-draining.**  With ``admit_into_draining`` (default on
  the batched path) a draining slot counts as admittable one tick
  early: ``admit`` stashes the newcomer as a *takeover* whose cache
  writes (clear / blob import) are deferred, and the next
  ``dispatch_step`` snapshots (exports) the draining rows first, then
  applies the clears and imports, then steps — the new seq runs in the
  very step that frees its slot.  Early-gathered blobs wait in an
  export buffer and are returned by the next ``flush_exports``.
* **Invariants.**  A blob whose position extent exceeds the target
  cache raises (live positions are never silently truncated); a
  taken-over slot's pending import never lands before its draining
  rows are snapshotted; ``migration_mode="perslot"`` keeps the PR 2
  one-``jnp.take``-per-leaf path as the launch-count baseline and
  equivalence oracle.

Tree speculation (``spec_mode="tree"``)
---------------------------------------

Multi-path CST drafts are verified as *token trees* in one fused step:

* drafts arrive as :class:`~repro.engine.token_tree.TokenTree` values
  (or plain lists, treated as single-path trees — bit-identical to the
  linear path, which stays the oracle as ``spec_mode="linear"``);
* tree nodes occupy the verify columns after the anchor in topological
  order, each written to its own cache slot (``anchor_slot + node
  index`` — sibling nodes share a logical position, and therefore a
  sampling key, but need distinct rows), with an ancestor ``within``
  mask carried through the forward so a node attends exactly the
  committed prefix plus its own root path;
* acceptance generalises the longest-prefix rule to the longest
  accepted *path* (children of one node carry distinct tokens, so the
  accepted set is always a chain), selected on device; the winning
  branch's K/V rows are compacted into the canonical position-indexed
  slots and every rejected node's slot is invalidated inside the same
  donated jit; sampled/logprob outputs are relaid out path-major so
  ``commit_step`` is unchanged and the host still reads one tiny block
  per step.

SSM/hybrid archs verify single-path trees only (a recurrent scan is
linear in the step's columns; sibling branches would corrupt each
other's state) — branching trees on those archs raise.

Step functions are compiled once per (config, T) and shared by every
instance of that model (the paper colocates many instances per model).
``prefill_mode="sync"`` keeps the original admit-time python loop plus
host-side acceptance (one blocking read of the full sample block per
step) as the reference path for losslessness and perf comparisons.
"""
from __future__ import annotations

import time
import zlib
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.engine.sampling import (draft_acceptance, position_keys,
                                   sample_tokens, token_logprobs_at,
                                   tree_acceptance)
from repro.engine.token_tree import TokenTree, bucket_pow2, chain_tree
from repro.models import build_cross_cache, forward, init_cache
from repro.obs.trace import NO_SPAN
from repro.sharding import ShardCtx

_INT32_MAX = np.iinfo(np.int32).max


def _sctx_key(sctx: Optional[ShardCtx]):
    """Step-cache key component for a sharding context.  Engine meshes
    are cached per (degree, devices) (``launch.mesh.engine_mesh``), so
    those are the whole identity — instances of equal tp on the same
    devices share compilations; a mesh on other devices needs its own
    (its sharding annotations name those devices)."""
    if sctx is None:
        return None
    return sctx.tp_size, tuple(d.id for d in sctx.mesh.devices.flat)

_DONATION_SUPPORTED: Optional[bool] = None


def donation_supported() -> bool:
    """Whether the default backend actually reuses donated buffers."""
    global _DONATION_SUPPORTED
    if _DONATION_SUPPORTED is None:
        probe = jnp.zeros((8,), jnp.float32)
        jax.jit(lambda a: a + 1, donate_argnums=(0,))(probe)
        _DONATION_SUPPORTED = bool(probe.is_deleted())
    return _DONATION_SUPPORTED


# ---------------------------------------------------------------------------
# jitted step functions (shared per config)
# ---------------------------------------------------------------------------


class StepFunctions:
    """Compile-once holder for a given model config.

    Every returned callable counts its calls in ``invocations`` (total
    step launches) and ``invocations_by_kind`` ("step:T" / "fused:T" /
    "prefill:T") — the benchmark/regression currency for the batched
    prefill + fused-step work: fewer launches for the same tokens.
    ``host_syncs`` counts blocking device->host reads of step results
    (the other currency: the fused path reads one tiny block per step,
    the sync reference path synchronizes the full sample block).
    """

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._step_cache: dict = {}
        self.invocations = 0
        self.invocations_by_kind: Dict[str, int] = {}
        self.host_syncs = 0
        # device dispatches issued for KV migration (jitted batch calls
        # on the batched path; one per leaf op on the per-slot path) —
        # the launch-count currency of batched migration
        self.migration_calls = 0
        self.migration_calls_by_kind: Dict[str, int] = {}

    def count_migration(self, kind: str, n: int = 1) -> None:
        self.migration_calls += n
        self.migration_calls_by_kind[kind] = \
            self.migration_calls_by_kind.get(kind, 0) + n

    def _counted(self, fn, kind: str):
        def wrapper(*args):
            self.invocations += 1
            self.invocations_by_kind[kind] = \
                self.invocations_by_kind.get(kind, 0) + 1
            return fn(*args)
        wrapper.lower = fn.lower      # the jitted program, uncounted
        return wrapper

    def step(self, T: int, sctx: Optional[ShardCtx] = None):
        """Reference step (no donation, host-side acceptance):
        (params, cache, tokens(B,T), positions, mask, keys, temps,
        sample_rows(B,)) -> (sampled(B,T), logprobs(B,T), new_cache)."""
        key = ("step", T, _sctx_key(sctx))
        if key in self._step_cache:
            return self._step_cache[key]
        cfg = self.cfg

        @jax.jit
        def fn(params, cache, tokens, positions, mask, keys, temps,
               sample_rows):
            logits, new_cache, _ = forward(
                cfg, params, tokens, positions, cache, token_mask=mask,
                sctx=sctx)
            logits = logits.astype(jnp.float32)
            sampled = sample_tokens(logits, keys, temps, sample_rows)
            lp = token_logprobs_at(logits, sampled)
            return sampled, lp, new_cache

        counted = self._counted(fn, f"step:{T}")
        self._step_cache[key] = counted
        return counted

    def tree_step(self, T: int, sctx: Optional[ShardCtx] = None):
        """Reference *tree* step (no donation, host-side acceptance):
        (params, cache, tokens(B,T), positions(B,T), slot_index(B,T),
        mask(B,T), within(B,T,T), keys, temps, sample_rows(B,)) ->
        (sampled(B,T), logprobs(B,T), new_cache).

        The forward is identical to :meth:`fused_tree_step`'s; acceptance,
        the winning-branch KV compaction and node-slot invalidation run on
        the *host* (``_run_step_sync_tree``) so branching tree steps can be
        cross-checked token-exactly against the fused path."""
        key = ("tree_ref", T, _sctx_key(sctx))
        if key in self._step_cache:
            return self._step_cache[key]
        cfg = self.cfg

        @jax.jit
        def fn(params, cache, tokens, positions, slot_index, mask,
               within, keys, temps, sample_rows):
            logits, new_cache, _ = forward(
                cfg, params, tokens, positions, cache, token_mask=mask,
                slot_index=slot_index, within_mask=within, sctx=sctx)
            logits = logits.astype(jnp.float32)
            sampled = sample_tokens(logits, keys, temps, sample_rows)
            lp = token_logprobs_at(logits, sampled)
            return sampled, lp, new_cache

        counted = self._counted(fn, f"tree_ref:{T}")
        self._step_cache[key] = counted
        return counted

    def fused_step(self, T: int, sctx: Optional[ShardCtx] = None):
        """Device-resident step with donated cache and on-device
        accept/commit.

        (params, cache, tokens(B,T), positions, mask, keys, temps,
        sample_rows(B,), anchor(B,), n_drafts(B,)) ->
        (sampled(B,T), logprobs(B,T), n_accepted(B,), new_cache)

        Row layout: column ``anchor[i]`` holds the row's pending token
        (0 for plain decode/verify rows; the tail-fused first-decode row
        puts its pending token after the last prefill-chunk column);
        columns ``anchor+1 .. anchor+n_drafts`` hold draft tokens.  The
        returned cache already has rejected draft positions invalidated
        (``slot_pos`` rollback) and, on SSM/hybrid archs, the recurrent
        state replayed over the accepted prefix only — the host never
        touches the cache between steps.
        """
        key = ("fused", T, _sctx_key(sctx))
        if key in self._step_cache:
            return self._step_cache[key]
        cfg = self.cfg

        def raw(params, cache, tokens, positions, mask, keys, temps,
                sample_rows, anchor, n_drafts):
            has_rec = "ssm" in cache
            pre_rec = {k: cache[k] for k in ("ssm", "conv")
                       if k in cache}
            logits, new_cache, _ = forward(
                cfg, params, tokens, positions, cache, token_mask=mask,
                sctx=sctx)
            logits = logits.astype(jnp.float32)
            with jax.named_scope("sample"):
                sampled = sample_tokens(logits, keys, temps, sample_rows)
                lp = token_logprobs_at(logits, sampled)
            with jax.named_scope("accept"):
                n_acc = draft_acceptance(sampled, tokens, anchor, n_drafts)
                # on-device commit: the accepted chain of row i covers
                # positions [pos(anchor), pos(anchor)+n_acc]; invalidate every
                # cache slot beyond it (rejected drafts)
                anchor_pos = jnp.take_along_axis(
                    positions, anchor[:, None], axis=1)[:, 0]
                committed_end = jnp.where(
                    sample_rows, anchor_pos + n_acc + 1, _INT32_MAX)
                if "slot_pos" in new_cache:
                    new_cache["slot_pos"] = jnp.where(
                        new_cache["slot_pos"] >= committed_end[:, None], -1,
                        new_cache["slot_pos"])
                if has_rec and T > 1:
                    # SSM states advanced through *rejected* draft tokens
                    # cannot be invalidated by slot masking — replay the
                    # accepted prefix from the pre-step recurrent state as a
                    # masked second pass in the same jit (beyond-paper:
                    # spec-decode on SSM/hybrid archs; see DESIGN.md).
                    # Prefill rows keep their full mask: every chunk token is
                    # "accepted" and the replay recomputes their state
                    # identically.
                    cols = jnp.arange(T)[None, :]
                    acc_mask = mask & jnp.where(
                        sample_rows[:, None],
                        cols <= (anchor + n_acc)[:, None], True)

                    def replay(nc):
                        c2 = dict(nc)
                        c2.update(pre_rec)
                        _, c3, _ = forward(cfg, params, tokens, positions,
                                           c2, token_mask=acc_mask,
                                           sctx=sctx)
                        return c3

                    new_cache = jax.lax.cond(
                        jnp.any(acc_mask != mask), replay, lambda nc: nc,
                        new_cache)
            return sampled, lp, n_acc, new_cache

        # the program's name in HLO and on the profiler's device plane
        raw.__name__ = f"seer_step_t{T}"
        fn = jax.jit(raw, donate_argnums=(1,))
        counted = self._counted(fn, f"fused:{T}")
        self._step_cache[key] = counted
        return counted

    def fused_tree_step(self, T: int, sctx: Optional[ShardCtx] = None):
        """Device-resident *tree*-verify step: multi-path CST drafts
        merged into one token tree per row, verified in a single fused
        forward with everything committed on device.

        (params, cache, tokens(B,T), positions(B,T), slot_index(B,T),
        mask(B,T), within(B,T,T), keys, temps, sample_rows(B,),
        anchor(B,), parent(B,T), depth(B,T)) ->
        (sampled(B,T), logprobs(B,T), n_accepted(B,), new_cache)

        Row layout: column ``anchor[i]`` holds the row's pending token;
        tree nodes follow in topological order, each written to cache
        slot ``slot_index`` (laid out after the anchor so sibling nodes
        at one logical position get distinct rows) and attending its
        ancestors only via ``within``.  On device: longest accepted
        *path* selection (:func:`tree_acceptance`), KV compaction of the
        winning branch into the canonical position-indexed slots,
        ``slot_pos`` invalidation of every rejected node, the SSM
        accepted-path replay, and a path-major relayout of
        sampled/logprobs — the host reads columns ``0..n_accepted`` of
        the returned block exactly as it does on the linear path.  With
        a single-path tree this computes bit-identically to
        :meth:`fused_step` (the exactness oracle tests assert it).
        """
        key = ("tree", T, _sctx_key(sctx))
        if key in self._step_cache:
            return self._step_cache[key]
        cfg = self.cfg
        ring = cfg.sliding_window > 0

        def raw(params, cache, tokens, positions, slot_index, mask,
                within, keys, temps, sample_rows, anchor, parent, depth):
            B = tokens.shape[0]
            has_rec = "ssm" in cache
            pre_rec = {k: cache[k] for k in ("ssm", "conv")
                       if k in cache}
            logits, new_cache, _ = forward(
                cfg, params, tokens, positions, cache, token_mask=mask,
                slot_index=slot_index, within_mask=within, sctx=sctx)
            logits = logits.astype(jnp.float32)
            with jax.named_scope("sample"):
                sampled = sample_tokens(logits, keys, temps, sample_rows)
                lp = token_logprobs_at(logits, sampled)
            with jax.named_scope("accept"):
                n_acc, path_col, acc = tree_acceptance(
                    sampled, tokens, parent, depth, within, mask, anchor)
                n_acc = jnp.where(sample_rows, n_acc, 0)
                # path-major relayout: column d of the output holds the
                # sample/logprob at the accepted path's depth-d node, so the
                # host commit is identical to the linear path at offset 0
                out_sampled = jnp.take_along_axis(sampled, path_col, axis=1)
                out_lp = jnp.take_along_axis(lp, path_col, axis=1)
                anchor_pos = jnp.take_along_axis(
                    positions, anchor[:, None], axis=1)[:, 0]
                if "slot_pos" in new_cache:
                    S = new_cache["slot_pos"].shape[1]
                    bidx = jnp.arange(B)[:, None]
                    # 1) invalidate every tree-node slot (this step's
                    # writes); 2) re-commit the winning branch into the
                    # canonical slots (slot == position, mod ring) so the
                    # cache looks exactly as if the accepted chain had been
                    # decoded linearly
                    node_slots = jnp.where((depth > 0) & mask, slot_index, S)
                    sp = new_cache["slot_pos"].at[bidx, node_slots].set(
                        -1, mode="drop")
                    dcols = jnp.arange(T, dtype=jnp.int32)[None, :]
                    dvalid = (dcols >= 1) & (dcols <= n_acc[:, None]) \
                        & sample_rows[:, None]
                    src = jnp.where(
                        dvalid,
                        jnp.take_along_axis(slot_index, path_col, axis=1), S)
                    dst_pos = anchor_pos[:, None] + dcols
                    dst = jnp.where(dvalid, dst_pos % S if ring else dst_pos,
                                    S)
                    new_cache["slot_pos"] = sp.at[bidx, dst].set(
                        dst_pos, mode="drop")
                    src_c = jnp.clip(src, 0, S - 1)
                    for kk in ("k", "v"):
                        kv = new_cache[kk]            # (L, B, S, H, D)
                        vals = jnp.take_along_axis(
                            kv, src_c[None, :, :, None, None], axis=2)
                        new_cache[kk] = kv.at[:, bidx, dst].set(
                            vals, mode="drop")
                if has_rec and T > 1:
                    # recurrent state advanced through rejected tree nodes:
                    # replay the accepted path (anchor + accepted chain, in
                    # column order = topological order) from the pre-step
                    # state; prefill rows keep their full mask
                    cols = jnp.arange(T)[None, :]
                    keep = mask & jnp.where(
                        sample_rows[:, None],
                        (cols <= anchor[:, None]) | acc, True)

                    def replay(nc):
                        c2 = dict(nc)
                        c2.update(pre_rec)
                        _, c3, _ = forward(cfg, params, tokens, positions,
                                           c2, token_mask=keep,
                                           slot_index=slot_index,
                                           within_mask=within, sctx=sctx)
                        return c3

                    new_cache = jax.lax.cond(
                        jnp.any(keep != mask), replay, lambda nc: nc,
                        new_cache)
            return out_sampled, out_lp, n_acc, new_cache

        raw.__name__ = f"seer_tree_step_t{T}"
        fn = jax.jit(raw, donate_argnums=(1,))
        counted = self._counted(fn, f"tree:{T}")
        self._step_cache[key] = counted
        return counted

    def prefill(self, T: int, sctx: Optional[ShardCtx] = None):
        key = ("prefill", T, _sctx_key(sctx))
        if key in self._step_cache:
            return self._step_cache[key]
        cfg = self.cfg

        @jax.jit
        def fn(params, cache, tokens, positions, mask):
            _, new_cache, _ = forward(
                cfg, params, tokens, positions, cache, token_mask=mask,
                sctx=sctx)
            return new_cache

        counted = self._counted(fn, f"prefill:{T}")
        self._step_cache[key] = counted
        return counted

    def export_batch(self, lives: Tuple[int, ...],
                     sctx: Optional[ShardCtx] = None):
        """Jitted multi-slot KV gather: ``(cache, slots(n,)) -> [blob
        leaf dict] * n``.

        Each cache leaf is read by exactly one gather no matter how many
        slots migrate; blob ``i``'s position-indexed leaves are then
        trimmed (inside the same jit — still one dispatch) to
        ``lives[i]``, capped at the leaf's own extent (ring caches are
        shorter).  Outputs are fresh buffers, never aliases of the
        (donated) instance cache.  Compiled once per ``lives`` tuple;
        callers bucket each live extent (powers of two) and pass the
        tuple in canonical non-decreasing order so the key space is the
        multiset of buckets, keeping compiled variants bounded.

        On a meshed instance the blobs are forced fully replicated
        (``out_shardings = P()``): the all-gather over the head axis
        happens *inside* this jit, so exported blobs always carry the
        canonical unsharded host layout regardless of the source's tp
        degree — headers, nbytes and CRCs are tp-invariant, and any
        instance (tp=1, tp=4, unmeshed) can import them."""
        key = ("export", lives, _sctx_key(sctx))
        if key in self._step_cache:
            return self._step_cache[key]

        jit_kwargs = {}
        if sctx is not None:
            jit_kwargs["out_shardings"] = NamedSharding(sctx.mesh, P())

        @partial(jax.jit, **jit_kwargs)
        def seer_export(cache, slots):
            gathered = {}
            for k, v in cache.items():
                sax = _slot_slice(k)
                gathered[k] = jnp.moveaxis(
                    jnp.take(v, slots, axis=sax), sax, 0)
            out = []
            for i, live in enumerate(lives):
                leaves = {}
                for k, g in gathered.items():
                    row = g[i]
                    ax = _pos_axis(k)
                    if ax is not None:
                        row = jax.lax.slice_in_dim(
                            row, 0, min(live, row.shape[ax]), axis=ax)
                    leaves[k] = row
                out.append(leaves)
            return out

        self._step_cache[key] = seer_export
        return seer_export

    def import_batch(self, sctx: Optional[ShardCtx] = None):
        """Jitted multi-slot KV scatter: ``(cache, slots(n,), [blob leaf
        dict] * n) -> new_cache``.

        Blobs are stacked, padded back to the cache's position extent
        (``slot_pos`` with -1 so dead entries stay invalid, K/V with
        zeros) and written with one scatter per leaf — K migrated
        arrivals cost one cache write per leaf, not K.  The cache is
        donated, matching the step path's in-place contract.  Shared
        across batch sizes/extents (jit recompiles per shape).

        Blobs arrive in the canonical replicated layout (see
        :meth:`export_batch`); on a meshed instance the scatter output
        keeps the destination cache's head-sharded placement (GSPMD
        propagates it from the donated cache operand), so the re-shard
        of imported bytes happens inside this jit with no host sync."""
        key = ("import_batch", _sctx_key(sctx))
        if key in self._step_cache:
            return self._step_cache[key]

        def seer_import(cache, slots, blobs):
            new = dict(cache)
            for k in cache:
                sax = _slot_slice(k)
                src = jnp.stack([b[k] for b in blobs])
                pax = _pos_axis(k)
                if pax is not None:
                    pad = cache[k].shape[pax + 1] - src.shape[pax + 1]
                    if pad > 0:
                        widths = [(0, 0)] * src.ndim
                        widths[pax + 1] = (0, pad)
                        fill = -1 if k == "slot_pos" else 0
                        src = jnp.pad(src, widths, constant_values=fill)
                idx = [slice(None)] * cache[k].ndim
                idx[sax] = slots
                new[k] = cache[k].at[tuple(idx)].set(
                    jnp.moveaxis(src, 0, sax).astype(cache[k].dtype))
            return new

        fn = jax.jit(seer_import, donate_argnums=(0,))
        self._step_cache[key] = fn
        return fn

    @property
    def rollback(self):
        key = "rollback"
        if key in self._step_cache:
            return self._step_cache[key]

        @jax.jit
        def fn(slot_pos, from_pos):
            # invalidate every cache slot holding a position >= from_pos
            return jnp.where(slot_pos >= from_pos[:, None], -1, slot_pos)

        self._step_cache[key] = fn
        return fn


# ---------------------------------------------------------------------------
# per-request engine state
# ---------------------------------------------------------------------------


@dataclass
class EngineSeq:
    req_id: str
    group_id: str
    prompt: List[int]
    seed: int
    temperature: float = 1.0
    max_new_tokens: int = 256
    stop_token: Optional[int] = None
    # mutable generation state
    generated: List[int] = field(default_factory=list)
    logprobs: List[float] = field(default_factory=list)
    last_token: int = -1          # pending token (fed on next step)
    next_pos: int = 0             # position of the pending token
    finished: bool = False
    # queued prefill work (batched prefill): tokens not yet written to the
    # KV cache, and the absolute position of the first of them.  While the
    # queue is non-empty the slot submits prefill chunks instead of
    # decode rows; ``next_pos``/``last_token`` already hold the resume
    # state, so KV accounting sees the full footprint from admission.
    prefill_queue: List[int] = field(default_factory=list)
    prefill_pos: int = 0
    # prefix-revalidation queue (truncate-mode weight refresh): tokens
    # generated under the OLD params, replayed as verify drafts under
    # the new ones — accepted prefixes are re-committed without paying a
    # decode step per token, and the first divergence drops the rest.
    # Consumed by the rollout's draft collection; empty in steady state.
    reval_queue: List[int] = field(default_factory=list)

    @property
    def prefilling(self) -> bool:
        return bool(self.prefill_queue)

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.generated)

    def finish_reason(self) -> str:
        if self.stop_token is not None and self.generated and \
                self.generated[-1] == self.stop_token:
            return "stop"
        return "length"


@dataclass
class KVBlob:
    """Exported per-request cache state (what the global pool stores).

    Position-indexed leaves (k/v/slot_pos) are trimmed to the live
    prefix ``[0, min(next_pos, cache_len))`` — batched exports round
    the array extent up to a bucketed shape (entries past ``next_pos``
    carry ``slot_pos == -1``, never attended), but ``nbytes`` always
    counts the live prefix only, so pool accounting and migration
    byte counters move no dead bytes.  Recurrent leaves (ssm/conv)
    have no position axis and ship whole.
    """
    req_id: str
    arrays: dict                  # cache leaves sliced at the slot
    next_pos: int
    nbytes: int
    # CRC32 over the blob *header* (req_id, next_pos, nbytes and every
    # leaf's name/shape/dtype) — the metadata that decides where import
    # scatters the bytes.  A corrupted header is the failure mode that
    # silently lands KV at garbage positions; content checksums over the
    # device arrays would force a device->host sync per exported blob
    # and break both export overlap and the 1-host-sync contract, so the
    # header is the integrity boundary.  Stamped by the pool on put,
    # verified by ``Instance`` before any import-side mutation.
    checksum: Optional[int] = None

    def header_crc(self) -> int:
        parts = [self.req_id, str(self.next_pos), str(self.nbytes)]
        for name in sorted(self.arrays):
            leaf = self.arrays[name]
            parts.append(f"{name}:{tuple(leaf.shape)}:{leaf.dtype}")
        return zlib.crc32("|".join(parts).encode()) & 0xFFFFFFFF

    def stamp_checksum(self) -> "KVBlob":
        """Idempotent: (re)stamps ``checksum`` from the current header."""
        self.checksum = self.header_crc()
        return self

    def verify_checksum(self) -> None:
        """Raise :class:`BlobCorruptionError` on a stamp/header mismatch.
        Unstamped blobs (``checksum is None``, e.g. hand-built in tests
        or never pooled) pass — there is nothing to verify against."""
        if self.checksum is not None and self.checksum != self.header_crc():
            raise BlobCorruptionError(
                f"KV blob for {self.req_id!r} failed checksum validation "
                f"(stored 0x{self.checksum:08x} != computed "
                f"0x{self.header_crc():08x}); refusing to import at "
                f"possibly-garbage positions")


class BlobCorruptionError(RuntimeError):
    """A pooled KV blob's checksum no longer matches its header.

    Raised instead of importing the blob — scattering bytes whose
    position metadata is untrustworthy corrupts live cache rows.  The
    rollout treats this like a failed fetch: retry with backoff, then
    degrade to replay-based recovery."""


# ---------------------------------------------------------------------------
# instance
# ---------------------------------------------------------------------------


def _slot_slice(key: str):
    """Cache leaves carry the slot (batch) dim at 0 or 1."""
    return 0 if key == "slot_pos" else 1


def _pos_axis(key: str) -> Optional[int]:
    """Axis of the cache-position dim in a per-slot blob leaf, or None
    for leaves without one (recurrent state, cross-attention memory)."""
    return {"k": 1, "v": 1, "slot_pos": 0}.get(key)


def _live_nbytes(leaves: dict, next_pos: int) -> int:
    """Byte footprint of a blob counting only the live prefix
    ``[0, next_pos)`` along each position axis — batched-export leaves
    may be padded past it to a bucketed extent, but the padding
    (``slot_pos == -1``, never attended) is dead weight the pool must
    not account."""
    total = 0
    for k, v in leaves.items():
        n = v.size
        ax = _pos_axis(k)
        if ax is not None and v.shape[ax]:
            n = n // v.shape[ax] * min(next_pos, v.shape[ax])
        total += n * v.dtype.itemsize
    return total


@dataclass
class StepTicket:
    """In-flight device step: everything ``commit_step`` needs to fold
    the (still-async) results into host state.  ``sampled``/``lps``/
    ``n_acc`` are device arrays; reading them is the one host sync."""
    sampled: jax.Array
    lps: jax.Array
    n_acc: jax.Array
    sample_slots: List[int]           # decode rows + tail-fused rows
    anchors: Dict[int, int]           # slot -> column of its pending token


@dataclass
class _SyncTicket:
    """Already-committed result of the sync reference path."""
    out: Dict[int, Tuple[List[int], List[float], int]]


@dataclass
class _TreeBatch:
    """One built tree-verify step batch, shared by the fused device path
    and the sync reference path (identical layout => token-exact
    cross-checks)."""
    T: int
    fused: List[int]
    anchors: Dict[int, int]
    trees: Dict[int, TokenTree]
    n_tree_nodes: int
    tokens: np.ndarray
    positions: np.ndarray
    slot_index: np.ndarray
    mask: np.ndarray
    within: np.ndarray
    temps: np.ndarray
    seeds: np.ndarray
    sample_rows: np.ndarray
    anchor: np.ndarray
    parent: np.ndarray
    depth: np.ndarray


class Instance:
    """One inference instance (a model replica with its own KV buffer)."""

    def __init__(self, cfg: ModelConfig, params, steps: StepFunctions, *,
                 tp: Optional[int] = None,
                 devices: Optional[Sequence] = None,
                 max_slots: int = 8, cache_len: int = 4096,
                 prefill_chunk: int = 64, gamma_max: int = 8,
                 prefill_mode: str = "batched",
                 prefill_budget: Optional[int] = None,
                 migration_mode: Optional[str] = None,
                 spec_mode: str = "linear",
                 cost_model=None, prefill_latency_factor: float = 2.0,
                 instance_id: str = "inst0", node: str = "n0",
                 admit_into_draining: Optional[bool] = None,
                 base_seed: int = 0,
                 modality_embeds=None):
        if prefill_mode not in ("batched", "sync"):
            raise ValueError(f"prefill_mode={prefill_mode!r}")
        if spec_mode not in ("linear", "tree"):
            raise ValueError(f"spec_mode={spec_mode!r}")
        if migration_mode is None:
            # the sync reference path keeps the PR 2 per-slot moves
            migration_mode = "perslot" if prefill_mode == "sync" \
                else "batched"
        if migration_mode not in ("batched", "perslot"):
            raise ValueError(f"migration_mode={migration_mode!r}")
        self.cfg = cfg
        self.params = params
        self.steps = steps
        self.max_slots = max_slots
        self.cache_len = cache_len
        self.prefill_chunk = prefill_chunk
        self.gamma_max = gamma_max
        self.prefill_mode = prefill_mode
        self.migration_mode = migration_mode
        # "tree": decode rows verify multi-path draft token trees in one
        # fused step (drafts may be TokenTree values); "linear" keeps
        # the single-chain verify as the oracle path
        self.spec_mode = spec_mode
        # Sarathi-style cap on prefill tokens admitted into one mixed
        # step (bounds decode-row latency).  None + a cost model =
        # adaptive: _prefill_plan caps the *modeled mixed-step latency*
        # at ``prefill_latency_factor`` x the decode-only step instead
        # of capping tokens; None without a cost model = one chunk per
        # slot (no throttle).
        self.prefill_budget = prefill_budget
        self.cost_model = cost_model
        self.prefill_latency_factor = prefill_latency_factor
        self.instance_id = instance_id
        # which host this instance lives on: the KV pool charges
        # cross-node fetches the inter-node fabric hop, and the
        # scheduler ranks placements by that cost
        self.node = node
        # optional flight-recorder hook (repro.obs.Tracer); hooks only
        # record host-side metadata already in hand — never a device
        # read — so the 1-host-sync-per-step contract is untouched
        self.tracer = None
        if admit_into_draining is None:
            admit_into_draining = (migration_mode == "batched"
                                   and prefill_mode == "batched")
        elif admit_into_draining and (migration_mode != "batched"
                                      or prefill_mode != "batched"):
            # takeovers defer the newcomer's cache writes to the next
            # batched dispatch; the sync/per-slot paths would write the
            # slot before its draining rows are snapshotted
            raise ValueError(
                "admit_into_draining requires prefill_mode='batched' "
                "and migration_mode='batched'")
        # admit-into-draining: a draining slot counts as admittable one
        # tick early; the new seq's import/clear is deferred until the
        # next dispatch snapshots (exports) the draining rows first
        self.admit_into_draining = admit_into_draining
        # tensor-parallel mesh: tp=None is today's unmeshed single-device
        # path (sctx None end to end — bit-identical to the pre-tp
        # engine); tp>=1 builds a per-instance (tp,)-over-"model" mesh,
        # commits params + cache to head-sharded NamedShardings and
        # threads the ShardCtx into every StepFunctions getter.  tp=1 is
        # the degenerate meshed case: every constraint is a full-
        # replication annotation, so the step math is bit-identical to
        # tp=None (the oracle gate in check_bench.py asserts it).
        #
        # ``devices`` places the instance: one device for tp=None, the
        # ``tp`` mesh devices otherwise.  None keeps the default layout
        # (unmeshed: wherever params live, i.e. the default device;
        # meshed: the first tp devices).
        self.tp = tp
        if devices is not None and len(devices) != (tp or 1):
            raise ValueError(
                f"instance with tp={tp} given {len(devices)} devices")
        # where blob imports land on an unmeshed instance
        self.device = devices[0] if devices is not None and tp is None \
            else jax.devices()[0]
        if tp is None:
            self._sctx: Optional[ShardCtx] = None
        else:
            from repro.launch.mesh import engine_mesh, make_engine_shard_ctx
            self._sctx = make_engine_shard_ctx(engine_mesh(
                tp, None if devices is None else tuple(devices)))
        self.base_key = jax.random.PRNGKey(base_seed)
        self.cache = init_cache(cfg, max_slots, cache_len)
        if cfg.arch_type in ("vlm", "audio"):
            if modality_embeds is None:
                from repro.models import modality_inputs
                modality_embeds = next(iter(
                    modality_inputs(cfg, max_slots).values()))
            ck, cv = build_cross_cache(cfg, params, modality_embeds)
            self.cache["cross_k"], self.cache["cross_v"] = ck, cv
        if self._sctx is not None:
            from repro.launch.steps import (engine_cache_shardings,
                                            engine_param_shardings)
            self.params = jax.device_put(
                params, engine_param_shardings(cfg, self._sctx))
            self.cache = jax.device_put(
                self.cache, engine_cache_shardings(self._sctx, self.cache))
        elif devices is not None:
            self.params = jax.device_put(params, self.device)
            self.cache = jax.device_put(self.cache, self.device)
        self.slots: List[Optional[EngineSeq]] = [None] * max_slots
        self._inflight: Optional[StepTicket] = None
        # liveness: a crashed instance refuses all work until replaced.
        # The rollout's recovery path flips this via ``crash()`` (fault
        # injection / watchdog escalation) and re-homes every victim.
        self.alive = True
        # KV migration state: draining slots hold a released-but-not-yet
        # -exported seq (rows masked out of steps, unavailable to admit);
        # pending imports are admitted blobs not yet scattered into the
        # cache (flushed in one batched call at the next dispatch)
        self._draining: Dict[int, EngineSeq] = {}
        self._pending_imports: List[Tuple[int, KVBlob]] = []
        # admit-into-draining state: slot -> the NEW seq admitted into a
        # still-draining slot (its cache writes are deferred until the
        # draining rows are exported); blobs gathered early (at
        # dispatch, to unblock a takeover) wait here for the next
        # ``flush_exports`` call to hand them to the pool
        self._takeovers: Dict[int, EngineSeq] = {}
        self._pending_clears: List[int] = []
        self._export_buffer: Dict[str, KVBlob] = {}
        # stats
        self.crashes = 0
        self.tokens_generated = 0
        self.steps_run = 0
        self.prefill_tokens = 0
        self.admits = 0
        self.admit_seconds = 0.0
        # migration accounting
        self.slots_exported = 0
        self.slots_imported = 0
        self.takeover_admits = 0
        self.export_overlapped_slots = 0
        self.migration_bytes_out = 0
        self.migration_bytes_in = 0
        self.migration_host_seconds = 0.0
        # row-occupancy accounting: every forward scores max_slots rows;
        # wasted rows = rows carrying neither decode nor prefill work
        self.row_slots_total = 0
        self.row_slots_active = 0
        # column occupancy: every forward scores max_slots x T columns;
        # active = the step's mask (decode, draft and prefill tokens)
        self.cols_total = 0
        self.cols_active = 0
        self.prefill_rows_packed = 0   # chunk-rows of prefill work issued
        self.tail_fused_rows = 0       # tail chunks fused with 1st decode
        # tree-speculation accounting: steps that verified >= 1 tree
        # node, total nodes verified, and nodes on branching (non-chain)
        # trees — the draft-budget currency of tree mode
        self.tree_steps = 0
        self.tree_nodes = 0
        self.tree_branch_nodes = 0

    # -- capacity ------------------------------------------------------------

    def free_slots(self) -> int:
        if not self.alive:
            return 0
        free = sum(s is None for s in self.slots)
        if self.admit_into_draining:
            # a draining slot is admittable one tick early: the next
            # dispatch snapshots its rows before the newcomer's import
            free += sum(1 for i in self._draining
                        if i not in self._takeovers)
        return free

    def pending_takeovers(self) -> List[int]:
        return sorted(self._takeovers)

    def active_slots(self) -> List[int]:
        """Slots carrying step work (draining slots are excluded: their
        seq is released, they only await the batched KV export)."""
        return [i for i, s in enumerate(self.slots)
                if s is not None and i not in self._draining]

    def draining_slots(self) -> List[int]:
        return sorted(self._draining)

    def decode_slots(self) -> List[int]:
        """Slots holding a pending token (prefill complete)."""
        return [i for i, s in enumerate(self.slots)
                if s is not None and not s.prefilling
                and i not in self._draining]

    def prefilling_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots)
                if s is not None and s.prefilling
                and i not in self._draining]

    def queued_prefill_tokens(self) -> int:
        return sum(len(s.prefill_queue)
                   for s in self.slots if s is not None)

    def kv_used_tokens(self) -> int:
        return sum(min(s.next_pos, self.cache_len)
                   for s in self.slots if s is not None)

    def kv_capacity_tokens(self) -> int:
        return self.max_slots * self.cache_len

    def kv_headroom(self) -> float:
        return 1.0 - self.kv_used_tokens() / max(self.kv_capacity_tokens(), 1)

    # -- admission / release ---------------------------------------------------

    def admit(self, seq: EngineSeq, blob: Optional[KVBlob] = None) -> int:
        """Place ``seq`` in a free slot.  Batched mode only *queues* the
        prefill work — O(1), no forward — so K admissions cost K queue
        appends, not K x ceil(len/chunk) single-row forwards; the queued
        chunks ride along with subsequent mixed step batches."""
        if self._inflight is not None and self.prefill_mode != "batched":
            # the batched path tolerates admission with a step in
            # flight: every cache write is either deferred to the next
            # dispatch (queued prefill, batched imports, takeover
            # clears) or a functional update enqueued on the post-step
            # buffers (slot clears, per-slot imports), and the
            # in-flight ticket's sample_slots are disjoint from
            # admittable slots.  That window is what lets the rollout
            # overlap scheduling — and takeover snapshots — with device
            # compute.  The sync path keeps the guard: it block-waits
            # on the cache inside admit.
            raise RuntimeError("admit() while a step ticket is in flight")
        if not self.alive:
            raise RuntimeError("admit() on a crashed instance")
        if blob is not None and blob.next_pos == seq.next_pos:
            # integrity gate BEFORE any slot/cache mutation: a corrupt
            # blob must leave the instance untouched so the caller can
            # retry the fetch or re-admit with blob=None (replay path)
            blob.verify_checksum()
        t0 = time.perf_counter()
        takeover = False
        free = [i for i, s in enumerate(self.slots) if s is None]
        if free:
            slot = free[0]
        else:
            cands = [i for i in self.draining_slots()
                     if i not in self._takeovers]
            if not (self.admit_into_draining and cands):
                raise ValueError("no admittable slot")
            # admit into a draining slot: the old seq is safe in
            # _draining; every cache write (clear / blob import) is
            # deferred until the next dispatch exports the old rows
            slot, takeover = cands[0], True
            self._takeovers[slot] = seq
            self.takeover_admits += 1
        self.slots[slot] = seq
        if takeover:
            self._pending_clears.append(slot)
        else:
            self._clear_slot_cache(slot)
        seq.prefill_queue = []
        seq.prefill_pos = 0
        if blob is not None and blob.next_pos == seq.next_pos:
            self._check_blob_fits(blob)
            self.slots_imported += 1
            self.migration_bytes_in += blob.nbytes
            if self.migration_mode == "batched" \
                    and self.prefill_mode == "batched":
                # queue the import; dispatch_step scatters every pending
                # blob in one batched call per source extent
                self._pending_imports.append((slot, blob))
            else:
                tm = time.perf_counter()
                self._import_kv(slot, blob)
                self.migration_host_seconds += time.perf_counter() - tm
        elif seq.next_pos > 0:
            # no blob (pool miss): re-prefill everything up to next_pos
            tokens = (seq.prompt + seq.generated)[:seq.next_pos]
            self._queue_prefill(slot, seq, tokens, start_pos=0)
        else:
            tokens = seq.prompt[:-1]
            seq.last_token = seq.prompt[-1]
            seq.next_pos = len(seq.prompt) - 1
            self._queue_prefill(slot, seq, tokens, start_pos=0)
        if takeover and self._inflight is not None:
            # takeover-aware overlap: with the previous step still in
            # flight, snapshot the draining rows NOW — the gather
            # enqueues behind that step (it never writes draining rows;
            # donation preserves them), so the export rides the overlap
            # window instead of stalling the next dispatch.  The blob
            # surfaces at the next flush_exports as usual; the
            # newcomer's clear/import stay deferred to the next
            # dispatch.
            self._export_buffer.update(self._gather_exports({slot}))
        if self.prefill_mode == "sync":
            # jit dispatch is async: without a barrier the timer would
            # capture only trace/dispatch time, not the chunk forwards
            jax.block_until_ready(self.cache)
        self.admits += 1
        self.admit_seconds += time.perf_counter() - t0
        return slot

    def release(self, slot: int, export: bool = True) -> Optional[KVBlob]:
        """Immediate release: export (per-slot path) and free the slot.

        The batched alternative for migrating slots is
        :meth:`release_async` + :meth:`flush_exports`."""
        if self._inflight is not None:
            raise RuntimeError("release() while a step ticket is in flight")
        if slot in self._draining:
            raise RuntimeError(f"slot {slot} is already draining")
        # takeover imports must not land before their draining rows are
        # snapshotted — nor before their deferred slot clear runs (an
        # early-gathered takeover is no longer in _takeovers, but its
        # clear is still pending and would wipe an import that landed
        # first); everything else flushes now
        self._flush_imports(exclude=set(self._takeovers)
                            | set(self._pending_clears))
        seq = self.slots[slot]
        self._check_exportable(slot, seq, export)
        blob = None
        if export and seq:
            t0 = time.perf_counter()
            blob = self._export_kv(slot, seq)
            self.slots_exported += 1
            self.migration_bytes_out += blob.nbytes
            self.migration_host_seconds += time.perf_counter() - t0
        self.slots[slot] = None
        return blob

    def release_async(self, slot: int) -> None:
        """Mark a slot draining: its seq is released from stepping, but
        the KV export is deferred to the next :meth:`flush_exports` —
        dispatched right after the next step so the gather overlaps
        device compute.  The slot stays unavailable to ``admit`` until
        the export is flushed."""
        if self._inflight is not None:
            raise RuntimeError(
                "release_async() while a step ticket is in flight")
        if self.migration_mode != "batched":
            raise RuntimeError("release_async() requires "
                               "migration_mode='batched'; use release()")
        seq = self.slots[slot]
        if seq is None or slot in self._draining:
            raise RuntimeError(f"slot {slot} holds no releasable seq")
        self._check_exportable(slot, seq, export=True)
        self._draining[slot] = seq

    def flush_exports(self) -> Dict[str, KVBlob]:
        """Materialise every draining slot's blob and free the slots.

        One jitted gather for the whole batch (each cache leaf touched
        once); each blob is trimmed inside the jit to its own live
        prefix, bucketed to a power-of-two ``prefill_chunk`` multiple so
        compiled shapes stay log-bounded.  ``nbytes`` counts the exact
        live prefix — the sub-bucket padding (``slot_pos == -1``, never
        attended) is not accounted, so pool accounting still carries no
        dead bytes.  Legal while a step ticket is in flight — the step
        never writes draining rows, so the gather reads them unchanged
        from the post-step cache; that is the overlap window.

        Blobs a dispatch already snapshotted early (to unblock an
        admit-into-draining takeover) are returned here too — callers
        see one export stream regardless of when the gather ran."""
        out = dict(self._export_buffer)
        self._export_buffer.clear()
        out.update(self._gather_exports())
        return out

    def cancel_pending_imports(self) -> List[int]:
        """Drop every queued KV-blob import without scattering it into
        the cache (weight refresh: the blobs hold KV computed under the
        OLD params and must not land under the new ones).  Returns the
        slots whose import was cancelled; their seqs still carry
        ``next_pos > 0`` with an empty prefill queue, so the caller must
        re-queue a full re-prefill (the pool-miss path) or truncate."""
        slots = [s for s, _ in self._pending_imports]
        self._pending_imports.clear()
        return slots

    def crash(self) -> List[EngineSeq]:
        """Lose the worker: cache contents, draining export buffers and
        every piece of in-flight bookkeeping are gone.  Returns the seqs
        that were live here (active, prefilling, draining, takeover
        admissions — deduped) so the caller can re-home them; blobs
        sitting in the export buffer are simply lost (their requests
        must recover by replay).  A dead instance refuses ``admit`` and
        ``dispatch_step`` and reports zero free slots until replaced."""
        victims: List[EngineSeq] = []
        seen = set()
        for s in list(self.slots) + list(self._draining.values()):
            if s is not None and id(s) not in seen:
                seen.add(id(s))
                victims.append(s)
        self.alive = False
        self.crashes += 1
        self._inflight = None
        self.slots = [None] * self.max_slots
        self._draining.clear()
        self._takeovers.clear()
        self._pending_imports.clear()
        self._pending_clears.clear()
        self._export_buffer.clear()
        return victims

    @property
    def step_in_flight(self) -> bool:
        return self._inflight is not None

    def _gather_exports(self, only: Optional[set] = None
                        ) -> Dict[str, KVBlob]:
        """Gather draining slots (all, or just ``only``) in one jitted
        call.  Dispatch passes the taken-over subset so the remaining
        draining slots keep their overlap window (flushed behind the
        step as usual)."""
        slots = [i for i in self.draining_slots()
                 if only is None or i in only]
        if not slots:
            return {}
        t0 = time.perf_counter()
        if self._inflight is None:
            # blobs queued for *other* slots must land before the gather
            # reads the cache; imports aimed at taken-over (or cleared-
            # but-not-yet-dispatched) slots wait until the draining rows
            # are snapshotted and the deferred clear has run
            self._flush_imports(exclude=set(self._takeovers)
                                | set(self._pending_clears))
        seqs = [self._draining[i] for i in slots]
        overlapped = self._inflight is not None
        out: Dict[str, KVBlob] = {}
        extents = [v.shape[_pos_axis(k) + 1] for k, v in
                   self.cache.items() if _pos_axis(k) is not None]
        max_ext = max(extents) if extents else 0
        lives = []
        for s in seqs:
            live = min(s.next_pos, max_ext)
            b = max(self.prefill_chunk, 1)
            while b < live:
                b <<= 1
            lives.append(min(b, max_ext) if max_ext else 0)
        # canonical order (by bucketed extent, then slot) so the compile
        # key is a multiset of buckets, not an ordered tuple — (16, 32)
        # and (32, 16) batches share one compiled gather
        order = sorted(range(len(slots)), key=lambda j: (lives[j],
                                                         slots[j]))
        slots = [slots[j] for j in order]
        seqs = [seqs[j] for j in order]
        fn = self.steps.export_batch(tuple(lives[j] for j in order),
                                     self._sctx)
        leaf_dicts = fn(self.cache, jnp.asarray(slots, jnp.int32))
        self.steps.count_migration(f"export:{len(slots)}")
        for seq, leaves in zip(seqs, leaf_dicts):
            out[seq.req_id] = KVBlob(seq.req_id, leaves, seq.next_pos,
                                     _live_nbytes(leaves, seq.next_pos))
        for i in slots:
            if i not in self._takeovers:
                self.slots[i] = None     # taken-over slots hold a new seq
            self._draining.pop(i, None)
            self._takeovers.pop(i, None)
        n = len(slots)
        self.slots_exported += n
        self.export_overlapped_slots += n if overlapped else 0
        self.migration_bytes_out += sum(b.nbytes for b in out.values())
        self.migration_host_seconds += time.perf_counter() - t0
        return out

    def _check_exportable(self, slot: int, seq: Optional[EngineSeq],
                          export: bool) -> None:
        if export and seq is not None and seq.prefilling:
            # a blob must cover [0, next_pos); half-done queued prefill
            # doesn't — callers release mid-prefill only without export,
            # or step until the queue drains and then export
            raise RuntimeError(
                f"slot {slot} ({seq.req_id}) still has queued prefill; "
                "cannot export its KV blob")

    def _check_blob_fits(self, blob: KVBlob) -> None:
        """A blob whose position extent exceeds the target cache would
        silently lose live positions on import (wrapped-ring or
        longer-context source) — refuse loudly; a caller that owns
        mixed-geometry instances must catch this and re-admit the seq
        without the blob (pool-miss re-prefill)."""
        for k, src in blob.arrays.items():
            pax = _pos_axis(k)
            if pax is None or k not in self.cache:
                continue
            tgt = self.cache[k].shape[pax + 1]
            if src.shape[pax] > tgt:
                raise ValueError(
                    f"KV blob {blob.req_id!r}: leaf {k!r} covers "
                    f"{src.shape[pax]} positions but the target cache "
                    f"holds {tgt}; importing would drop live positions "
                    "— re-prefill instead of importing this blob")

    # -- KV migration -----------------------------------------------------------

    def _localize_blob_arrays(self, arrays: dict) -> dict:
        """Re-place blob leaves for this instance's devices.

        A blob exported by a meshed instance is replicated over *that*
        instance's mesh; feeding it straight to a jit whose other
        operands live on a different mesh (or a single device) raises.
        Meshed target: commit every leaf replicated on our mesh — a
        cross-tp-degree re-place with no host sync.  Unmeshed target:
        move leaves that live elsewhere (another instance's device or
        mesh) to this instance's device; leaves already there (and
        hand-built numpy blobs) pass through untouched."""
        if self._sctx is not None:
            sh = NamedSharding(self._sctx.mesh, P())
            return {k: jax.device_put(v, sh) for k, v in arrays.items()}

        def one(v):
            sharding = getattr(v, "sharding", None)
            if sharding is None or sharding.device_set == {self.device}:
                return v
            return jax.device_put(v, self.device)

        return {k: one(v) for k, v in arrays.items()}

    def _export_kv(self, slot: int, seq: EngineSeq) -> KVBlob:
        """Slice the slot's cache state, trimmed to the live prefix.

        ``jnp.take`` / ``lax.slice`` materialise new arrays, so blobs
        never alias the (donated) instance cache."""
        arrays = {}
        nbytes = 0
        for k, v in self.cache.items():
            sl = jnp.take(v, slot, axis=_slot_slice(k))
            self.steps.count_migration("export_perslot")
            ax = _pos_axis(k)
            if ax is not None:
                # ring caches wrap at the buffer size; the live region is
                # [0, next_pos) until the ring fills, then the whole ring
                live = min(seq.next_pos, sl.shape[ax])
                sl = jax.lax.slice_in_dim(sl, 0, live, axis=ax)
                self.steps.count_migration("export_perslot")
            arrays[k] = sl
            nbytes += sl.size * sl.dtype.itemsize
        if self._sctx is not None:
            # canonicalize: gather the head shards so the blob carries
            # the same replicated layout batched exports produce
            sh = NamedSharding(self._sctx.mesh, P())
            arrays = {k: jax.device_put(a, sh) for k, a in arrays.items()}
        return KVBlob(seq.req_id, arrays, seq.next_pos, nbytes)

    def _import_kv(self, slot: int, blob: KVBlob) -> None:
        blob.verify_checksum()     # defense in depth; admit gates too
        self._check_blob_fits(blob)
        arrays = self._localize_blob_arrays(blob.arrays)
        for k in self.cache:
            ax = _slot_slice(k)
            src = arrays[k]
            tshape = list(self.cache[k].shape)
            del tshape[ax]
            pax = _pos_axis(k)
            if pax is not None and src.shape[pax] != tshape[pax]:
                # trimmed blob: pad dead positions back (slot_pos with -1
                # so they stay invalid, K/V with zeros — never attended).
                # A source *longer* than the target was rejected above —
                # truncating it would drop live positions.
                pad = tshape[pax] - src.shape[pax]
                widths = [(0, 0)] * src.ndim
                widths[pax] = (0, pad)
                fill = -1 if k == "slot_pos" else 0
                src = jnp.pad(src, widths, constant_values=fill)
                self.steps.count_migration("import_perslot")
            idx = [slice(None)] * self.cache[k].ndim
            idx[ax] = slot
            self.cache[k] = self.cache[k].at[tuple(idx)].set(src)
            self.steps.count_migration("import_perslot")

    def _flush_imports(self, exclude: Optional[set] = None) -> None:
        """Scatter every pending admitted blob into the cache: one
        batched jitted call per distinct source position extent (blobs
        from one export batch share theirs), each cache leaf written
        once per call.  Imports for slots in ``exclude`` stay pending
        (their draining rows have not been snapshotted yet)."""
        if not self._pending_imports:
            return
        t0 = time.perf_counter()
        pending, self._pending_imports = self._pending_imports, []
        if exclude:
            held = [(s, b) for s, b in pending if s in exclude]
            pending = [(s, b) for s, b in pending if s not in exclude]
            self._pending_imports.extend(held)
            if not pending:
                return
        by_extent: Dict[tuple, List[Tuple[int, KVBlob]]] = {}
        for slot, blob in pending:
            ext = tuple(sorted(
                (k, v.shape[_pos_axis(k)]) for k, v in blob.arrays.items()
                if _pos_axis(k) is not None))
            by_extent.setdefault(ext, []).append((slot, blob))
        tr = self.tracer
        with tr.phase("seer.import", self.instance_id) if tr is not None \
                else NO_SPAN:
            for group in by_extent.values():
                slots = jnp.asarray([s for s, _ in group], jnp.int32)
                blobs = [self._localize_blob_arrays(b.arrays)
                         for _, b in group]
                self.cache = self.steps.import_batch(self._sctx)(
                    self.cache, slots, blobs)
                self.steps.count_migration(f"import:{len(group)}")
        self.migration_host_seconds += time.perf_counter() - t0

    def _clear_slot_cache(self, slot: int) -> None:
        if "slot_pos" in self.cache:
            self.cache["slot_pos"] = \
                self.cache["slot_pos"].at[slot].set(-1)
        if "ssm" in self.cache:
            self.cache["ssm"] = self.cache["ssm"].at[:, slot].set(0.0)
            self.cache["conv"] = self.cache["conv"].at[:, slot].set(0.0)

    # -- prefill -----------------------------------------------------------------

    def _queue_prefill(self, slot: int, seq: EngineSeq,
                       tokens: List[int], start_pos: int) -> None:
        if not tokens:
            return
        if self.prefill_mode == "sync":
            self._prefill_slot(slot, tokens, start_pos)
        else:
            seq.prefill_queue = list(tokens)
            seq.prefill_pos = start_pos

    def _prefill_slot(self, slot: int, tokens: List[int], start_pos: int):
        """Reference path: one single-row forward per chunk at admit time."""
        if not tokens:
            return
        B = self.max_slots
        c = self.prefill_chunk
        fn = self.steps.prefill(c, self._sctx)
        for off in range(0, len(tokens), c):
            chunk = tokens[off:off + c]
            buf = np.zeros((B, c), np.int32)
            pos = np.zeros((B, c), np.int32)
            mask = np.zeros((B, c), bool)
            buf[slot, :len(chunk)] = chunk
            pos[slot, :len(chunk)] = start_pos + off + np.arange(len(chunk))
            mask[slot, :len(chunk)] = True
            self.cache = fn(self.params, self.cache, jnp.asarray(buf),
                            jnp.asarray(pos), jnp.asarray(mask))
            self.prefill_tokens += len(chunk)
            self.row_slots_total += B
            self.row_slots_active += 1
            self.cols_total += mask.size
            self.cols_active += len(chunk)
            self.prefill_rows_packed += 1

    # -- the mixed prefill / decode / verify step ---------------------------------

    def _resolve_prefill_budget(self) -> int:
        """Per-step prefill token budget.  Explicit int -> fixed cap;
        None + cost model -> adaptive (largest chunk-multiple whose
        modeled mixed-step latency stays within
        ``prefill_latency_factor`` x the decode-only step — caps
        latency, not tokens); None without a model -> one chunk per
        slot."""
        if self.prefill_budget is not None:
            return self.prefill_budget
        cap_tokens = self.max_slots * self.prefill_chunk
        cm = self.cost_model
        decode = self.decode_slots() if cm is not None else []
        if cm is None or not decode:
            # nothing decoding -> no latency to protect; drain freely
            return cap_tokens
        B = len(decode)
        mean_ctx = sum(min(self.slots[i].next_pos, self.cache_len)
                       for i in decode) / B
        cap = self.prefill_latency_factor * cm.step_time(B, 1, mean_ctx)
        budget = self.prefill_chunk       # always make chunk progress
        while budget + self.prefill_chunk <= cap_tokens:
            nxt = budget + self.prefill_chunk
            if cm.mixed_step_time(B, 1, nxt, mean_ctx) > cap:
                break
            budget = nxt
        return budget

    def _prefill_plan(self) -> Dict[int, int]:
        """slot -> number of queued prefill tokens to pack this step,
        bounded per-row by ``prefill_chunk`` and per-step by the
        resolved prefill budget (Sarathi-style).  Slots whose *group*
        has no decode-active member on this instance come first
        (decode-starved group priority: their group's DGDS context and
        speculation stall until a member decodes), then shortest
        remaining prefill (ties by slot index) so nearly-ready slots
        reach decode — and release their queue budget — sooner."""
        plan: Dict[int, int] = {}
        # at least one token per step, or prefilling slots starve forever
        budget = max(self._resolve_prefill_budget(), 1)
        decode_groups = {self.slots[i].group_id
                         for i in self.decode_slots()}
        order = sorted(
            self.prefilling_slots(),
            key=lambda i: (self.slots[i].group_id in decode_groups,
                           len(self.slots[i].prefill_queue), i))
        for i in order:
            if budget <= 0:
                break
            n = min(len(self.slots[i].prefill_queue), self.prefill_chunk,
                    budget)
            if n > 0:
                plan[i] = n
                budget -= n
        return plan

    def run_step(self, drafts: Optional[Dict[int, List[int]]] = None
                 ) -> Dict[int, Tuple[List[int], List[float], int]]:
        """One engine iteration over all active slots: dispatch + commit.

        drafts: slot -> draft token list (may be empty; ignored for
        still-prefilling slots).  Returns slot -> (new_tokens, logprobs,
        n_draft_accepted) for sample rows only.
        """
        return self.commit_step(self.dispatch_step(drafts))

    def dispatch_step(self, drafts: Optional[Dict[int, List[int]]] = None):
        """Enqueue one engine step on the device without any host sync.

        Builds a single (max_slots, T) batch in which each row is either
        a decode/verify row (pending token + drafts) or the next prefill
        chunk of a still-prefilling slot — admitting K migrated chunks
        costs ~K rows inside shared forwards instead of K full-batch
        forwards, and prefill no longer head-of-line-blocks decode.  A
        tail chunk that fits T with a column to spare also carries the
        row's pending token and samples its first decode token in the
        same forward.

        Returns a :class:`StepTicket` (or None if there is nothing to
        do) to pass to :meth:`commit_step`; callers may dispatch steps
        on several instances before committing any, overlapping host
        work with device compute.
        """
        tr = self.tracer
        with (tr.phase("seer.dispatch", self.instance_id)
              if tr is not None else NO_SPAN) as span:
            return self._dispatch_step(drafts or {}, span)

    def _dispatch_step(self, drafts, span):
        if self._inflight is not None:
            raise RuntimeError("dispatch_step() with a ticket in flight")
        if not self.alive:
            raise RuntimeError("dispatch_step() on a crashed instance")
        if self.prefill_mode == "sync":
            return _SyncTicket(self._run_step_sync(drafts))
        if self._takeovers:
            # snapshot ONLY the taken-over slots' draining rows so their
            # clears/imports (and this very step) may write them — the
            # admitted seq steps this tick instead of next; the other
            # draining slots keep their overlapped flush window
            self._export_buffer.update(
                self._gather_exports(set(self._takeovers)))
        for slot in self._pending_clears:
            self._clear_slot_cache(slot)
        self._pending_clears.clear()
        self._flush_imports()
        active = self.active_slots()
        if not active:
            return None
        decode = self.decode_slots()
        plan = self._prefill_plan()
        if not decode and not plan:
            return None
        if span is not None:
            span.args.update(decode_rows=len(decode), prefill_rows=len(plan),
                             prefill_tokens=sum(plan.values()))
        if self.spec_mode == "tree":
            return self._dispatch_tree(decode, plan, drafts)
        gamma = max((len(drafts.get(i, [])) for i in decode), default=0)
        gamma = min(gamma, self.gamma_max)
        # bucket gamma to bound the number of compiled step shapes
        for b in (0, 1, 2, 4, 8, 16, 32):
            if gamma <= b:
                gamma = b
                break
        T = gamma + 1
        if plan:
            # bucket the widest planned chunk to a power of two (capped
            # at prefill_chunk) so tail/throttled chunks don't pad every
            # decode row to a full-width forward, while compiled step
            # shapes stay bounded
            need = max(plan.values())
            b = 1
            while b < need:
                b <<= 1
            T = max(T, min(b, self.prefill_chunk))
        B = self.max_slots

        # tail-chunk fusion: a slot whose whole remaining queue fits this
        # step with one column to spare becomes a sample row — its first
        # decode token is emitted by the same forward, saving one full
        # step per admission
        fused = [i for i, n in plan.items()
                 if n == len(self.slots[i].prefill_queue) and n + 1 <= T]

        tokens = np.zeros((B, T), np.int32)
        positions = np.zeros((B, T), np.int32)
        mask = np.zeros((B, T), bool)
        temps = np.zeros((B,), np.float32)
        seeds = np.zeros((B,), np.int32)
        sample_rows = np.zeros((B,), bool)
        anchor = np.zeros((B,), np.int32)
        n_drafts = np.zeros((B,), np.int32)
        anchors: Dict[int, int] = {}
        for i in decode:
            seq = self.slots[i]
            d = list(drafts.get(i, []))[:gamma]
            n_drafts[i] = len(d)
            row = [seq.last_token] + d
            tokens[i, :len(row)] = row
            positions[i, :len(row)] = seq.next_pos + np.arange(len(row))
            mask[i, :len(row)] = True
            temps[i] = seq.temperature
            seeds[i] = seq.seed
            sample_rows[i] = True
            anchors[i] = 0
        for i, n in plan.items():
            seq = self.slots[i]
            tokens[i, :n] = seq.prefill_queue[:n]
            positions[i, :n] = seq.prefill_pos + np.arange(n)
            mask[i, :n] = True
            if i in fused:
                # queue covers [prefill_pos, next_pos): the pending token
                # sits right after the tail chunk
                tokens[i, n] = seq.last_token
                positions[i, n] = seq.next_pos
                mask[i, n] = True
                temps[i] = seq.temperature
                seeds[i] = seq.seed
                sample_rows[i] = True
                anchor[i] = n
                anchors[i] = n

        positions_d = jnp.asarray(positions)
        keys = position_keys(self.base_key, jnp.asarray(seeds), positions_d)
        fn = self.steps.fused_step(T, self._sctx)
        sampled, lps, n_acc, self.cache = fn(
            self.params, self.cache, jnp.asarray(tokens),
            positions_d, jnp.asarray(mask), keys,
            jnp.asarray(temps), jnp.asarray(sample_rows),
            jnp.asarray(anchor), jnp.asarray(n_drafts))
        self.row_slots_total += B
        self.row_slots_active += len(decode) + len(plan)
        self.cols_total += mask.size
        self.cols_active += int(mask.sum())
        self.prefill_rows_packed += len(plan)
        self.tail_fused_rows += len(fused)

        # consume queued prefill that this step just wrote to the cache
        # (host bookkeeping only — no result needed)
        for i, n in plan.items():
            seq = self.slots[i]
            del seq.prefill_queue[:n]
            seq.prefill_pos += n
            self.prefill_tokens += n
        self.steps_run += 1

        ticket = StepTicket(sampled=sampled, lps=lps, n_acc=n_acc,
                            sample_slots=decode + fused, anchors=anchors)
        self._inflight = ticket
        return ticket

    def _dispatch_tree(self, decode: List[int], plan: Dict[int, int],
                       drafts) -> StepTicket:
        """Build and launch one tree-mode fused step.

        Drafts may be :class:`TokenTree` values (multi-path, merged by
        the tree builder) or plain token lists (converted to degenerate
        chain trees, which compute bit-identically to the linear path).
        Tree nodes are laid out after the anchor: column ``1+j`` holds
        node ``j`` (topological order) at cache slot ``next_pos+1+j``,
        logical position ``next_pos+depth[j]`` — sibling nodes share a
        position (and its sampling key) but occupy distinct cache rows,
        with the ancestor ``within`` mask restricting in-step attention.
        Widths are bucketed with the same ladder as linear gamma so
        compiled step shapes stay bounded.
        """
        bt = self._build_tree_batch(decode, plan, drafts)
        positions_d = jnp.asarray(bt.positions)
        keys = position_keys(self.base_key, jnp.asarray(bt.seeds),
                             positions_d)
        fn = self.steps.fused_tree_step(bt.T, self._sctx)
        sampled, lps, n_acc, self.cache = fn(
            self.params, self.cache, jnp.asarray(bt.tokens),
            positions_d, jnp.asarray(bt.slot_index),
            jnp.asarray(bt.mask), jnp.asarray(bt.within), keys,
            jnp.asarray(bt.temps), jnp.asarray(bt.sample_rows),
            jnp.asarray(bt.anchor), jnp.asarray(bt.parent),
            jnp.asarray(bt.depth))
        self.row_slots_total += self.max_slots
        self.row_slots_active += len(decode) + len(plan)
        self.cols_total += bt.mask.size
        self.cols_active += int(bt.mask.sum())
        self.prefill_rows_packed += len(plan)
        self.tail_fused_rows += len(bt.fused)
        self.tree_steps += 1 if bt.n_tree_nodes else 0
        for i, n in plan.items():
            seq = self.slots[i]
            del seq.prefill_queue[:n]
            seq.prefill_pos += n
            self.prefill_tokens += n
        self.steps_run += 1
        ticket = StepTicket(sampled=sampled, lps=lps, n_acc=n_acc,
                            sample_slots=decode + bt.fused,
                            anchors=bt.anchors)
        self._inflight = ticket
        return ticket

    def _build_tree_batch(self, decode: List[int], plan: Dict[int, int],
                          drafts) -> "_TreeBatch":
        """Shared tree-step batch construction (layout, within masks,
        slot indices) for the fused device path and the sync reference
        path — both verify the identical batch, which is what makes the
        host cross-check token-exact."""
        trees: Dict[int, TokenTree] = {}
        widest = 0
        for i in decode:
            d = drafts.get(i)
            t = d if isinstance(d, TokenTree) else chain_tree(d or [])
            cap = min(self.gamma_max,
                      max(0, self.cache_len - 2 - self.slots[i].next_pos))
            if len(t) > cap:
                # topological order: a node-count prefix is a valid tree
                t = TokenTree(tokens=t.tokens[:cap],
                              parent=t.parent[:cap], depth=t.depth[:cap],
                              paths=[p[:cap] for p in t.paths if p[:cap]])
            trees[i] = t
            widest = max(widest, len(t))
        if "ssm" in self.cache and \
                any(not t.is_chain() for t in trees.values()):
            # a recurrent scan is linear in the step's columns: sibling
            # branches would corrupt each other's state.  The rollout's
            # draft gate collapses trees to chains on these archs.
            raise ValueError(
                "branching draft trees require an attention-only arch; "
                "SSM/hybrid instances verify single-path trees only")
        T = bucket_pow2(widest, 32) + 1
        if plan:
            T = max(T, bucket_pow2(max(plan.values()),
                                   self.prefill_chunk))
        B = self.max_slots
        fused = [i for i, n in plan.items()
                 if n == len(self.slots[i].prefill_queue) and n + 1 <= T]
        S = self.cache["slot_pos"].shape[1] if "slot_pos" in self.cache \
            else self.cache_len
        ring = self.cfg.sliding_window > 0

        def to_slot(p):
            return p % S if ring else p

        tokens = np.zeros((B, T), np.int32)
        positions = np.zeros((B, T), np.int32)
        slot_index = np.zeros((B, T), np.int32)
        mask = np.zeros((B, T), bool)
        within = np.zeros((B, T, T), bool)
        temps = np.zeros((B,), np.float32)
        seeds = np.zeros((B,), np.int32)
        sample_rows = np.zeros((B,), bool)
        anchor = np.zeros((B,), np.int32)
        parent = np.full((B, T), -1, np.int32)
        depth = np.zeros((B, T), np.int32)
        anchors: Dict[int, int] = {}
        n_tree_nodes = 0
        for i in decode:
            seq = self.slots[i]
            t = trees[i]
            tokens[i, 0] = seq.last_token
            positions[i, 0] = seq.next_pos
            slot_index[i, 0] = to_slot(seq.next_pos)
            mask[i, 0] = True
            within[i, 0, 0] = True
            anc = t.ancestors_or_self()
            for j, tok in enumerate(t.tokens):
                c = 1 + j
                tokens[i, c] = tok
                positions[i, c] = seq.next_pos + t.depth[j]
                slot_index[i, c] = to_slot(seq.next_pos + 1 + j)
                mask[i, c] = True
                parent[i, c] = 0 if t.parent[j] < 0 else 1 + t.parent[j]
                depth[i, c] = t.depth[j]
                within[i, c, 0] = True
                for a in anc[j]:
                    within[i, c, 1 + a] = True
            temps[i] = seq.temperature
            seeds[i] = seq.seed
            sample_rows[i] = True
            anchors[i] = 0
            n_tree_nodes += len(t)
            self.tree_nodes += len(t)
            if len(t) and not t.is_chain():
                self.tree_branch_nodes += len(t)
        for i, n in plan.items():
            seq = self.slots[i]
            tokens[i, :n] = seq.prefill_queue[:n]
            pos = seq.prefill_pos + np.arange(n)
            positions[i, :n] = pos
            slot_index[i, :n] = to_slot(pos)
            mask[i, :n] = True
            k = n
            if i in fused:
                tokens[i, n] = seq.last_token
                positions[i, n] = seq.next_pos
                slot_index[i, n] = to_slot(seq.next_pos)
                mask[i, n] = True
                temps[i] = seq.temperature
                seeds[i] = seq.seed
                sample_rows[i] = True
                anchor[i] = n
                anchors[i] = 0      # outputs are path-major: offset 0
                k = n + 1
            # prefill chunks are chains by position: plain causal order
            within[i, :k, :k] = np.tril(np.ones((k, k), bool))

        return _TreeBatch(
            T=T, fused=fused, anchors=anchors, trees=trees,
            n_tree_nodes=n_tree_nodes, tokens=tokens, positions=positions,
            slot_index=slot_index, mask=mask, within=within, temps=temps,
            seeds=seeds, sample_rows=sample_rows, anchor=anchor,
            parent=parent, depth=depth)

    def commit_step(self, ticket) -> Dict[int, Tuple[List[int],
                                                     List[float], int]]:
        """Fold a dispatched step's results into host state.

        Performs the step's single host sync: one ``jax.device_get`` of
        the tiny ``(sampled, logprobs, n_accepted)`` block.  Everything
        else (acceptance, rollback, SSM replay) already happened on
        device."""
        if ticket is None:
            return {}
        if isinstance(ticket, _SyncTicket):
            return ticket.out
        if ticket is not self._inflight:
            # committing a stale/duplicate ticket would re-apply its
            # results (duplicated tokens, next_pos past the cache state)
            raise RuntimeError("commit_step(): ticket is not the "
                               "instance's in-flight step")
        self._inflight = None
        with self.tracer.phase("seer.commit_wait", self.instance_id) \
                if self.tracer is not None else NO_SPAN:
            sampled, lps, n_acc = jax.device_get(
                (ticket.sampled, ticket.lps, ticket.n_acc))
        self.steps.host_syncs += 1
        out = {}
        for i in ticket.sample_slots:
            seq = self.slots[i]
            a = int(n_acc[i])
            off = ticket.anchors[i]
            new_toks = [int(sampled[i, off + j]) for j in range(a + 1)]
            new_lps = [float(lps[i, off + j]) for j in range(a + 1)]
            out[i] = self._commit_row(seq, new_toks, new_lps, a)
        return out

    def _commit_row(self, seq: EngineSeq, new_toks: List[int],
                    new_lps: List[float], a: int):
        """Shared host bookkeeping for one sample row's step result."""
        # truncate to request budget / stop token
        room = seq.max_new_tokens - len(seq.generated)
        cut = new_toks[:room]
        if seq.stop_token is not None and seq.stop_token in cut:
            cut = cut[:cut.index(seq.stop_token) + 1]
        new_toks, new_lps = cut, new_lps[:len(cut)]
        seq.generated.extend(new_toks)
        seq.logprobs.extend(new_lps)
        self.tokens_generated += len(new_toks)
        # cache holds positions next_pos .. next_pos+gamma for this row;
        # committed prefix is next_pos .. next_pos+a (len(new_toks) may
        # be shorter due to budget/stop, but those are finished anyway)
        committed_hi = seq.next_pos + a          # highest valid position
        seq.last_token = new_toks[-1] if new_toks else seq.last_token
        seq.next_pos = committed_hi + 1
        if seq.stop_token is not None and new_toks and \
                new_toks[-1] == seq.stop_token:
            seq.finished = True
        if len(seq.generated) >= seq.max_new_tokens:
            seq.finished = True
        if seq.next_pos >= self.cache_len - 1 and not self.cfg.sliding_window \
                and self.cfg.arch_type not in ("ssm",):
            seq.finished = True   # cache exhausted (engine-tier guard)
        return (new_toks, new_lps, a)

    # -- sync reference path (losslessness oracle) --------------------------------

    def _run_step_sync(self, drafts: Dict[int, List[int]]
                       ) -> Dict[int, Tuple[List[int], List[float], int]]:
        """Seed-path step: undonated cache, host-side acceptance over the
        full sample block, host-issued rollback and SSM replay.  Kept
        verbatim as the oracle the fused device path is tested against.

        Tree drafts: a single-path (chain) tree computes bit-identically
        to the linear layout (node ``j`` sits at column/position/slot
        ``1+j`` either way), so chains are flattened to token lists and
        take the linear oracle below; a step carrying any *branching*
        tree routes to :meth:`_run_step_sync_tree`."""
        if self.spec_mode == "tree" or \
                any(isinstance(d, TokenTree) for d in drafts.values()):
            flat: Dict[int, List[int]] = {}
            branching = False
            for i, d in drafts.items():
                if isinstance(d, TokenTree):
                    if d.is_chain():
                        flat[i] = list(d.tokens)
                    else:
                        branching = True
                        break
                else:
                    flat[i] = list(d or [])
            if branching:
                return self._run_step_sync_tree(drafts)
            drafts = flat
        active = self.active_slots()
        if not active:
            return {}
        decode = self.decode_slots()
        plan = self._prefill_plan()
        if not decode and not plan:
            return {}
        gamma = max((len(drafts.get(i, [])) for i in decode), default=0)
        gamma = min(gamma, self.gamma_max)
        for b in (0, 1, 2, 4, 8, 16, 32):
            if gamma <= b:
                gamma = b
                break
        T = gamma + 1
        if plan:
            need = max(plan.values())
            b = 1
            while b < need:
                b <<= 1
            T = max(T, min(b, self.prefill_chunk))
        B = self.max_slots

        tokens = np.zeros((B, T), np.int32)
        positions = np.zeros((B, T), np.int32)
        mask = np.zeros((B, T), bool)
        temps = np.zeros((B,), np.float32)
        seeds = np.zeros((B,), np.int32)
        sample_rows = np.zeros((B,), bool)
        ndraft = {}
        for i in decode:
            seq = self.slots[i]
            d = list(drafts.get(i, []))[:gamma]
            ndraft[i] = len(d)
            row = [seq.last_token] + d
            tokens[i, :len(row)] = row
            positions[i, :len(row)] = seq.next_pos + np.arange(len(row))
            mask[i, :len(row)] = True
            temps[i] = seq.temperature
            seeds[i] = seq.seed
            sample_rows[i] = True
        for i, n in plan.items():
            seq = self.slots[i]
            tokens[i, :n] = seq.prefill_queue[:n]
            positions[i, :n] = seq.prefill_pos + np.arange(n)
            mask[i, :n] = True

        positions_d = jnp.asarray(positions)
        keys = position_keys(self.base_key, jnp.asarray(seeds), positions_d)
        fn = self.steps.step(T, self._sctx)
        has_ssm = "ssm" in self.cache
        pre_ssm = (self.cache["ssm"], self.cache["conv"]) \
            if (has_ssm and gamma > 0) else None
        sampled, lps, self.cache = fn(
            self.params, self.cache, jnp.asarray(tokens),
            positions_d, jnp.asarray(mask), keys,
            jnp.asarray(temps), jnp.asarray(sample_rows))
        sampled = np.asarray(sampled)
        lps = np.asarray(lps)
        self.steps.host_syncs += 2   # full sample + logprob blocks
        self.row_slots_total += B
        self.row_slots_active += len(decode) + len(plan)
        self.cols_total += mask.size
        self.cols_active += int(mask.sum())
        self.prefill_rows_packed += len(plan)

        # consume queued prefill that this step just wrote to the cache
        for i, n in plan.items():
            seq = self.slots[i]
            del seq.prefill_queue[:n]
            seq.prefill_pos += n
            self.prefill_tokens += n

        out = {}
        rollback_from = np.full((B,), _INT32_MAX, np.int32)
        for i in decode:
            seq = self.slots[i]
            d = list(drafts.get(i, []))[:ndraft[i]]
            # acceptance: longest prefix of drafts matching sampled chain
            a = 0
            while a < len(d) and d[a] == int(sampled[i, a]):
                a += 1
            new_toks = [int(sampled[i, j]) for j in range(a + 1)]
            new_lps = [float(lps[i, j]) for j in range(a + 1)]
            rollback_from[i] = seq.next_pos + a + 1
            out[i] = self._commit_row(seq, new_toks, new_lps, a)
        if "slot_pos" in self.cache and gamma > 0:
            self.cache["slot_pos"] = self.steps.rollback(
                self.cache["slot_pos"], jnp.asarray(rollback_from))
        if pre_ssm is not None:
            # SSM states advanced through *rejected* draft tokens cannot be
            # invalidated by slot masking — restore the pre-step recurrent
            # state and replay only the accepted prefix.  Prefill rows keep
            # their full mask: every chunk token is "accepted", and the
            # replay recomputes their state identically.
            accepted_mask = mask.copy()
            for i in decode:
                accepted_mask[i, :] = False
                n_ok = rollback_from[i] - positions[i, 0]
                accepted_mask[i, :n_ok] = True
            if not np.array_equal(accepted_mask, mask):
                self.cache["ssm"], self.cache["conv"] = pre_ssm
                _, _, self.cache = fn(
                    self.params, self.cache, jnp.asarray(tokens),
                    positions_d, jnp.asarray(accepted_mask), keys,
                    jnp.asarray(temps), jnp.asarray(sample_rows))
        self.steps_run += 1
        return out

    def _run_step_sync_tree(self, drafts
                            ) -> Dict[int, Tuple[List[int], List[float],
                                                 int]]:
        """Sync-path *tree* step: the reference (undonated) tree forward
        plus host-side acceptance — a numpy port of
        :func:`~repro.engine.sampling.tree_acceptance` — and host-issued
        node-slot invalidation / winning-branch KV compaction.  Lets the
        oracle cross-check branching ``spec_mode="tree"`` steps
        token-exactly against the fused device path (the batch layout is
        shared via :meth:`_build_tree_batch`)."""
        active = self.active_slots()
        if not active:
            return {}
        decode = self.decode_slots()
        plan = self._prefill_plan()
        if not decode and not plan:
            return {}
        bt = self._build_tree_batch(decode, plan, drafts)
        B, T = self.max_slots, bt.T
        positions_d = jnp.asarray(bt.positions)
        keys = position_keys(self.base_key, jnp.asarray(bt.seeds),
                             positions_d)
        fn = self.steps.tree_step(T, self._sctx)
        sampled_d, lps_d, self.cache = fn(
            self.params, self.cache, jnp.asarray(bt.tokens),
            positions_d, jnp.asarray(bt.slot_index),
            jnp.asarray(bt.mask), jnp.asarray(bt.within), keys,
            jnp.asarray(bt.temps), jnp.asarray(bt.sample_rows))
        sampled = np.asarray(sampled_d)
        lps = np.asarray(lps_d)
        self.steps.host_syncs += 2   # full sample + logprob blocks
        self.row_slots_total += B
        self.row_slots_active += len(decode) + len(plan)
        self.cols_total += bt.mask.size
        self.cols_active += int(bt.mask.sum())
        self.prefill_rows_packed += len(plan)
        self.tail_fused_rows += len(bt.fused)
        self.tree_steps += 1 if bt.n_tree_nodes else 0
        for i, n in plan.items():
            seq = self.slots[i]
            del seq.prefill_queue[:n]
            seq.prefill_pos += n
            self.prefill_tokens += n

        # longest accepted *path* on host — same closed form as the
        # device tree_acceptance: a node is accepted iff every ancestor
        # edge token matches its parent's sample
        node = (bt.depth > 0) & bt.mask
        par = np.clip(bt.parent, 0, T - 1)
        edge_ok = np.where(
            bt.parent >= 0,
            bt.tokens == np.take_along_axis(sampled, par, axis=1), True)
        acc = node & np.all(edge_ok[:, None, :] | ~bt.within, axis=2)
        n_acc = np.max(np.where(acc, bt.depth, 0), axis=1).astype(np.int32)
        n_acc = np.where(bt.sample_rows, n_acc, 0)
        dd = np.arange(T, dtype=np.int32)[None, :]
        hit = acc[:, None, :] & (bt.depth[:, None, :] == dd[:, :, None]) \
            & (dd[:, :, None] > 0)
        path_col = np.where(np.any(hit, axis=2), np.argmax(hit, axis=2),
                            bt.anchor[:, None]).astype(np.int32)
        anchor_pos = np.take_along_axis(
            bt.positions, bt.anchor[:, None], axis=1)[:, 0]

        out = {}
        for i in decode + bt.fused:
            seq = self.slots[i]
            a = int(n_acc[i])
            new_toks = [int(sampled[i, path_col[i, j]])
                        for j in range(a + 1)]
            new_lps = [float(lps[i, path_col[i, j]])
                       for j in range(a + 1)]
            out[i] = self._commit_row(seq, new_toks, new_lps, a)

        # host-issued cache fix-up mirroring fused_tree_step: 1) every
        # tree-node slot written this step is invalidated; 2) the
        # winning branch is re-committed into the canonical
        # position-indexed slots, so the cache looks exactly as if the
        # accepted chain had been decoded linearly
        if "slot_pos" in self.cache and bt.n_tree_nodes:
            S = self.cache["slot_pos"].shape[1]
            ring = self.cfg.sliding_window > 0
            bidx = jnp.arange(B)[:, None]
            node_slots = np.where(node, bt.slot_index, S)
            sp = self.cache["slot_pos"].at[
                bidx, jnp.asarray(node_slots)].set(-1, mode="drop")
            dcols = np.arange(T, dtype=np.int32)[None, :]
            dvalid = (dcols >= 1) & (dcols <= n_acc[:, None]) \
                & bt.sample_rows[:, None]
            src = np.where(
                dvalid,
                np.take_along_axis(bt.slot_index, path_col, axis=1), S)
            dst_pos = anchor_pos[:, None] + dcols
            dst = np.where(dvalid, dst_pos % S if ring else dst_pos, S)
            self.cache["slot_pos"] = sp.at[
                bidx, jnp.asarray(dst)].set(jnp.asarray(dst_pos),
                                            mode="drop")
            src_c = jnp.asarray(np.clip(src, 0, S - 1))
            dst_j = jnp.asarray(dst)
            for kk in ("k", "v"):
                kv = self.cache[kk]            # (L, B, S, H, D)
                vals = jnp.take_along_axis(
                    kv, src_c[None, :, :, None, None], axis=2)
                self.cache[kk] = kv.at[:, bidx, dst_j].set(
                    vals, mode="drop")
        self.steps_run += 1
        return out
