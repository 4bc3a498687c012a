"""Mesh-sharded engine step: per-instance tensor parallelism with the
1-chip path as the bit-exact oracle.

``Instance(tp=None)`` is today's unmeshed path.  ``tp=1`` places params
and cache on a 1-device mesh — the degenerate case must be
bit-identical (same tokens, same host-sync count).  ``tp>1`` runs
head-sharded attention and ff-sharded MLP/MoE under the token-exact
column-parallel scheme (repro.sharding.exact_col_spec): every matmul's
reduction dim stays unsharded, so sampled tokens match the oracle
bitwise under plain, linear-spec and tree-spec decode.  Exported blobs
canonicalize to the unsharded host layout inside the export jit, so
headers/CRCs are tp-invariant and blobs migrate across tp degrees."""
import jax
import numpy as np
import pytest

from repro.core import SeerRollout
from repro.engine import (EngineSeq, Instance, StepFunctions,
                          build_token_tree, chain_tree)

# one arch per family: dense transformer, MoE, SSM-hybrid (tiny configs
# keep 4 heads / 2 kv heads — divisible by tp=2)
TP_ARCHS = ["granite-3-8b", "mixtral-8x7b", "zamba2-1.2b"]
TP = 2


def _seq(rid, prompt, n, temp=1.0, seed=3):
    return EngineSeq(rid, "g0", list(prompt), seed=seed, temperature=temp,
                     max_new_tokens=n)


def _run_pair(cfg, params, steps, tp, n_new=10, gamma_max=4):
    """Two sequences, linear drafts every other step; returns
    (tokens, host_syncs, steps_taken)."""
    inst = Instance(cfg, params, steps, max_slots=2, cache_len=128,
                    gamma_max=gamma_max, prefill_chunk=8, base_seed=7,
                    tp=tp)
    s0 = _seq("r0", [2, 3, 4, 5, 6, 7], n_new, seed=3)
    s1 = _seq("r1", [5, 9, 2], n_new, seed=4)
    slot0 = inst.admit(s0)
    inst.admit(s1)
    syncs0 = steps.host_syncs
    it = 0
    while not (s0.finished and s1.finished):
        drafts = {slot0: [(s0.generated[-1] + 13) % cfg.vocab_size] * 2} \
            if (s0.generated and not s0.finished and it % 2) else {}
        inst.run_step(drafts)
        it += 1
        assert it < 200
    return ([list(s0.generated), list(s1.generated)],
            steps.host_syncs - syncs0, it)


# ---------------- tp=1: the degenerate mesh is bit-identical --------------------


@pytest.mark.parametrize("arch", TP_ARCHS)
def test_tp1_bit_identical_to_unmeshed(arch, tiny_params_cache):
    """tp=1 must change nothing: same tokens, same step count, same
    host-sync count as the unmeshed path (its sharding constraints are
    pure annotations on a 1-device mesh)."""
    cfg, params = tiny_params_cache(arch)
    steps = StepFunctions(cfg)
    ref = _run_pair(cfg, params, steps, tp=None)
    tp1 = _run_pair(cfg, params, steps, tp=1)
    assert tp1[0] == ref[0]
    assert tp1[1] == ref[1]          # host syncs
    assert tp1[2] == ref[2]          # steps


# ---------------- tp=2: token-exact vs the 1-chip oracle ------------------------


@pytest.mark.parametrize("arch", TP_ARCHS)
def test_tp2_token_exact_plain_and_linear_spec(arch, tiny_params_cache):
    """tp=2 samples exactly the oracle's tokens under plain decode and
    linear speculative decode, on every arch family."""
    cfg, params = tiny_params_cache(arch)
    steps = StepFunctions(cfg)
    assert cfg.num_heads % TP == 0 and cfg.num_kv_heads % TP == 0
    ref = _run_pair(cfg, params, steps, tp=None)
    tp2 = _run_pair(cfg, params, steps, tp=TP)
    assert tp2[0] == ref[0]
    assert tp2[2] == ref[2]          # same accept/reject -> same steps
    # plain decode (no drafts at all)
    ref_p = _run_pair(cfg, params, steps, tp=None, gamma_max=0)
    tp2_p = _run_pair(cfg, params, steps, tp=TP, gamma_max=0)
    assert tp2_p[0] == ref_p[0]


def test_tp2_token_exact_tree_spec(tiny_params_cache):
    """tp=2 under tree-speculative decode (branching token trees through
    the fused tree step) commits exactly the oracle's tokens."""
    cfg, params = tiny_params_cache("granite-3-8b")
    steps = StepFunctions(cfg)
    prompt = list(range(2, 14))

    def run(tp, spec_mode, drafts_fn):
        inst = Instance(cfg, params, steps, max_slots=2, cache_len=128,
                        gamma_max=4, prefill_chunk=8,
                        spec_mode=spec_mode, base_seed=7, tp=tp)
        seq = _seq("r0", prompt, 12)
        slot = inst.admit(seq)
        i = 0
        while not seq.finished:
            inst.run_step(drafts_fn(inst, slot, seq, i))
            i += 1
            assert i < 500
        return list(seq.generated)

    ref = run(None, "linear", lambda *a: {})

    def tree_drafts(inst, slot, seq, i):
        if seq.prefilling or not inst.decode_slots():
            return {}
        k = len(seq.generated)
        good = list(ref[k:k + 2])
        if not good:
            return {}
        bad = [(x + 7) % cfg.vocab_size for x in good]
        # branching tree: garbage trunk + matching side branch (the
        # rescue path exercises the within-mask under sharded heads)
        return {slot: build_token_tree([bad, good])}

    def chain_drafts(inst, slot, seq, i):
        if seq.prefilling or not inst.decode_slots():
            return {}
        k = len(seq.generated)
        toks = list(ref[k:k + 3])
        return {slot: chain_tree(toks)} if toks else {}

    assert run(TP, "tree", tree_drafts) == ref
    assert run(TP, "tree", chain_drafts) == ref
    assert run(TP, "tree", lambda *a: {}) == ref


# ---------------- host-sync contract at tp>1 ------------------------------------


def test_tp2_at_most_one_host_sync_per_step(tiny_params_cache):
    """Sharding must not smuggle extra device->host syncs into the step:
    the fused tp=2 step still reads back exactly one tiny block."""
    cfg, params = tiny_params_cache("granite-3-8b")
    steps = StepFunctions(cfg)
    inst = Instance(cfg, params, steps, max_slots=2, cache_len=128,
                    gamma_max=4, prefill_chunk=8, base_seed=7, tp=TP)
    s0 = _seq("r0", [2, 3, 4, 5, 6, 7], 12, seed=3)
    s1 = _seq("r1", [5, 9, 2], 12, seed=4)
    slot0 = inst.admit(s0)
    inst.admit(s1)
    inst.run_step()                       # warm compiles outside the guard
    inst.run_step({slot0: [1, 1]})
    it = 0
    while not (s0.finished and s1.finished):
        syncs0 = steps.host_syncs
        drafts = {slot0: [(s0.generated[-1] + 13) % cfg.vocab_size] * 2} \
            if (s0.generated and not s0.finished and it % 2) else {}
        with jax.transfer_guard_device_to_host("disallow"):
            inst.run_step(drafts)
        assert steps.host_syncs - syncs0 <= 1
        it += 1
        assert it < 200


# ---------------- cross-tp migration --------------------------------------------


def test_blob_headers_tp_invariant(tiny_params_cache):
    """The same request exported from tp=2, tp=1 and unmeshed instances
    yields byte-identical blobs: same header CRC, same nbytes, same
    array bytes (export canonicalizes to the unsharded host layout
    inside the jit)."""
    cfg, params = tiny_params_cache("granite-3-8b")
    steps = StepFunctions(cfg)
    prompt = list(range(2, 14))

    def export_after(tp, n_steps=6):
        inst = Instance(cfg, params, steps, max_slots=2, cache_len=128,
                        gamma_max=0, prefill_chunk=8, base_seed=7,
                        instance_id=f"tp{tp}", tp=tp)
        seq = _seq("r0", prompt, 16, seed=1)
        slot = inst.admit(seq)
        for _ in range(n_steps):
            inst.run_step()
        return inst.release(slot, export=True), seq

    ref_blob, ref_seq = export_after(None)
    for tp in (1, TP):
        blob, seq = export_after(tp)
        assert seq.generated == ref_seq.generated
        assert blob.next_pos == ref_blob.next_pos
        assert blob.nbytes == ref_blob.nbytes
        assert blob.header_crc() == ref_blob.header_crc()
        for name in sorted(ref_blob.arrays):
            a, b = blob.arrays[name], ref_blob.arrays[name]
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["granite-3-8b", "zamba2-1.2b"])
def test_cross_tp_migration_token_exact(arch, tiny_params_cache):
    """A request migrating tp=2 -> tp=1 -> tp=2 (and into an unmeshed
    instance) continues token-exact vs the single-device oracle, with
    checksums verified at every import."""
    cfg, params = tiny_params_cache(arch)
    steps = StepFunctions(cfg)
    prompt = list(range(2, 16))
    n_new = 16

    # unmeshed oracle, no migration
    oracle_inst = Instance(cfg, params, steps, max_slots=2, cache_len=128,
                           gamma_max=0, prefill_chunk=8, base_seed=7)
    oracle = _seq("ref", prompt, n_new, seed=1)
    oracle_inst.admit(oracle)
    while not oracle.finished:
        oracle_inst.run_step()

    seq = _seq("r0", prompt, n_new, seed=1)
    hops = [TP, 1, TP, None]
    inst = Instance(cfg, params, steps, max_slots=2, cache_len=128,
                    gamma_max=0, prefill_chunk=8, base_seed=7,
                    instance_id="hop0", tp=hops[0])
    slot = inst.admit(seq)
    for hop, tp in enumerate(hops[1:], start=1):
        for _ in range(4):
            if seq.finished:
                break
            inst.run_step()
        if seq.finished:
            break
        blob = inst.release(slot, export=True).stamp_checksum()
        nxt = Instance(cfg, params, steps, max_slots=2, cache_len=128,
                       gamma_max=0, prefill_chunk=8, base_seed=7,
                       instance_id=f"hop{hop}", tp=tp)
        slot = nxt.admit(seq, blob)
        assert nxt.prefill_tokens == 0      # blob hit: no re-prefill
        inst = nxt
    while not seq.finished:
        inst.run_step()
    assert seq.generated == oracle.generated


@pytest.mark.parametrize("arch", ["granite-3-8b", "zamba2-1.2b"])
def test_placed_instances_migrate_token_exact(arch, tiny_params_cache):
    """Instances placed with ``devices=`` keep params and cache on their
    own devices, and a request hopping device 1 -> device 2 -> a tp=2
    mesh on devices 3-4 -> device 1 lands each import on the target's
    devices and continues token-exact vs the default-device oracle."""
    cfg, params = tiny_params_cache(arch)
    steps = StepFunctions(cfg)
    prompt = list(range(2, 16))
    n_new = 16
    kw = dict(max_slots=2, cache_len=128, gamma_max=0, prefill_chunk=8,
              base_seed=7)
    oracle_inst = Instance(cfg, params, steps, **kw)
    oracle = _seq("ref", prompt, n_new, seed=1)
    oracle_inst.admit(oracle)
    while not oracle.finished:
        oracle_inst.run_step()

    devs = jax.devices()
    hops = [(None, [devs[1]]), (None, [devs[2]]), (TP, devs[3:5]),
            (None, [devs[1]])]
    seq = _seq("r0", prompt, n_new, seed=1)
    inst, slot = None, None
    for hop, (tp, placed) in enumerate(hops):
        nxt = Instance(cfg, params, steps, instance_id=f"hop{hop}", tp=tp,
                       devices=placed, **kw)
        for leaf in jax.tree.leaves((nxt.params, nxt.cache)):
            assert leaf.sharding.device_set == set(placed)
        if inst is None:
            slot = nxt.admit(seq)
        else:
            blob = inst.release(slot, export=True).stamp_checksum()
            slot = nxt.admit(seq, blob)
            assert nxt.prefill_tokens == 0      # blob hit: no re-prefill
        inst = nxt
        for _ in range(4):
            if not seq.finished:
                inst.run_step()
        for leaf in jax.tree.leaves(inst.cache):
            assert leaf.sharding.device_set == set(placed)
    while not seq.finished:
        inst.run_step()
    assert seq.generated == oracle.generated


def test_instance_devices_must_match_tp(tiny_params_cache):
    cfg, params = tiny_params_cache("granite-3-8b")
    steps = StepFunctions(cfg)
    with pytest.raises(ValueError, match="given 2 devices"):
        Instance(cfg, params, steps, max_slots=1, cache_len=32,
                 devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="need 4 devices, given 3"):
        SeerRollout(cfg, params, steps=steps, n_instances=2, tp=2,
                    max_slots=1, cache_len=32, devices=jax.devices()[:3])


def test_tp_requires_enough_devices(tiny_params_cache):
    """Asking for more tp shards than jax has devices fails with the
    actionable XLA_FLAGS message, not an opaque mesh error."""
    from repro.launch.mesh import engine_mesh
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        engine_mesh(jax.device_count() + 1)
    with pytest.raises(ValueError, match="tp must be >= 1"):
        engine_mesh(0)
