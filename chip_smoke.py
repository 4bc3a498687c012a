"""Smoke test: the divided-rollout server on a TPU v5e, at the published
widths of zamba2-1.2b (38 Mamba2 layers, d_model 2048, a shared
attention block every 6 layers, vocab 32000), with random weights and
traffic made from ``--seed``.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the paths that exist only across chips

One chip: one ``SeerRollout`` iteration with 2 instances of 8 slots and a
2048-position cache, serving 4 GRPO groups x 8 samples (512-token
prompts, 128 to 256 new tokens, greedy, grouped speculative decoding) in
chunks of 64 tokens, so requests migrate between instances.  It checks every
request's tokens and log-probs, that migrations happened and that the
engine read the device at most once per step, then reruns the traffic on
1 instance with no chunking and compares tokens (divided rollout must
not change them; at these widths it does, so the comparison is printed
and not enforced: ROADMAP D13).

Four chips: (a) 4 one-chip instances, one per chip, against the same 4
instances all on chip 0; (b) one tp=4 instance against tp=None on chip
0.  Both comparisons should be token-exact.

Progress and numbers go to earlier lines; the last line is one JSON
object naming the device.  Without a TPU v5e the script fails at once.
Times are host wall-clock seconds, compilation reported apart; none is a
device metric.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "zamba2-1.2b"
# the device the rollout's cost model describes (core/sdmodel.TPU_V5E)
V5E_KIND = "TPU v5 lite"

# each response's length is drawn from --seed in [128, 256]: samples of
# one prompt end at different lengths, so chunk boundaries fall out of
# step and the scheduler moves chunks between instances (in lockstep,
# its deterministic placement hands every chunk back to where it was)
TRAFFIC = dict(groups=4, group_size=8, prompt_len=512, max_new_tokens=256,
               min_new_tokens=128, temperature=0.0)
ONE_CHIP = dict(n_instances=2, max_slots=8, cache_len=2048, chunk_size=64,
                policy="seer", spec_decode=True)
# the four-chip phases check placement and sharding, not load: shorter
# traffic at the same widths (a four-chip second costs four), 16
# requests against 4 x 2 slots so chunks still migrate, and four
# instances that fit on chip 0 together (phase a's reference)
FOUR_CHIP_TRAFFIC = dict(groups=2, group_size=8, prompt_len=128,
                         max_new_tokens=64, min_new_tokens=32,
                         temperature=0.0)
FOUR_CHIPS = dict(n_instances=4, max_slots=2, cache_len=512, chunk_size=16,
                  policy="seer", spec_decode=True)
TP = dict(n_instances=1, max_slots=8, cache_len=512, chunk_size=16,
          policy="seer", spec_decode=True)

_T0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def check_outputs(cfg, groups, report, *, migrate: bool) -> list:
    """What must hold of any rollout: every request finished with its
    full budget of in-vocabulary tokens and finite log-probs, and the
    engine read the device at most once per step.  ``migrate``: the
    divided rollout must have moved requests between instances."""
    bad = []
    for g in groups:
        for r in g.requests:
            if not r.finished or len(r.generated) != r.max_new_tokens:
                bad.append(f"{r.req_id}: {len(r.generated)} of "
                           f"{r.max_new_tokens} tokens, finished="
                           f"{r.finished}")
            elif not all(0 <= t < cfg.vocab_size for t in r.generated):
                bad.append(f"{r.req_id}: token outside the vocabulary")
            elif len(r.logprobs) != len(r.generated) or not all(
                    math.isfinite(lp) for lp in r.logprobs):
                bad.append(f"{r.req_id}: log-probs missing or not finite")
    if migrate and report["migrations"] <= 0:
        bad.append("no request migrated")
    if report["host_syncs_per_step"] > 1:
        bad.append(f"{report['host_syncs_per_step']} host syncs per step")
    return bad


def first_divergence(a: dict, b: dict):
    """(req_id, token index) of the first difference, or None."""
    for rid in sorted(set(a) | set(b)):
        x, y = a.get(rid, []), b.get(rid, [])
        if x != y:
            i = next((i for i, (p, q) in enumerate(zip(x, y)) if p != q),
                     min(len(x), len(y)))
            return rid, i
    return None


def rollout(name, cfg, params, traffic, rollout_kw, *, steps, seed):
    """Serve fresh traffic once; print its numbers; return (responses,
    failures)."""
    from repro.launch.serve import make_traffic, serve

    groups = make_traffic(cfg, seed=seed, **traffic)
    res, rep = serve(cfg, params, groups, steps=steps, **rollout_kw)
    say(f"{name}: requests={rep['requests']} tokens={rep['tokens']} "
        f"steps={rep['engine_steps']} chunks={rep['chunks']} "
        f"migrations={rep['migrations']} drafted={rep['drafted']} "
        f"accepted={rep['accepted']} "
        f"host_syncs_per_step={rep['host_syncs_per_step']} "
        f"compiles={rep['compiles']} "
        f"host_compile_seconds={rep['host_compile_seconds']} "
        f"host_wall_seconds={rep['host_wall_seconds']}")
    bad = check_outputs(cfg, groups, rep,
                        migrate=rollout_kw.get("n_instances", 1) > 1)
    for b in bad:
        say(f"{name}: FAILED {b}")
    return res.responses(), bad


def compare(name, got: dict, want: dict) -> None:
    div = first_divergence(got, want)
    if div is None:
        say(f"{name}: token-exact over {len(want)} requests")
    else:
        n = sum(got.get(r) != want.get(r) for r in set(got) | set(want))
        say(f"{name}: NOT token-exact; {n} of {len(want)} requests "
            f"differ, the first is {div[0]} at token {div[1]}")


def run_one_chip(cfg, params, traffic, rollout_kw, *, seed=0) -> list:
    """The divided rollout and its undivided reference on the default
    device.  Returns the failures."""
    from repro.engine.engine import StepFunctions

    steps = StepFunctions(cfg)
    divided, bad = rollout("divided", cfg, params, traffic, rollout_kw,
                           steps=steps, seed=seed)
    # the undivided reference: one instance, one chunk per request
    ref_kw = dict(rollout_kw, n_instances=1,
                  chunk_size=max(rollout_kw["chunk_size"],
                                 traffic["max_new_tokens"]))
    reference, bad_ref = rollout("reference", cfg, params, traffic, ref_kw,
                                 steps=steps, seed=seed)
    compare("divided vs 1-instance reference", divided, reference)
    return bad + bad_ref


def run_four_chips(cfg, params, traffic, rollout_kw, tp_kw, devices, *,
                   seed=0) -> list:
    """(a) one instance per device vs all on devices[0]; (b) one tp
    instance over ``devices`` vs tp=None on devices[0].  Returns the
    failures."""
    from repro.engine.engine import StepFunctions

    n = len(devices)
    steps = StepFunctions(cfg)
    spread, bad_a = rollout(f"(a) {n} instances on {n} devices", cfg,
                            params, traffic,
                            dict(rollout_kw, n_instances=n,
                                 devices=list(devices)),
                            steps=steps, seed=seed)
    stacked, bad_a0 = rollout(f"(a) {n} instances on device 0", cfg,
                              params, traffic,
                              dict(rollout_kw, n_instances=n,
                                   devices=[devices[0]] * n),
                              steps=steps, seed=seed)
    compare("(a) spread vs stacked", spread, stacked)
    sharded, bad_b = rollout(f"(b) tp={n} instance", cfg, params, traffic,
                             dict(tp_kw, tp=n, devices=list(devices)),
                             steps=StepFunctions(cfg), seed=seed)
    single, bad_b0 = rollout("(b) tp=None instance on device 0", cfg,
                             params, traffic,
                             dict(tp_kw, devices=[devices[0]]),
                             steps=steps, seed=seed)
    compare(f"(b) tp={n} vs tp=None", sharded, single)
    return bad_a + bad_a0 + bad_b + bad_b0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    stamp = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(devices)}
    if dev.platform != "tpu" or dev.device_kind != V5E_KIND:
        print(f"chip_smoke: needs a {V5E_KIND} chip; JAX found "
              f"{stamp}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.serve import init_params_on_device
    except ImportError as e:
        print(f"chip_smoke: the repo's src/ is not beside this script: {e}",
              file=sys.stderr)
        return 1

    say(f"device {stamp}; compile cache {enable_compile_cache()}")
    cfg = get_config(ARCH)
    params = jax.block_until_ready(init_params_on_device(cfg, args.seed))
    leaves = jax.tree.leaves(params)
    say(f"{ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {sum(x.size for x in leaves)} params "
        f"({sum(x.nbytes for x in leaves)} bytes) on {dev.device_kind}")
    if args.chips == 1:
        bad = run_one_chip(cfg, params, TRAFFIC, ONE_CHIP, seed=args.seed)
    else:
        bad = run_four_chips(cfg, params, FOUR_CHIP_TRAFFIC, FOUR_CHIPS, TP,
                             devices[:args.chips], seed=args.seed)
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        say(f"device {d.id} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    if bad:
        print(f"chip_smoke: {len(bad)} checks failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": stamp}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
