"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: healthy, it comes out correct; with a token altered where the
engine produces it, or with a step that leaves the cache as it was, it
does not; and with the float8 control in the program's place it does not
either."""
import dataclasses
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def run(cell, seed, **kw):
    return harness.measure(cell, seed, 0.5, False, time.monotonic(),
                           require_chip=False, log=lambda m: None, **kw)


def test_tiny_run_is_correct_and_reports_its_metrics():
    line = run(tiny.cell(), 2 ** 31 + 11)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 8
    assert line["checks"]["max_logit_gap"]["value"] < 0.05
    assert {"rollout_tokens_per_s", "setup_s"} <= set(line["metrics"])
    assert line["checks"]["window_compiles"]["value"] == 0
    assert list(line)[-1] == "checks"


def test_an_altered_token_is_not_correct(monkeypatch):
    # the fault: every committed row's first token replaced where the
    # engine produces it, before the rollout sees it
    from repro.engine.engine import Instance
    commit = Instance._commit_row

    def altered(self, seq, new_toks, new_lps, a):
        vocab = self.cfg.vocab_size
        new_toks = [(new_toks[0] + 1) % vocab] + list(new_toks[1:])
        return commit(self, seq, new_toks, new_lps, a)

    monkeypatch.setattr(Instance, "_commit_row", altered)
    line = run(tiny.cell(), 2 ** 31 + 12)
    assert not line["correct"]
    assert line["checks"]["max_logit_gap"]["value"] > 0.05


def test_a_step_that_leaves_the_cache_as_it_was_is_not_correct(
        monkeypatch):
    # the fault: every fused step hands back the KV cache it was given,
    # so no token's keys and values are ever written
    import jax
    import jax.numpy as jnp
    from repro.engine.engine import StepFunctions
    fused_step = StepFunctions.fused_step

    def unchanged(self, T, sctx=None):
        fn = fused_step(self, T, sctx)

        def step(params, cache, *args):
            kept = jax.tree.map(jnp.copy, cache)
            *out, _ = fn(params, cache, *args)
            return (*out, kept)
        return step

    monkeypatch.setattr(StepFunctions, "fused_step", unchanged)
    line = run(tiny.cell(), 2 ** 31 + 14)
    assert not line["correct"]
    assert line["checks"]["max_logit_gap"]["value"] > 0.05


def run_instances(monkeypatch, seed):
    """A traced run of the tiny two-instance cell; its line, the readings
    its metrics were read from, and its log."""
    seen, logs = [], []
    load = harness.load_reader

    def recording(name):
        read = load(name)

        def reader(r):
            seen.append(r)
            return read(r)
        return reader

    monkeypatch.setattr(harness, "load_reader", recording)
    line = harness.measure(tiny.instances_cell(2), seed, 0.5, True,
                           time.monotonic(), require_chip=False,
                           log=logs.append)
    return line, seen[0], logs


def test_two_instances_are_correct_and_count_steps_per_instance(
        monkeypatch):
    line, r, logs = run_instances(monkeypatch, 2 ** 31 + 21)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 16
    assert r["instances"] == 2
    m = line["metrics"]
    assert m["step_ms"]["value"] == pytest.approx(
        1e3 * r["window_s"] / (r["engine_steps"] / 2))
    assert m["migration_share"]["value"] > 0
    assert m["migration_share"]["value"] == pytest.approx(
        100.0 * r["migrations"] / r["chunks"])
    # both instances ran steps in every iteration
    per = [re.search(r"steps \[(\d+), (\d+)\]", s) for s in logs]
    per = [tuple(map(int, g.groups())) for g in per if g]
    assert per and all(a > 0 and b > 0 for a, b in per)


def test_a_migration_that_carries_no_cache_is_not_correct(monkeypatch):
    # the fault: a chunk re-admitted on another instance gets its blob
    # with every key and value zeroed, as if the exchange between chips
    # were left out
    import jax.numpy as jnp
    from repro.core.rollout import SeerRollout
    fetch = SeerRollout._pool_fetch

    def left_out(self, r, inst, stats):
        blob = fetch(self, r, inst, stats)
        if blob is None or r.instance_id in (None, inst.instance_id):
            return blob
        return dataclasses.replace(blob, arrays={
            k: jnp.zeros_like(a) if jnp.issubdtype(a.dtype, jnp.floating)
            else a for k, a in blob.arrays.items()})

    monkeypatch.setattr(SeerRollout, "_pool_fetch", left_out)
    line, r, _ = run_instances(monkeypatch, 2 ** 31 + 22)
    assert r["migrations"] > 0
    assert not line["correct"]
    assert line["checks"]["max_logit_gap"]["value"] > 0.05


def test_the_control_in_the_programs_place_is_not_correct():
    line = run(tiny.cell(), 2 ** 31 + 13, control=True)
    assert not line["correct"]
    control = line["checks"]["max_logit_gap"]["value"]
    assert control > line["checks"]["max_logit_gap"]["limit"]
    assert control > 3 * line["program_gap"]


def script(cwd, *args, env=None):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "granite-3-8b.pp2.grpo", "--seed", "1", "--seconds", "1", *args],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_no_chip_means_no_result():
    p = script(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refused" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    p = script(tmp_path, env={"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_warm_up_compiles_every_step_width_the_engine_can_pick():
    # the engine's rule: the draft bucket plus one column, widened to the
    # widest prefill piece of the step rounded up to a power of two and
    # capped at a whole chunk; the per-step prefill budget can cut a
    # piece to any length
    c = tiny.cell()
    _, _, ro = harness.build(c, 2 ** 31 + 5)
    harness.warm_programs(ro, c.conf["serving"]["cache_len"])
    for inst in ro.instances:
        chunk = inst.prefill_chunk
        widths = set()
        for g in harness.GAMMA_BUCKETS:
            if g > inst.gamma_max:
                continue
            widths.add(g + 1)
            for need in range(1, chunk + 1):
                b = 1 << (need - 1).bit_length()
                widths.add(max(g + 1, min(b, chunk)))
        compiled = {k[1] for k in inst.steps._step_cache if k[0] == "fused"}
        assert widths <= compiled, sorted(widths - compiled)
