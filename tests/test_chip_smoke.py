"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size, on the
host platform's forced devices.  This finds wrong paths, arguments,
placement and control flow before a chip run; it says nothing about
speed."""
import importlib.util
from pathlib import Path

import jax
import pytest


def _load_chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_chip_smoke()

TRAFFIC = dict(groups=2, group_size=4, prompt_len=8, max_new_tokens=12,
               min_new_tokens=6, temperature=0.0)
# 8 requests on 2 x 2 slots in 4-token chunks: requests queue and migrate
ROLLOUT = dict(n_instances=2, max_slots=2, cache_len=64, chunk_size=4,
               policy="seer", spec_decode=True)


@pytest.fixture(scope="module")
def zamba2():
    """Tiny zamba2 with weights as the server holds them (compute
    dtype, made on the device)."""
    from repro.configs import get_tiny_config
    from repro.launch.serve import init_params_on_device

    cfg = get_tiny_config("zamba2-1.2b")
    return cfg, init_params_on_device(cfg, 1)


def test_one_chip_phases_on_tiny_zamba2(zamba2, capsys):
    cfg, params = zamba2
    assert smoke.run_one_chip(cfg, params, TRAFFIC, ROLLOUT) == []
    out = capsys.readouterr().out
    assert "divided vs 1-instance reference: token-exact" in out


def test_four_chip_phases_on_forced_host_devices(zamba2, capsys):
    cfg, params = zamba2
    devices = jax.devices()[:4]
    assert len(devices) == 4
    bad = smoke.run_four_chips(
        cfg, params, TRAFFIC, dict(ROLLOUT, max_slots=1),
        dict(ROLLOUT, n_instances=1, max_slots=4), devices)
    assert bad == []
    out = capsys.readouterr().out
    assert "(a) spread vs stacked: token-exact" in out
    assert "(b) tp=4 vs tp=None: token-exact" in out


def test_checks_catch_bad_outputs(zamba2):
    """The output checks are not vacuous: a short, out-of-vocabulary or
    non-finite response, a run without migrations and a second host
    sync per step are each reported."""
    from repro.launch.serve import make_traffic

    cfg, _ = zamba2
    groups = make_traffic(cfg, groups=1, group_size=4, prompt_len=4,
                          max_new_tokens=3, temperature=0.0, seed=0)
    for r in groups[0].requests:
        r.finish(0.0)
        r.generated, r.logprobs = [1, 2, 3], [-0.5, -0.5, -0.5]
    good = {"migrations": 1, "host_syncs_per_step": 1.0}
    assert smoke.check_outputs(cfg, groups, good, migrate=True) == []
    r0, r1, r2, _ = groups[0].requests
    r0.generated = [1, 2]
    r1.generated = [1, 2, cfg.vocab_size]
    r2.logprobs = [-0.5, float("nan"), -0.5]
    bad = smoke.check_outputs(
        cfg, groups, {"migrations": 0, "host_syncs_per_step": 1.5},
        migrate=True)
    assert len(bad) == 5, bad


def test_first_divergence():
    assert smoke.first_divergence({"a": [1, 2]}, {"a": [1, 2]}) is None
    assert smoke.first_divergence({"a": [1, 2], "b": [3, 4]},
                                  {"a": [1, 2], "b": [3, 5]}) == ("b", 1)
    assert smoke.first_divergence({"a": [1]}, {"a": [1, 2]}) == ("a", 1)


def test_compile_cache_placement(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing sets another;
    without it the cache is the checkout's fixed ``.jax_cache/``."""
    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        placed = compile_cache.enable_compile_cache()
        root = Path(__file__).resolve().parents[1]
        assert placed == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_main_refuses_without_a_tpu(capsys):
    """On the host platform the script fails at once and prints no
    result."""
    assert smoke.main([]) == 1
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs a TPU v5 lite chip" in captured.err
