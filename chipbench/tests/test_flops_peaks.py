"""FLOPs per token tied to the configurations' parameter counts, and the
table of peaks."""
import pytest

from chipbench import cells, flops
from chipbench.peaks import peaks


def program_cfg(name):
    return cells.model_config(cells.load_config(name))


def test_dense_matmul_params_are_the_parameter_count_less_norms():
    c = cells.load_config("granite-3-8b.pp2")
    m = c["model"]
    cfg = program_cfg("granite-3-8b.pp2")
    norms = m["num_layers"] * 2 * m["d_model"] + m["d_model"]
    # tied head: the embedding is counted once, as the head's matmul
    assert flops.matmul_params(m) == cfg.num_params() - norms
    # 4.19e9 parameters, 20 of granite-3.0-8b's 40 layers
    assert 4.1e9 < cfg.num_params() < 4.3e9


def test_flops_per_token_grow_with_context_up_to_the_window():
    m = dict(cells.load_config("granite-3-8b.pp2")["model"],
             sliding_window=100)
    base = flops.flops_per_token(m, 0)
    assert flops.flops_per_token(m, 50) > base
    assert flops.flops_per_token(m, 100) == flops.flops_per_token(m, 5000)
    # whole requests: the closed form equals the per-position sum
    want = sum(flops.flops_per_token(m, p) for p in range(130))
    assert flops.flops_for_requests(m, [(30, 100)]) == pytest.approx(want)


def test_peaks_refuse_an_unknown_chip():
    assert peaks("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
