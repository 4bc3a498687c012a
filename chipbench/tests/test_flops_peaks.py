"""FLOPs per token tied to the configurations' parameter counts, and the
table of peaks."""
import dataclasses

import pytest

from chipbench import cells, flops, harness
from chipbench.peaks import peaks


def program_cfg(name):
    return cells.model_config(cells.load_config(name))


def test_dense_matmul_params_are_the_parameter_count_less_norms():
    c = cells.load_config("granite-3-8b.pp2")
    m = c["model"]
    cfg = program_cfg("granite-3-8b.pp2")
    norms = m["num_layers"] * 2 * m["d_model"] + m["d_model"]
    # tied head: the embedding is counted once, as the head's matmul
    assert flops.matmul_params(c) == cfg.num_params() - norms
    # 4.19e9 parameters, 20 of granite-3.0-8b's 40 layers
    assert 4.1e9 < cfg.num_params() < 4.3e9


def test_dense_counts_are_pinned():
    # the numbers the dense count gave before it learned MoE and MLA:
    # the benchmark's dense cell reads the same step_mfu as before
    c = cells.load_config("granite-3-8b.pp2")
    assert flops.matmul_params(c) == 4185927680
    assert flops.flops_per_token(c, 0) == 8371855360
    assert flops.flops_per_token(c, 300) == 8470159360
    assert flops.flops_for_requests(
        c, [(256, 1), (256, 78), (256, 256), (30, 0)]) == 9557322424320.0
    windowed = dict(c, model=dict(c["model"], sliding_window=100))
    assert flops.flops_for_requests(
        windowed, [(256, 78), (30, 100)]) == 3896435671040.0


def test_flops_per_token_grow_with_context_up_to_the_window():
    c = cells.load_config("granite-3-8b.pp2")
    c = dict(c, model=dict(c["model"], sliding_window=100))
    base = flops.flops_per_token(c, 0)
    assert flops.flops_per_token(c, 50) > base
    assert flops.flops_per_token(c, 100) == flops.flops_per_token(c, 5000)
    # whole requests: the closed form equals the per-position sum
    want = sum(flops.flops_per_token(c, p) for p in range(130))
    assert flops.flops_for_requests(c, [(30, 100)]) == pytest.approx(want)


def test_moe_with_every_expert_held_is_the_parameter_count_less_the_rest():
    from repro.configs import get_config
    cfg = get_config("deepseek-moe-16b")
    c = {"name": cfg.name, "model": dataclasses.asdict(cfg)}
    d, L, v = cfg.d_model, cfg.num_layers, cfg.vocab_size
    norms = L * 2 * d + d
    n_moe = L - cfg.first_dense_layers
    idle = n_moe * (cfg.num_experts - cfg.moe_top_k) * 3 * d * cfg.moe_d_ff
    # untied: the embedding lookup is no matmul, the head is
    assert flops.matmul_params(c) == cfg.num_params() - norms - v * d - idle


def moonlight(held, **kw):
    """Moonlight-16B-A3B's block from the catalog's config.json numbers,
    with ``held`` of its 64 routed experts on this chip."""
    model = dict(arch_type="moe", num_layers=27, d_model=2048,
                 num_heads=16, num_kv_heads=16, d_ff=11264,
                 vocab_size=163840, tie_embeddings=False, num_experts=held,
                 num_shared_experts=2, moe_top_k=6, moe_d_ff=1408,
                 first_dense_layers=1, kv_lora_rank=512, q_lora_rank=None,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    return {"name": "moonlight", "model": dict(model, **kw),
            "published": {"n_routed_experts": 64}}


@pytest.mark.parametrize("held,want", [(64, 2_579_103_744),
                                       (8, 1_398_276_096)])
def test_moe_with_latent_attention_counts_the_held_share(held, want):
    c = moonlight(held)
    assert flops.matmul_params(c) == want
    head = 163840 * 2048
    assert head == 335_544_320
    if held == 64:
        assert round((want - head) / 1e6) == 2244      # in the layers
    # attention's scores over nope + rope and values over v, 27 layers
    assert flops.attention_flops_per_position(c["model"]) == 276_480
    assert flops.flops_per_token(c, 10) - flops.flops_per_token(c, 0) \
        == 10 * 276_480


def test_a_low_rank_query_counts_both_of_its_projections():
    plain, low = moonlight(64), moonlight(64, q_lora_rank=1536)
    per_layer = (2048 * 1536 + 1536 * 16 * 192) - 2048 * 16 * 192
    assert flops.matmul_params(low) - flops.matmul_params(plain) \
        == 27 * per_layer


def test_a_model_that_cannot_be_counted_is_refused_before_build(
        monkeypatch):
    conf = cells.load_config("granite-3-8b.pp2")
    conf["model"]["arch_type"] = "hybrid"
    monkeypatch.setattr(cells, "load_config", lambda name: conf)

    def no_build(*a, **k):
        raise AssertionError("build() ran")

    monkeypatch.setattr(harness, "build", no_build)
    with pytest.raises(ValueError, match="hybrid"):
        harness.cell("granite-3-8b.pp2.grpo")


def test_peaks_refuse_an_unknown_chip():
    assert peaks("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
