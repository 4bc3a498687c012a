"""The plain references against the program's model, at tiny size on the
CPU, on the weights both make from one seed."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import granite
from chipbench.reference.common import Keys, param, weight_key
from chipbench.tests.tiny import DENSE, PROGRAM

SEED = 2 ** 31 + 77          # above int32, as the benchmark's seeds are


def program(model):
    from repro.configs import get_config
    from repro.launch.serve import init_params_on_device
    cfg = dataclasses.replace(get_config(PROGRAM), **model)
    return cfg, init_params_on_device(cfg, SEED % (1 << 32))


def test_reference_logits_match_program_in_f32():
    # both sides in float32 at the highest matmul precision: they differ
    # only in the order of float32 operations (the program's fused
    # attention against plain softmax), a few ulps of logits near 1 --
    # 1e-4 leaves two orders of magnitude over what was seen (2.5e-6) and
    # stays far below the gaps of a bf16 or fp8 forward (1e-2 and up)
    from repro.models import forward
    m = dict(DENSE, dtype="float32", param_dtype="float32")
    cfg, params = program(m)
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], (2, 64))
    with jax.default_matmul_precision("highest"):
        want, _, _ = forward(cfg, params, jnp.asarray(toks),
                             jnp.broadcast_to(jnp.arange(64), (2, 64)))
    got = granite.logits(m, SEED, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=0)


def test_reference_draws_the_served_weights():
    # the reference makes its own weights from the seed; bit for bit the
    # program's bfloat16 embedding and final norm
    model = DENSE
    _, params = program(model)
    keys = Keys(weight_key(SEED))
    dt = jnp.dtype(model["param_dtype"])
    embed = param(keys, "embed", (model["vocab_size"], model["d_model"]), dt)
    ln = param(keys, "ones", (model["d_model"],), dt)
    assert bool((embed == params["embed"]).all())
    assert bool((ln == params["final_ln"]).all())


def test_fp8_control_departs_from_the_reference():
    # the control rounds every matmul operand to float8 e4m3: its logits
    # move by far more than f32 rounding, so it can fail the comparison
    toks = np.random.default_rng(1).integers(0, 512, (1, 48))
    f = np.asarray(granite.logits(DENSE, SEED, toks))
    q = np.asarray(granite.logits(DENSE, SEED, toks, "fp8"))
    assert np.abs(q - f).max() > 1e-2
