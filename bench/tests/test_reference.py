"""The plain float32 reference against the program on the CPU, at a tiny
size and in float32 on both sides: the same weights give the same
logits.  The served path (prefill without capacity drops) is compared,
for the dense and the routed-expert configuration alike."""
from __future__ import annotations

import numpy as np
import pytest

from bench import program
from bench.reference import model
from bench.tests import util


@pytest.mark.parametrize("moe", [False, True], ids=["qwen3", "granite"])
def test_reference_matches_program_in_float32(moe):
    import jax
    import jax.numpy as jnp

    from repro.models.layers import Ctx
    from repro.models.model import forward

    cfgj = util.tiny_config(moe)
    cfg = program.model_config(cfgj, 8)
    params = program.make_params(cfg, 3)
    toks = np.random.default_rng(0).integers(0, cfgj["vocab_size"], 24)
    w = program.reference_weights(params, cfgj)
    ref = np.asarray(model.logits(w, cfgj, toks, np.arange(24)))
    ctx = Ctx(dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        for n in (1, 9, 24):
            got, _, _ = forward(cfg, params, {"tokens": jnp.asarray(
                toks[None, :n])}, ctx, mode="prefill")
            got = np.asarray(got)[0, -1, :cfgj["vocab_size"]]
            np.testing.assert_allclose(got, ref[n - 1], rtol=1e-4,
                                       atol=1e-4)


@pytest.mark.parametrize("moe", [False, True], ids=["qwen3", "granite"])
def test_fp8_control_moves_the_logits(moe):
    cfgj = util.tiny_config(moe)
    cfg = program.model_config(cfgj, 8)
    w = program.reference_weights(program.make_params(cfg, 3), cfgj)
    toks = np.random.default_rng(0).integers(0, cfgj["vocab_size"], 24)
    f32 = np.asarray(model.logits(w, cfgj, toks, np.arange(24)))
    fp8 = np.asarray(model.logits(w, cfgj, toks, np.arange(24), "fp8"))
    assert np.abs(fp8 - f32).max() > 100 * 1e-4
