"""The plain reference holds the same model as the program (weights made
from the same seed) and computes the same function: against the program
run in float32, its logits agree to float32 rounding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench.conftest import TINY_CONFIG
from bench.reference import gqa

def _program(config, dtype):
    from repro.models.api import build

    cfg = dataclasses.replace(H.program_config(config), dtype=dtype)
    return build(cfg)


def test_weights_match_the_program_init():
    config = TINY_CONFIG
    key = H.jax_key(2 ** 31 + 17)
    p = _program(config, "bfloat16").init_params(key)
    w = gqa.make_weights(gqa.Dims.from_config(config), key)
    L = p["layers"]
    pairs = [(p["embed"]["embed"], w["embed"]),
             (p["embed"]["lm_head"], w["lm_head"]),
             (L["attn"]["wq"], w["wq"]), (L["attn"]["wo"], w["wo"]),
             (L["mlp"]["w_up"], w["w_up"]), (L["mlp"]["w_gate"], w["w_gate"])]
    for a, b in pairs:
        assert a.dtype == b.dtype and bool((a == b).all())


@pytest.mark.parametrize("qk_norm", [True, False], ids=["qk_norm", "plain"])
def test_reference_matches_the_program_in_float32(qk_norm):
    config = dict(TINY_CONFIG, reference={"qk_norm": qk_norm})
    config["program"] = {"registry": "qwen3-14b", "overrides": dict(
        TINY_CONFIG["program"]["overrides"], qk_norm=qk_norm)}
    key = H.jax_key(99)
    m = gqa.Dims.from_config(config)
    model = _program(config, "float32")
    w = gqa.make_weights(m, key)
    # the program in float32 with the reference's (bf16-valued) weights
    p = jax.tree.map(lambda x: x.astype(jnp.float32),
                     _program(config, "bfloat16").init_params(key))
    toks = np.random.default_rng(0).integers(0, m.vocab, 37).astype(np.int32)
    at = np.arange(37)
    with jax.default_matmul_precision("highest"):
        got, _ = model.prefill_fn(p, {"tokens": jnp.asarray(toks)[None],
                                      "logits_at": jnp.asarray(at)[None]})
    ref = gqa.logits_at(m, w, toks, at)
    np.testing.assert_allclose(np.asarray(got[0]), ref, atol=2e-4, rtol=0)


def test_control_rounds_coarser_than_bf16():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 256)),
                    jnp.float32)
    err = lambda y: float(jnp.sqrt(jnp.mean((y - x) ** 2)))
    bf16 = err(x.astype(jnp.bfloat16).astype(jnp.float32))
    assert err(gqa._quant(x, -1, "fp8")) > 5 * bf16


def test_a_mixture_of_experts_config_is_refused():
    with pytest.raises(ValueError):
        gqa.Dims.from_config(dict(TINY_CONFIG, num_local_experts=4))
