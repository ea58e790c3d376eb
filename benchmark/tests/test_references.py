"""The two float32 references against ``ray_tpu/models`` at debug
widths. The models compute in float32 here (``dtype=float32``), so the
agreement is to rounding: a wrong rotary convention, norm, GQA grouping
or tied head would be off by orders of magnitude more."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import gpt2 as gpt2_builder
from benchmark.builders import llama as llama_builder
from benchmark.lib import serving
from benchmark.reference.loss import mean_cross_entropy

LLAMA = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 160, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
         "tie_word_embeddings": False, "sliding_window": None}
GPT2 = {"vocab_size": 256, "n_embd": 64, "n_layer": 3, "n_head": 4,
        "n_positions": 128, "layer_norm_epsilon": 1e-5}


def tokens(vocab, shape=(2, 48)):
    return jnp.asarray(np.random.default_rng(0).integers(0, vocab, shape),
                       jnp.int32)


def test_mistral_reference_matches_models_llama():
    model = llama_builder.build_model(LLAMA, 64, extra={
        "dtype": jnp.float32, "remat": False})
    params = model.init(jax.random.key(1))
    toks = tokens(256)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, toks)
    want = llama_builder.reference_forward(LLAMA)(params, toks)
    assert got.shape == want.shape == (2, 48, 256)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_gpt2_reference_matches_models_gpt2_and_its_loss():
    model = gpt2_builder.build_model(GPT2, 64, extra={
        "dtype": jnp.float32, "remat": False})
    params = model.init(jax.random.key(2))
    toks = tokens(256)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, toks)
        loss = model.loss(params, toks, jnp.roll(toks, -1, axis=1))
    forward = gpt2_builder.reference_forward(GPT2)
    np.testing.assert_allclose(got, forward(params, toks), atol=2e-4)
    want = mean_cross_entropy(forward, params, toks,
                              jnp.roll(toks, -1, axis=1))
    assert abs(float(loss) - float(want)) < 1e-4


@pytest.mark.parametrize("fault", ["none", "last_layer_skipped",
                                   "int8_weights"])
def test_logits_check_passes_the_model_and_refuses_a_fault(fault):
    """The serve cells' check at debug widths, bf16 compute as served:
    the model passes the cells' own tolerance; a model whose last layer
    adds nothing, or whose matmul weights went through int8 (one scale
    per output channel), is refused."""
    tol = json.load(open(os.path.join(
        harness.HERE, "traffic", "chat_mixed.json")))["correctness"][
            "tolerance_rel_rms"]
    model = llama_builder.build_model(LLAMA, 256)
    true = model.init(jax.random.key(3))
    served = dict(true, layers=dict(true["layers"]))
    if fault == "last_layer_skipped":
        for k in ("wo", "w_down"):
            served["layers"][k] = true["layers"][k].at[-1].set(0.0)
    elif fault == "int8_weights":
        def int8(w, axis):
            s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
            return jnp.round(w / s) * s
        for k, axis in (("wq", 1), ("wk", 1), ("wv", 1), ("wo", (1, 2)),
                        ("w_gate", 1), ("w_up", 1), ("w_down", 1)):
            served["layers"][k] = int8(true["layers"][k], axis)
        served["lm_head"] = int8(true["lm_head"], 0)
    server = types.SimpleNamespace(model=model, engine=types.SimpleNamespace(
        params=served, block_size=16))
    reference = llama_builder.reference_forward(LLAMA)
    out = serving.check_logits(
        server, lambda _served, toks: reference(true, toks), seed=5,
        prompt_len=32, decode_steps=4, tol_rel_rms=tol)
    # debug widths have their own noise floor (0.016 here against 0.015
    # at the published ones), so the clean model gets a little room
    if fault == "none":
        assert out["logits_rel_rms"] < 0.02, out
    else:
        assert not out["ok"] and out["logits_rel_rms"] > 1.4 * tol, out


def test_token_gaps_let_a_near_tie_pass_and_refuse_a_random_token():
    rng = np.random.default_rng(0)
    want = rng.normal(size=(4, 1000)).astype(np.float32)
    first = want.argmax(-1)
    assert serving.token_gaps(want, list(first)) == [0.0] * 4
    second = np.argsort(want, -1)[:, -2]
    want[np.arange(4), second] = want.max(-1) - 0.02      # a near-tie
    assert max(serving.token_gaps(want, list(second))) < 0.1
    assert min(serving.token_gaps(want, [7, 7, 7, 7])) > 1.0
