"""Stable names on the device side: ``jax.named_scope`` around the parts
of the model and train programs, so that a trace's operations can be
grouped by what they compute and the grouping survives a refactor. The
scope path rides in each operation's metadata (``op_name``), which is
what ``as_text(debug_info=True)`` prints as ``loc(...)``: ``mlp/...`` in
a forward program, ``jvp(mlp)/...`` and ``transpose(jvp(mlp))/...`` in
a train step."""

import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models.gpt2 import GPT2Config, GPT2Model
from ray_tpu.models.llama import LlamaConfig, LlamaModel
from ray_tpu.train.spmd import make_train_step

BLOCK = {"attention", "mlp", "norm_residual", "embed", "logits"}
I32 = jnp.int32


def lowered_text(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def train_step_text(model):
    step = make_train_step(model)
    params, opt_state = jax.eval_shape(step.init_fn, jax.random.key(0))
    batch = (jax.ShapeDtypeStruct((2, 16), I32),) * 2
    return step.step_fn.lower(params, opt_state, batch).as_text(
        debug_info=True)


def llama_text(method):
    model = LlamaModel(LlamaConfig.debug(vocab_size=256, max_seq_len=64))
    params = jax.eval_shape(model.init, jax.random.key(0))
    toks = jax.ShapeDtypeStruct((2, 16), I32)
    two = jax.ShapeDtypeStruct((2,), I32)
    if method == "forward_step":
        cache = jax.eval_shape(lambda: model.init_kv_cache(2, 16))
        return lowered_text(model.forward_step, params, toks, cache, two)
    pool = jax.eval_shape(lambda: model.init_kv_pool(9, 8))
    if method == "decode_step_paged":
        tables = jax.ShapeDtypeStruct((2, 4), I32)
        return lowered_text(model.decode_step_paged, params, two, pool,
                            tables, two)
    prefix = jax.ShapeDtypeStruct(
        (pool["k"].shape[0], 2, 8) + pool["k"].shape[3:], pool["k"].dtype)
    return lowered_text(model.prefill_with_prefix, params, toks, prefix,
                        prefix, two, two)


CASES = {
    "llama_train_step": (
        lambda: train_step_text(LlamaModel(
            LlamaConfig.debug(vocab_size=256, max_seq_len=64))),
        BLOCK | {"loss", "optimizer"}),
    "gpt2_train_step": (
        lambda: train_step_text(GPT2Model(GPT2Config.debug())),
        BLOCK | {"loss", "optimizer"}),
    "llama_forward_step": (lambda: llama_text("forward_step"),
                           BLOCK | {"kv_update"}),
    "llama_decode_step_paged": (lambda: llama_text("decode_step_paged"),
                                BLOCK | {"kv_update"}),
    "llama_prefill_with_prefix": (lambda: llama_text("prefill_with_prefix"),
                                  BLOCK),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_scope_names_are_in_the_lowered_programs_metadata(case):
    lower, scopes = CASES[case]
    text = lower()
    missing = [s for s in sorted(scopes)
               if not re.search(rf'[/("]{s}[/)]', text)]
    assert not missing, f"{case}: no operation under scope(s) {missing}"
