"""A model WITHOUT the layer kind a PR adds is served by the programs it
was served by before: the StableHLO of an engine's five programs at the
benchmark configurations' debug widths, hashed, against the hashes the
parent gave with this container's jax.

- The ONE-kind engines (``mistral-7b-v0.3-d6``, ``olmoe-1b-7b-d3``)
  against the parent of PR 32 (c95a537), which brought layer kinds.
- PR 35 (EVA attention: a third kind, two-part K/V): the one-kind hashes
  stand, and the TWO-kind engine (``mellum2-12b-a2.5b-d8``: window and
  full layers, a pool a kind) joins, against PR 35's parent (f9165a5).

A PR that changes what these programs compute changes a hash, and says
in ``CHANGES.md`` which operation and why, and writes the new hash here.

PR 33: the two one-kind ``decode`` hashes are its own. The sampler joined
the decode program (an ``argmax`` over the logits; the key split, the
top-k sort and the categorical draw inside conditionals of batch-level
predicates) and the logits no longer leave it; the model's part of the
program and the other four programs are the parent's.

PR 37: the two EXPERT models' ``decode``, ``prefill`` and
``prefill_prefix`` hashes are its own. Their layer scans close over the
expert stacks ``[L*E, ...]`` and hand the body the layer's index where
they sliced ``e_gate`` / ``e_up`` / ``e_down`` a layer at a time, and
``ragged_dot``'s ``group_sizes`` is the layer's E counts placed into a
zero ``[L*E]`` vector (``dynamic_update_slice``); every other operation,
``insert`` and ``gather``, and all five of the dense model's programs
are the parent's.
"""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from tests.program_readers import lowered_programs

# sha256[:16] of ``lowered.as_text()``, computed on c95a537 (``decode``: on
# PR 33's tree; mellum2: on f9165a5; the expert models' ``decode``,
# ``prefill`` and ``prefill_prefix``: on PR 37's tree)
PARENT = {
    "mistral-7b-v0.3-d6": {
        "decode": "20fa90ecbf267886", "prefill": "8d7bc32d9dda3104",
        "insert": "1b106dfa26607af4", "gather": "c3d4dde8973ad705",
        "prefill_prefix": "625968f268b7e03b"},
    "olmoe-1b-7b-d3": {
        "decode": "2655b19927cbf11f", "prefill": "492cb54525c2b3ce",
        "insert": "47860a4b15fc27e3", "gather": "58895b3540c687ed",
        "prefill_prefix": "c8f08df0f7c5c35c"},
    "mellum2-12b-a2.5b-d8": {
        "decode": "78510f869b7968a7", "prefill": "546ac83a2d1f6993",
        "insert": "8b3a1532733cbdc8", "gather": "54aa86b2efd8fa61",
        "prefill_prefix": "62933ba3014f7270"},
}


@pytest.fixture(scope="module", params=sorted(PARENT))
def programs(request):
    return request.param, lowered_programs(request.param)


@pytest.mark.parametrize("program", sorted(PARENT["olmoe-1b-7b-d3"]))
def test_one_kind_program_is_the_parents(programs, program):
    name, lowered = programs
    text = lowered[program].as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT[name][program], (
            f"{name}'s {program} program is no longer the one its parent "
            f"lowered: say in CHANGES.md which operation changed and why")


def test_the_training_program_is_the_parents():
    """``value_and_grad`` of the debug Llama's loss, the program the
    train cells' model takes: PR 35's norm, residual and head options
    leave it as f9165a5 lowered it."""
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    model = LlamaModel(LlamaConfig.debug())
    params = jax.eval_shape(model.init, jax.random.key(0))
    batch = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = jax.jit(jax.value_and_grad(model.loss)).lower(
        params, batch, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "36d429ac0c36f2f4"
