"""Two guards beside ``tests/test_deepseek_v32_engine.py``, in a file of
their own so that the suite's workers share the load: the timed-path tool
of the sparse-attention cell at debug widths, and kanana's programs held
to their parent's StableHLO."""

import pytest


@pytest.mark.parametrize("fault", [False, True])
def test_the_timed_path_check_holds_layer_one_rows_and_sees_a_fault(
        fault, capsys):
    """``tools/dsa_timed_path_check.py`` at debug widths: an engine's own
    chunked prefills and decode steps leave layer 1's cache rows (``c``,
    ``k_pe`` and the index key) where the reference's float32 arithmetic
    puts them, and two planted faults move them far off."""
    import json

    from tools import dsa_timed_path_check
    assert dsa_timed_path_check.main(
        ["--tiny-cpu"] + ["--fault"] * fault) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["fault"] is fault and out["decode_steps"] >= 5
    assert (out["worst"] > 0.3) if fault else (out["worst"] < 1e-4)


# sha256[:16] of ``lowered.as_text()`` of kanana-2-30b-a3b-d5's engine at
# its debug widths, computed on PR 43's parent (b89b10d) with this
# container's jax: the fields this PR adds, at their defaults, leave every
# program of a model without them as it was
KANANA_PARENT = {
    "decode": "52125598a0dea5d2", "prefill": "104991dae29a6a29",
    "insert": "b9efb04a459e98da", "gather": "cc4fbbc454e1ff81",
    "prefill_prefix": "73618233f4579a51"}


@pytest.fixture(scope="module")
def kanana_programs():
    from tests.test_one_kind_programs import lowered_programs
    return lowered_programs("kanana-2-30b-a3b-d5")


@pytest.mark.parametrize("program", sorted(KANANA_PARENT))
def test_kananas_programs_are_the_parents(kanana_programs, program):
    import hashlib
    text = kanana_programs[program].as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == KANANA_PARENT[program]
