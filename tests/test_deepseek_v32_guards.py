"""Two guards beside ``tests/test_deepseek_v32_engine.py``, in a file of
their own so that the suite's workers share the load: the timed-path tool
of the sparse-attention cell at debug widths, and the two latent models'
programs held to their StableHLO (DeepSeek-V3.2's to its parent's)."""

import pytest

from tests.program_readers import lowered_programs


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
# its debug widths with this container's jax. Until PR 44 these were its
# parent's (b89b10d: an indexer's fields, at their defaults, leave every
# program of a model without them as it was). ISSUE 45 changed what they
# hold: the cache row is ``c | k_pe`` as ONE row of ``"k"`` (48 lanes at
# these widths) and ``"v"`` is zero-width, so all five programs move rows
# of another shape; computed on PR 45's tree, they hold that row from here
# on
KANANA_PARENT = {
    "decode": "ef7bd1d242ad6a80", "prefill": "ba20399f31f18400",
    "insert": "a5f3205040375c62", "gather": "6c260ce8daa07c0a",
    "prefill_prefix": "a68b5b634c8dee17"}
# ... and of deepseek-v3.2-d5's, computed on PR 45's parent (f2c638f):
# the one row is a model's without an indexer; an indexed model keeps its
# row and its programs
DEEPSEEK_V32_PARENT = {
    "decode": "933a2ed7c6d319c9", "prefill": "dca3eeaba8b60b9d",
    "insert": "8e6ff5760c166bcb", "gather": "6ed596fc9db0d59f",
    "prefill_prefix": "db3e7a2e69b0da52"}


def _hash(lowered) -> str:
    import hashlib
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def kanana_programs():
    return lowered_programs("kanana-2-30b-a3b-d5")


@pytest.fixture(scope="module")
def deepseek_v32_programs():
    return lowered_programs("deepseek-v3.2-d5")


@pytest.mark.parametrize("program", sorted(KANANA_PARENT))
def test_kananas_programs_are_the_parents(kanana_programs, program):
    assert _hash(kanana_programs[program]) == KANANA_PARENT[program]


@pytest.mark.parametrize("program", sorted(DEEPSEEK_V32_PARENT))
def test_deepseek_v32s_programs_are_the_parents(deepseek_v32_programs,
                                                program):
    assert _hash(deepseek_v32_programs[program]) \
        == DEEPSEEK_V32_PARENT[program]


def test_the_page_copy_bench_rehearses_on_the_cpu(capsys):
    """``tools/mla_page_copy_bench.py --tiny-cpu``: the latent kernel's
    walk with one copy a page and one wait a buffer, and its arithmetic
    over a resident buffer, move the pool's own pages and compute XLA's
    softmax under the interpreter; no time is read off a CPU."""
    import json

    from tools import mla_page_copy_bench as bench
    assert bench.main(["--tiny-cpu", "--only", "c,d"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["copies"]["c"] == {"starts_a_page": 1, "wait": "buffer",
                                  "bytes_a_page": 768, "ms": None}
    assert out["compute"] == {"d": {"buffers": 1, "ms": None}}
