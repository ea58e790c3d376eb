"""The tiers of pytest.ini hold: the tier-1 sweep selects with
``-m 'not slow'``, which REPLACES the default ``-m`` of ``addopts``, so
a test of the envelope or the chaos tier stays out of tier-1 only if it
is marked ``slow`` as well."""

import ast
import pathlib

import pytest


@pytest.mark.parametrize("tier", ["envelope", "chaos"])
def test_every_test_of_a_heavy_tier_is_also_slow(request, tier):
    # what conftest.py saw collected, before any -m deselection
    marks = request.config.collected_marks
    tiered = {nodeid for nodeid, names in marks.items() if tier in names}
    if not tiered:
        pytest.skip(f"no {tier} test collected in this session")
    assert sorted(n for n in tiered if "slow" not in marks[n]) == []


HARNESS = "serving_family.py"
# what the model-serving files used to declare each for itself
SHARED_HELPERS = {"rel_rms", "seqs", "prompt_of", "_prompt", "full_forward",
                  "prefill_then_paged_decode", "prefix_prefill",
                  "paged_decode_from_empty"}


def test_no_test_module_imports_another_and_the_helpers_have_one_home():
    """A helper two test files need lives in a module pytest does not
    collect (``tests/serving_family.py``): a test module that imports
    another runs it a second time under another name, and a copied helper
    is the copy that ``docs/serving.md``, "Adding a model family's
    tests", says not to make."""
    imports, defined = [], []
    for path in sorted(pathlib.Path(__file__).parent.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.FunctionDef):
                if node.name in SHARED_HELPERS and path.name != HARNESS:
                    defined.append(f"{path.name}: def {node.name}")
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                module = getattr(node, "module", None) or ""
                for name in [f"{module}.{a.name}" for a in node.names]:
                    if any(part.startswith("test_") and part != path.stem
                           for part in name.split(".")):
                        imports.append(f"{path.name}: {name}")
    assert imports == [] and defined == []
