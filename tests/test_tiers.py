"""The tiers of pytest.ini hold: the tier-1 sweep selects with
``-m 'not slow'``, which REPLACES the default ``-m`` of ``addopts``, so
a test of the envelope or the chaos tier stays out of tier-1 only if it
is marked ``slow`` as well."""

import pytest


@pytest.mark.parametrize("tier", ["envelope", "chaos"])
def test_every_test_of_a_heavy_tier_is_also_slow(request, tier):
    # what conftest.py saw collected, before any -m deselection
    marks = request.config.collected_marks
    tiered = {nodeid for nodeid, names in marks.items() if tier in names}
    if not tiered:
        pytest.skip(f"no {tier} test collected in this session")
    assert sorted(n for n in tiered if "slow" not in marks[n]) == []
