"""``BENCHMARK.json`` against the contract's limits, and against the
files it names: every metric has its reader, with the same constants."""

import json
import os
import re


from benchmark import run as harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok|n_embd|n_inner)")


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "-m", "benchmark.run"]
    assert BENCH["paths"] == ["benchmark"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_configs_and_cells():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        cfg = harness.load_json(ROOT, c["file"])
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert not any(WIDTH.search(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = harness.load_json(harness.HERE, "traffic",
                                    w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            harness.HERE, "drivers", traffic["kind"] + ".py"))


def test_metrics_have_readers_with_the_same_constants():
    cells = [w["name"] for w in BENCH["workloads"]]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    seen = set()
    for group in ("end_to_end", "per_layer"):
        for m in BENCH[group]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["name"] not in seen
            seen.add(m["name"])
            assert m["better"] in ("lower", "higher")
            mod = harness.load_metric(m["name"])
            assert (mod.UNIT, mod.BETTER, mod.SOURCE) == (
                m["unit"], m["better"], m["source"])
            for w in m.get("workloads", []):
                assert w in cells
            if group == "end_to_end":
                assert set(m) <= {"name", "unit", "better", "bound",
                                  "source", "workloads"}
                assert m["source"] in ("host_clock", "device_trace")
                assert 0.01 <= m["bound"] <= 0.1
            else:
                assert set(m) <= {"name", "unit", "better", "source",
                                  "layer", "moves", "workloads"}
                assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])
                target = e2e[m["moves"]]
                for w in m.get("workloads", cells):
                    assert w in target.get("workloads", cells), (
                        f"{m['name']} moves {m['moves']}, which {w} "
                        f"does not report")
    for w in cells:
        assert len(harness.cell_metrics(BENCH, w, "end_to_end")) >= 2
        assert len(harness.cell_metrics(BENCH, w, "per_layer")) >= 1
