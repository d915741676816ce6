"""Every piece the benchmark names exists, and is found by its name."""
import json
import os
import re

from common import ROOT, load_json, load_module, names

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_each_cell_names_pieces_that_exist():
    configs = {c["name"] for c in BENCHMARK["configs"]}
    assert configs == {w["config"] for w in BENCHMARK["workloads"]}
    for w in BENCHMARK["workloads"]:
        cell = load_json("workloads", w["name"] + ".json")
        assert cell["config"] == w["config"] in configs
        assert cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"]
        assert cell["why"] == w["why"]
        load_json("traffic", w["traffic"] + ".json")
        mod = load_module("configs", w["config"])
        for fn in ("System", "reference", "counts"):
            assert hasattr(mod, fn)
        assert set(cell["limits"]) <= {"loss_gap", "grad_norm_gap",
                                       "change_norm_gap", "codes_differing"}
    assert {w["name"] for w in BENCHMARK["workloads"]} <= set(
        names("workloads", ".json"))


def test_each_metric_has_a_reader_and_reaches_its_cells():
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    for m in BENCHMARK["per_layer"]:
        assert callable(load_module("metrics", m["name"]).read)
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])
    assert {m["name"] for m in BENCHMARK["per_layer"]} <= set(
        names("metrics", ".py"))


def test_names_and_files_keep_the_contract():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCHMARK[group]:
            assert NAME.fullmatch(e["name"]), e["name"]
    for c in BENCHMARK["configs"]:
        f = json.load(open(os.path.join(ROOT, c["file"])))
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert sorted(f["reduced"]) == sorted(c["reduced"])
    for w in BENCHMARK["workloads"]:
        assert len(w["why"]) <= 200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_each_cell_reports_one_end_to_end_metric_besides_setup():
    """``setup_s`` everywhere, and one rate per cell: the metric a cell's
    per-layer metrics move."""
    for w in BENCHMARK["workloads"]:
        e2e = [m["name"] for m in BENCHMARK["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e, w["name"]
        assert len(e2e) == 2, (w["name"], e2e)
