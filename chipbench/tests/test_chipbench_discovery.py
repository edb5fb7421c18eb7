"""The harness finds every part of a cell by its name, and a new cell,
configuration, traffic mix or metric is found as new files plus entries."""

from __future__ import annotations

import json
import os
import shutil

import pytest

from chipbench.tests._util import H, ROOT


def test_every_entry_resolves_by_name():
    bench = H.benchmark()
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        cfg = H.load_json("configs", f"{c['name']}.json")
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert callable(H.load_module("configs", f"{c['name']}.py").generate)
        assert callable(H.load_module("configs",
                                      f"{c['name']}.ref.py").reference)
    for w in bench["workloads"]:
        tr = H.load_json("traffic", f"{w['traffic']}.json")
        assert callable(H.load_module("paths", f"{tr['path']}.py").run)
        assert H.metrics_for(bench, "end_to_end", w["name"])
        assert H.metrics_for(bench, "per_layer", w["name"])
    for m in bench["per_layer"]:
        assert callable(H.load_module("metrics", f"{m['name']}.py").read)


def test_metrics_for_follows_workloads_keys():
    bench = H.benchmark()
    e2e = {m["name"] for m in H.metrics_for(bench, "end_to_end",
                                            "q15-sf1-pipeline")}
    assert e2e == {"rows_per_s", "setup_s"}
    layer = {m["name"] for m in H.metrics_for(bench, "per_layer",
                                              "q15-sf1-pipeline")}
    assert "pipeline_roofline" in layer
    assert not any(n.startswith("mesh.") for n in layer)
    assert H.metrics_for(bench, "per_layer", "no-such-cell") == []


def test_each_metric_moves_a_metric_its_cells_report():
    bench = H.benchmark()
    for m in bench["per_layer"]:
        for w in m["workloads"]:
            reported = {e["name"] for e in H.metrics_for(bench, "end_to_end",
                                                         w)}
            assert m["moves"] in reported, (m["name"], w)


def test_new_parts_are_found_as_new_files(tmp_path, monkeypatch):
    """A copy of the benchmark gains a configuration, a traffic mix, a
    metric and a cell by new files and new entries alone."""
    root = tmp_path / "checkout"
    shutil.copytree(H.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = H.benchmark()
    here = root / "chipbench"
    shutil.copy(here / "configs" / "tpch-q15-sf1.json",
                here / "configs" / "tpch-q15-sf10.json")
    for ext in ("py", "ref.py"):
        shutil.copy(here / "configs" / f"tpch-q15-sf1.{ext}",
                    here / "configs" / f"tpch-q15-sf10.{ext}")
    (here / "traffic" / "back-to-back-4.json").write_text(json.dumps(
        {"path": "pipeline", "in_flight": 4}))
    (here / "metrics" / "pipeline.queries.py").write_text(
        "def read(ctx):\n    return ctx.outcome.counters.get('queries')\n")
    bench["configs"].append(dict(bench["configs"][0], name="tpch-q15-sf10",
                                 file="chipbench/configs/tpch-q15-sf10.json"))
    bench["workloads"].append({"name": "q15-sf10-pipeline",
                               "config": "tpch-q15-sf10",
                               "traffic": "back-to-back-4", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "pipeline.queries", "unit": "q",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "compiled pipeline",
                               "moves": "rows_per_s",
                               "workloads": ["q15-sf10-pipeline"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(H, "HERE", str(here))
    monkeypatch.setattr(H, "ROOT", str(root))
    w = H.cell(H.benchmark(), "q15-sf10-pipeline")
    assert H.load_json("traffic", f"{w['traffic']}.json")["in_flight"] == 4
    assert H.load_module("configs", "tpch-q15-sf10.py").rows_consumed(
        H.load_json("configs", "tpch-q15-sf10.json")) == 6_001_215
    names = [m["name"] for m in H.metrics_for(H.benchmark(), "per_layer",
                                              "q15-sf10-pipeline")]
    assert names == ["pipeline.queries"]
    reader = H.load_module("metrics", "pipeline.queries.py")
    outcome = H.Outcome({}, {"queries": 7}, 7, 0, {})
    assert reader.read(H.ReadContext(None, outcome, {})) == 7


def test_peaks_are_keyed_by_device_kind():
    assert H.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert "source" in H.load_json("peaks.json")
    with pytest.raises(KeyError):
        H.peaks("TPU v9 imaginary")
