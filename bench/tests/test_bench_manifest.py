"""``BENCHMARK.json`` against the contract, and the harness found by name."""
import json
import os
import shutil

import pytest

from bench.lib import manifest


@pytest.fixture(scope="module")
def bm():
    return manifest.load()


def test_manifest_is_valid(bm):
    assert manifest.validate(bm) == []


def test_names_and_units(bm):
    metrics = bm["end_to_end"] + bm["per_layer"]
    for e in bm["configs"] + bm["workloads"] + metrics:
        assert manifest.NAME_RE.match(e["name"]), e["name"]
    for m in metrics:
        assert manifest.UNIT_RE.match(m["unit"]), m["unit"]
    assert not manifest.NAME_RE.match("bad name")
    assert not manifest.UNIT_RE.match("rows per second")


def test_every_layer_metric_moves_a_metric_its_cells_report(bm):
    for m in bm["per_layer"]:
        moved = manifest.by_name(bm["end_to_end"], m["moves"], "metric")
        for w in m.get("workloads", [c["name"] for c in bm["workloads"]]):
            assert manifest.applies(moved, w), (m["name"], w)


def test_four_chip_cells_at_most_half(bm):
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    assert four <= max(1, len(bm["workloads"]) // 2)
    bad = json.loads(json.dumps(bm))
    bad["workloads"] = [dict(w, chips=4) for w in bad["workloads"]] * 1
    bad["workloads"] += [dict(w, name=w["name"] + "-x", chips=4,
                              traffic=w["traffic"] + "-x")
                         for w in bm["workloads"]]
    assert any("4 chips" in p for p in manifest.validate(bad))


def test_contract_limits_are_enforced(bm):
    bad = json.loads(json.dumps(bm))
    bad["end_to_end"][0]["bound"] = 0.3
    bad["run_seconds"] = 52
    bad["per_layer"][0]["moves"] = "no_such_metric"
    bad["configs"][0]["reduced"] = ["d"]
    probs = " ".join(manifest.validate(bad))
    for want in ("bound", "run_seconds", "moves", "reduces width"):
        assert want in probs, (want, probs)


def test_each_metric_reader_declares_what_the_manifest_says(bm):
    for m in bm["per_layer"]:
        r = manifest.metric_reader(m["name"])
        assert (r.LAYER, r.UNIT, r.SOURCE, r.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
        assert r.read({}) is None          # nothing to read: no number


def test_each_config_states_its_driver_limits_and_cuts(bm):
    for c in bm["configs"]:
        cfg = manifest.read_json(c["file"])
        assert cfg["name"] == c["name"]
        assert os.path.exists(manifest.driver_path(cfg["driver"]))
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert cfg["published"][key] != cfg[key]
        assert cfg["limits"] and all(v >= 0 for v in cfg["limits"].values())


def test_a_new_cell_is_files_plus_entries(tmp_path, bm):
    """A config, a traffic mix and a per-layer metric added as new files and
    new manifest entries load with no edit to any existing file."""
    added = []
    try:
        src = manifest.read_json(bm["configs"][0]["file"])
        cfg = dict(src, name="throwaway-cfg")
        cpath = os.path.join(manifest.BENCH, "configs", "throwaway-cfg.json")
        tpath = manifest.traffic_path("throwaway-mix")
        mpath = manifest.metric_path("throwaway_metric.batch")
        for path, body in ((cpath, json.dumps(cfg)),
                           (tpath, json.dumps({"kind": "closed_jobs",
                                               "clients": 1, "stream": 7})),
                           (mpath, 'LAYER = "device"\nUNIT = "s"\n'
                                   'SOURCE = "device_trace"\n'
                                   'MOVES = "batch_rows_per_s"\n\n\n'
                                   'def read(rec):\n    return 1.5\n')):
            with open(path, "w") as f:
                f.write(body)
            added.append(path)
        new = json.loads(json.dumps(bm))
        new["configs"].append(dict(bm["configs"][0], name="throwaway-cfg",
                                   file="bench/configs/throwaway-cfg.json"))
        new["workloads"].append({"name": "throwaway-cell",
                                 "config": "throwaway-cfg",
                                 "traffic": "throwaway-mix", "chips": 1,
                                 "why": "a test entry"})
        new["per_layer"].append({"name": "throwaway_metric.batch",
                                 "unit": "s", "better": "lower",
                                 "source": "device_trace", "layer": "device",
                                 "moves": "batch_rows_per_s",
                                 "workloads": ["throwaway-cell"]})
        for m in new["end_to_end"]:
            if "workloads" in m:
                m["workloads"].append("throwaway-cell")
        assert manifest.validate(new) == []
        cell = manifest.cell(new, "throwaway-cell")
        assert cell["config"]["name"] == "throwaway-cfg"
        assert cell["traffic"]["stream"] == 7
        names = [m["name"] for m in cell["per_layer"]]
        assert names == ["throwaway_metric.batch"]
        assert manifest.metric_reader(names[0]).read({}) == 1.5
        assert manifest.driver(cell["config"]["driver"]).run
    finally:
        for path in added:
            os.remove(path)
        shutil.rmtree(os.path.join(manifest.BENCH, "metrics", "__pycache__"),
                      ignore_errors=True)
