"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a config and a traffic mix; each lives in
a file of its own, and each per-layer metric in a reader module of its own,
so a later change adds a cell by adding files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import re

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
BENCH = os.path.join(ROOT, "bench")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
# a width is never cut (shape keys of the deployment)
WIDTH_RE = re.compile(r"(_dim|_rank)$|^(d|k|n_eval|mu)$|hidden|intermediate"
                      r"|latent|state|projection|head|expansion|per_token")
RUN_S_MAX, CELLS_MAX = 51, 24
CHECK_BUDGET_S, CHECK_SPARE_S = 43_200, 1_200


def load(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def read_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def traffic_path(name: str) -> str:
    return os.path.join(BENCH, "traffic", f"{name}.json")


def metric_path(name: str) -> str:
    return os.path.join(BENCH, "metrics", f"{name}.py")


def driver_path(name: str) -> str:
    return os.path.join(BENCH, "drivers", f"{name}.py")


def load_module(path: str, modname: str):
    """Import a reader/driver file by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return load_module(metric_path(name),
                       "bench_metric_" + re.sub(r"\W", "_", name))


def driver(name: str):
    return load_module(driver_path(name), f"bench_driver_{name}")


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(bm: dict, workload: str) -> dict:
    """Everything one run of ``workload`` needs, resolved from files."""
    wl = by_name(bm["workloads"], workload, "workload")
    cfg_entry = by_name(bm["configs"], wl["config"], "config")
    config = read_json(cfg_entry["file"])
    with open(traffic_path(wl["traffic"])) as f:
        traffic = json.load(f)
    return {
        "workload": wl, "config_entry": cfg_entry, "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bm["end_to_end"] if applies(m, workload)],
        "per_layer": [m for m in bm["per_layer"] if applies(m, workload)],
    }


def check_seconds(run_seconds: int, cells: int = CELLS_MAX) -> float:
    """Seconds a full check takes at ``cells`` cells (contract formula)."""
    runs = 2 + 14 * cells
    return runs * (run_seconds + 60) + cells * 2 * 90 + CHECK_SPARE_S


def _line(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def validate(bm: dict) -> list[str]:
    """Problems with ``bm`` against the benchmark contract (empty = valid)."""
    p: list[str] = []
    if set(bm) != TOP_KEYS:
        p.append(f"top-level keys {sorted(bm)}")
        return p
    cmd, paths = bm["command"], bm["paths"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_line(w) for w in cmd)):
        p.append("command")
    for w in cmd[1:]:
        if w.startswith("/") or ".." in w.split("/"):
            p.append(f"command word {w!r} leaves the repo")
        elif "/" in w and not any(w.startswith(d + "/") for d in paths):
            p.append(f"command word {w!r} outside paths")
    if not (1 <= len(paths) <= 16 and all(
            PATH_RE.match(d) and not d.startswith("/")
            and ".." not in d.split("/") for d in paths)):
        p.append("paths")
    rs = bm["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= RUN_S_MAX):
        p.append("run_seconds")
    elif check_seconds(rs) > CHECK_BUDGET_S:
        p.append(f"run_seconds={rs} does not fit {CELLS_MAX} cells")

    names = {}
    for key, keys in (("configs", CONFIG_KEYS), ("workloads", WORKLOAD_KEYS)):
        ents = bm[key]
        if not 1 <= len(ents) <= CELLS_MAX:
            p.append(f"{key}: {len(ents)} entries")
        for e in ents:
            if set(e) != keys:
                p.append(f"{key} {e.get('name')}: keys {sorted(e)}")
                continue
            if not NAME_RE.match(e["name"]):
                p.append(f"{key} name {e['name']!r}")
            if not _line(e["why"]):
                p.append(f"{key} {e['name']}: why")
            if e["name"] in names.setdefault(key, set()):
                p.append(f"{key} {e['name']}: duplicate")
            names[key].add(e["name"])
    for c in bm["configs"]:
        if set(c) != CONFIG_KEYS:
            continue
        if not _line(c["source"]):
            p.append(f"config {c['name']}: source")
        if not any(c["file"].startswith(d + "/") for d in paths):
            p.append(f"config {c['name']}: file outside paths")
        elif not os.path.exists(os.path.join(ROOT, c["file"])):
            p.append(f"config {c['name']}: {c['file']} missing")
        if len(c["reduced"]) > 16 or not all(
                NAME_RE.match(r) for r in c["reduced"]):
            p.append(f"config {c['name']}: reduced")
        for r in c["reduced"]:
            if WIDTH_RE.search(r):
                p.append(f"config {c['name']}: reduces width {r!r}")
    files = [c["file"] for c in bm["configs"]]
    if len(set(files)) != len(files):
        p.append("two configs share a file")
    used = {w["config"] for w in bm["workloads"] if "config" in w}
    if used != names.get("configs", set()):
        p.append(f"configs unused or unknown: {used ^ names['configs']}")
    pairs = set()
    for w in bm["workloads"]:
        if set(w) != WORKLOAD_KEYS:
            continue
        if not (NAME_RE.match(w["config"]) and NAME_RE.match(w["traffic"])):
            p.append(f"workload {w['name']}: config/traffic name")
        if w["chips"] not in (1, 4):
            p.append(f"workload {w['name']}: chips")
        if (w["config"], w["traffic"]) in pairs:
            p.append(f"workload {w['name']}: pair repeated")
        pairs.add((w["config"], w["traffic"]))
        if not os.path.exists(traffic_path(w["traffic"])):
            p.append(f"workload {w['name']}: no traffic file")
    four = sum(w.get("chips") == 4 for w in bm["workloads"])
    if four > max(1, math.floor(len(bm["workloads"]) * 0.5)):
        p.append(f"{four} of {len(bm['workloads'])} cells on 4 chips")

    cells = names.get("workloads", set())
    metric_names: set[str] = set()
    e2e = bm["end_to_end"]
    if not 1 <= len(e2e) <= 16:
        p.append("end_to_end count")
    if not 1 <= len(bm["per_layer"]) <= 128:
        p.append("per_layer count")
    for m in e2e + bm["per_layer"]:
        base = E2E_KEYS if m in e2e else LAYER_KEYS
        if set(m) - {"workloads"} != base:
            p.append(f"metric {m.get('name')}: keys {sorted(m)}")
            continue
        if not NAME_RE.match(m["name"]) or m["name"] in metric_names:
            p.append(f"metric name {m['name']!r}")
        metric_names.add(m["name"])
        if not UNIT_RE.match(m["unit"]):
            p.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            p.append(f"metric {m['name']}: better")
        if m["source"] not in (SOURCES_E2E if m in e2e else SOURCES):
            p.append(f"metric {m['name']}: source {m['source']}")
        if "workloads" in m and not (set(m["workloads"]) <= cells
                                     and m["workloads"]):
            p.append(f"metric {m['name']}: workloads")
    e2e_names = {m["name"] for m in e2e}
    if "setup_s" not in e2e_names:
        p.append("no setup_s")
    for m in e2e:
        if set(m) - {"workloads"} != E2E_KEYS:
            continue
        top = 0.25
        if not (isinstance(m["bound"], (int, float))
                and 0.01 <= m["bound"] <= top):
            p.append(f"metric {m['name']}: bound {m['bound']}")
    for m in bm["per_layer"]:
        if set(m) - {"workloads"} != LAYER_KEYS:
            continue
        if not _line(m["layer"]):
            p.append(f"metric {m['name']}: layer")
        if m["moves"] not in e2e_names:
            p.append(f"metric {m['name']}: moves {m['moves']!r}")
            continue
        moved = by_name(e2e, m["moves"], "metric")
        for w in m.get("workloads", sorted(cells)):
            if not applies(moved, w):
                p.append(f"metric {m['name']}: cell {w} lacks {m['moves']}")
        if not os.path.exists(metric_path(m["name"])):
            p.append(f"metric {m['name']}: no reader file")
    for w in cells:
        got = [m["name"] for m in e2e if applies(m, w)]
        if "setup_s" not in got or len(got) < 2:
            p.append(f"cell {w}: needs setup_s and another end-to-end metric")
        if not any(applies(m, w) for m in bm["per_layer"]):
            p.append(f"cell {w}: no per-layer metric")
    if len(json.dumps(bm)) > 64 * 1024:
        p.append("BENCHMARK.json over 64 KiB")
    return p
