#!/usr/bin/env python3
"""Check BENCHMARK.json against the files under perfbench/, without a chip.

    python3 perfbench/check_manifest.py

Every cell resolves to its files (configuration, traffic mix, op), every
metric has a reader (end_to_end/ or layer_metrics/), names and units use only the characters
allowed, every per-layer metric's `moves` is an end-to-end metric that each
cell reporting the per-layer metric reports too, every cell reports `setup_s`,
one more end-to-end metric and a per-layer metric, and a configuration's
`reduced` is the list its file gives. Exits 1 with the faults listed.
"""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def _cells_of(metric, cells):
    return set(metric["workloads"]) if "workloads" in metric else set(cells)


def check(root: str = ROOT) -> list[str]:
    faults: list[str] = []
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    paths = bench["paths"]

    def under_paths(rel):
        return any(rel == p or rel.startswith(p.rstrip("/") + "/") for p in paths)

    configs = {c["name"]: c for c in bench["configs"]}
    cells = [w["name"] for w in bench["workloads"]]
    for c in bench["configs"]:
        if not NAME.match(c["name"]):
            faults.append(f"config name {c['name']!r}")
        if not under_paths(c["file"]) or not os.path.isfile(os.path.join(root, c["file"])):
            faults.append(f"config {c['name']}: no file {c['file']} under {paths}")
            continue
        with open(os.path.join(root, c["file"])) as f:
            data = json.load(f)
        if sorted(data.get("reduced", [])) != sorted(c["reduced"]):
            faults.append(f"config {c['name']}: reduced {c['reduced']} is not its file's {data.get('reduced')}")
        if data.get("source") != c["source"]:
            faults.append(f"config {c['name']}: source differs from its file's")
        for key in c["reduced"]:
            if not NAME.match(key):
                faults.append(f"config {c['name']}: reduced key {key!r}")
    for w in bench["workloads"]:
        if not NAME.match(w["name"]) or not NAME.match(w["traffic"]):
            faults.append(f"workload name {w['name']!r} / traffic {w['traffic']!r}")
        if w["config"] not in configs:
            faults.append(f"workload {w['name']}: no config {w['config']}")
        elif w["name"] != f"{w['config']}.{w['traffic']}":
            faults.append(f"workload {w['name']}: not <config>.<traffic>")
        if w["chips"] not in (1, 4):
            faults.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            faults.append(f"workload {w['name']}: why has {len(w['why'])} characters")
        mixes = [s for s in TRAFFIC_SUFFIXES if os.path.isfile(os.path.join(HERE, "traffic", w["traffic"] + s))]
        if mixes != [".json"]:
            faults.append(f"workload {w['name']}: traffic/{w['traffic']}.json missing")
            continue
        with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
            op = json.load(f).get("op", "")
        if not os.path.isfile(os.path.join(HERE, "ops", op + ".py")):
            faults.append(f"workload {w['name']}: traffic names op {op!r}, no ops/{op}.py")
    for c in configs:
        if not any(w["config"] == c for w in bench["workloads"]):
            faults.append(f"config {c}: no cell uses it")

    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    if "setup_s" not in end_to_end:
        faults.append("no setup_s among end_to_end")
    seen: set[str] = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not NAME.match(m["name"]) or m["name"] in seen:
            faults.append(f"metric name {m['name']!r} (bad or twice)")
        seen.add(m["name"])
        if not UNIT.match(m["unit"]):
            faults.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            faults.append(f"metric {m['name']}: better {m['better']!r}")
        for cell in m.get("workloads", []):
            if cell not in cells:
                faults.append(f"metric {m['name']}: lists {cell}, which is no cell")
    for m in bench["end_to_end"]:
        if not os.path.isfile(os.path.join(HERE, "end_to_end", m["name"] + ".py")):
            faults.append(f"end-to-end metric {m['name']}: no end_to_end/{m['name']}.py")
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end metric {m['name']}: source {m['source']}")
        if not 0.01 <= m["bound"] <= 0.25:
            faults.append(f"end-to-end metric {m['name']}: bound {m['bound']}")
    for m in bench["per_layer"]:
        if not os.path.isfile(os.path.join(HERE, "layer_metrics", m["name"] + ".py")):
            faults.append(f"per-layer metric {m['name']}: no layer_metrics/{m['name']}.py")
        if m["source"] not in ("device_trace", "program_span", "program_counter", "host_clock"):
            faults.append(f"per-layer metric {m['name']}: source {m['source']}")
        moved = end_to_end.get(m["moves"])
        if moved is None:
            faults.append(f"per-layer metric {m['name']}: moves {m['moves']}, which is no end-to-end metric")
        elif not _cells_of(m, cells) <= _cells_of(moved, cells):
            faults.append(f"per-layer metric {m['name']}: a cell that reports it does not report {m['moves']}")
    for cell in cells:
        mine = [m["name"] for m in bench["end_to_end"] if cell in _cells_of(m, cells)]
        if "setup_s" not in mine or len(mine) < 2:
            faults.append(f"cell {cell}: reports {mine}; needs setup_s and one more end-to-end metric")
        if not any(cell in _cells_of(m, cells) for m in bench["per_layer"]):
            faults.append(f"cell {cell}: reports no per-layer metric")
    return faults


if __name__ == "__main__":
    found = check()
    for fault in found:
        print("fault:", fault)
    print(f"check_manifest: {len(found)} fault(s)")
    sys.exit(1 if found else 0)
