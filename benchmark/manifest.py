"""`BENCHMARK.json`: loading it, and the harness's own check of it.

The harness lists no cell, configuration or metric in code. Everything is
found from the manifest by name: a configuration's `file`, a cell's traffic
mix at `benchmark/traffic/<traffic>.json`, an end-to-end metric's function at
`benchmark/e2e_metrics/<name>.py`, a per-layer metric's reader at
`benchmark/layer_metrics/<name>.py`, and an architecture's plain reference at
`benchmark/reference/<name>.py`, named by the configuration's file.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST_PATH = os.path.join(ROOT, "BENCHMARK.json")

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_RE = re.compile(r"hidden_size|intermediate_size|latent|state_size|proj|"
                      r"expan|_dim$|_rank$|head_dim|head_size|"
                      r"num_experts_per_tok")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def load(path: str = MANIFEST_PATH) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(
        f"no workload {name!r} in BENCHMARK.json; it has "
        f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")


def config_path(manifest: dict, name: str, base: str = ROOT) -> str:
    """A configuration's file; `base` is the directory of the manifest that
    names it (the repo's root, or a rehearsal's directory)."""
    return os.path.join(base, config_entry(manifest, name)["file"])


def load_config(manifest: dict, name: str, base: str = ROOT) -> dict:
    with open(config_path(manifest, name, base)) as f:
        return json.load(f)


def traffic_path(traffic: str, base: str = ROOT) -> str:
    """A traffic mix's file: beside a rehearsal's manifest if it has one of
    that name, else the benchmark's own."""
    beside = os.path.join(base, "traffic", traffic + ".json")
    if base != ROOT and os.path.isfile(beside):
        return beside
    return os.path.join(HERE, "traffic", traffic + ".json")


def load_traffic(traffic: str, base: str = ROOT) -> dict:
    with open(traffic_path(traffic, base)) as f:
        return json.load(f)


def metrics_for(manifest: dict, section: str, cell_name: str) -> list[dict]:
    """The metrics of `section` ("end_to_end" or "per_layer") that the cell
    reports: those with no `workloads` key, and those that list the cell."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_module(kind: str, name: str, base: str = ROOT):
    """The module `benchmark/<kind>/<name>.py`, found by the metric's (or
    generator's, kernel's or reference's) name; names may hold dots, so it
    is loaded from its path and not imported. Like a traffic mix, it is
    taken from beside a rehearsal's manifest (`base`) if that has one of the
    name, else from the benchmark's own."""
    path = os.path.join(base, kind, name + ".py")
    if base == ROOT or not os.path.isfile(path):
        path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"{kind[:-1] if kind.endswith('s') else kind} "
                            f"{name!r} has no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check(manifest: dict) -> list[str]:
    """Everything the harness can see wrong with the manifest before a run;
    an empty list when it is sound. Not the driver's check: the rules that
    can be checked from the files alone."""
    bad: list[str] = []

    def name_ok(what: str, value) -> None:
        if not isinstance(value, str) or not NAME_RE.match(value):
            bad.append(f"{what} {value!r} is not a permitted name")

    if set(manifest) != TOP_KEYS:
        return [f"top-level keys differ from the contract's: missing "
                f"{sorted(TOP_KEYS - set(manifest))}, unexpected "
                f"{sorted(set(manifest) - TOP_KEYS)}"]
    if not (isinstance(manifest["run_seconds"], int)
            and 1 <= manifest["run_seconds"] <= 51):
        bad.append("run_seconds must be a whole number from 1 to 51")
    paths = manifest["paths"]
    for word in manifest["command"]:
        if word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r} leaves the repo")

    def under_paths(p: str) -> bool:
        return any(p == d or p.startswith(d.rstrip("/") + "/") for d in paths)

    configs = {}
    files = set()
    for c in manifest["configs"]:
        name_ok("configuration", c.get("name"))
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"configuration {c.get('name')!r} has keys {sorted(c)}")
        if c["name"] in configs:
            bad.append(f"configuration {c['name']!r} appears twice")
        configs[c["name"]] = c
        if not under_paths(c["file"]):
            bad.append(f"configuration file {c['file']!r} is not under paths")
        if c["file"] in files:
            bad.append(f"configuration file {c['file']!r} is used twice")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(ROOT, c["file"])):
            bad.append(f"configuration file {c['file']!r} does not exist")
        for k in c["reduced"]:
            name_ok("reduced key", k)
            if WIDTH_RE.search(k):
                bad.append(f"configuration {c['name']!r} reduces a width: {k}")

    cells = {}
    pairs = set()
    for w in manifest["workloads"]:
        name_ok("workload", w.get("name"))
        name_ok("traffic", w.get("traffic"))
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')!r} has keys {sorted(w)}")
        if w["name"] in cells:
            bad.append(f"workload {w['name']!r} appears twice")
        cells[w["name"]] = w
        if w["config"] not in configs:
            bad.append(f"workload {w['name']!r} names unknown configuration "
                       f"{w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"pair {(w['config'], w['traffic'])} appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']!r} asks for {w['chips']} chips")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad.append(f"workload {w['name']!r}: why must be one line of at "
                       f"most 200 characters ({len(w['why'])})")
        if not os.path.isfile(traffic_path(w["traffic"])):
            bad.append(f"traffic mix {w['traffic']!r} has no file")
    for c in configs:
        if not any(w["config"] == c for w in cells.values()):
            bad.append(f"configuration {c!r} is used by no cell")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} cells ask for 4 chips")

    names = set()
    e2e = {}
    for m in manifest["end_to_end"]:
        name_ok("metric", m.get("name"))
        if not set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"} or not {"name", "unit", "better",
                                               "bound", "source"} <= set(m):
            bad.append(f"end-to-end metric {m.get('name')!r} has keys {sorted(m)}")
        if m["name"] in names:
            bad.append(f"metric {m['name']!r} appears twice")
        names.add(m["name"])
        e2e[m["name"]] = m
        if not UNIT_RE.match(m.get("unit", "")):
            bad.append(f"metric {m['name']!r}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']!r}: better {m.get('better')!r}")
        if m.get("source") not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end metric {m['name']!r} has source "
                       f"{m.get('source')!r}")
        if not (isinstance(m.get("bound"), (int, float))
                and 0.01 <= m["bound"] <= 0.1):
            bad.append(f"metric {m['name']!r}: bound {m.get('bound')!r}")
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    elif "workloads" in e2e["setup_s"]:
        bad.append("setup_s must be reported by every cell")

    def reported_in(metric: dict) -> set[str]:
        return set(metric.get("workloads") or cells)

    for m in manifest["end_to_end"]:
        for w in m.get("workloads", ()):
            if w not in cells:
                bad.append(f"metric {m['name']!r} lists unknown cell {w!r}")
    for m in manifest["per_layer"]:
        name_ok("metric", m.get("name"))
        if not set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"} or not {
                "name", "unit", "better", "source", "layer",
                "moves"} <= set(m):
            bad.append(f"per-layer metric {m.get('name')!r} has keys {sorted(m)}")
        if m["name"] in names:
            bad.append(f"metric {m['name']!r} appears twice")
        names.add(m["name"])
        if not UNIT_RE.match(m.get("unit", "")):
            bad.append(f"metric {m['name']!r}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']!r}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m['name']!r}: source {m.get('source')!r}")
        layer = m.get("layer", "")
        if not 1 <= len(layer) <= 200 or "\n" in layer or "\t" in layer:
            bad.append(f"metric {m['name']!r}: layer {layer!r}")
        for w in m.get("workloads", ()):
            if w not in cells:
                bad.append(f"metric {m['name']!r} lists unknown cell {w!r}")
        moved = e2e.get(m.get("moves"))
        if moved is None:
            bad.append(f"metric {m['name']!r} moves {m.get('moves')!r}, "
                       "which is no end-to-end metric")
        else:
            missing = reported_in(m) - reported_in(moved)
            if missing:
                bad.append(f"metric {m['name']!r} moves {m['moves']!r}, which "
                           f"{sorted(missing)} do not report")
        if not os.path.isfile(os.path.join(HERE, "layer_metrics",
                                           m["name"] + ".py")):
            bad.append(f"per-layer metric {m['name']!r} has no reader file")
    for m in manifest["end_to_end"]:
        if m["name"] != "setup_s" and not os.path.isfile(
                os.path.join(HERE, "e2e_metrics", m["name"] + ".py")):
            bad.append(f"end-to-end metric {m['name']!r} has no file")
    for w in cells:
        got_e2e = [m["name"] for m in metrics_for(manifest, "end_to_end", w)]
        if len([n for n in got_e2e if n != "setup_s"]) < 1:
            bad.append(f"cell {w!r} reports no end-to-end metric but setup_s")
        if not metrics_for(manifest, "per_layer", w):
            bad.append(f"cell {w!r} reports no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        bad.append("BENCHMARK.json is over 64 KiB")
    return bad
