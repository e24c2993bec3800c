"""BENCHMARK.json against the rules that can be checked from the files: names
and units in the permitted characters, every `moves` an end-to-end metric
that each reporting cell reports, every cell, configuration and metric
found by name with no list in code."""

import copy
import glob
import inspect
import json
import os

import pytest

from benchmark import manifest as mf

MANIFEST = mf.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_the_committed_manifest_is_sound():
    assert mf.check(MANIFEST) == []


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_names_and_units_are_in_the_permitted_characters(section):
    for m in MANIFEST[section]:
        assert mf.NAME_RE.match(m["name"]), m["name"]
        assert mf.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_moves_names_an_end_to_end_metric_every_reporting_cell_reports(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    moved = next(x for x in MANIFEST["end_to_end"] if x["name"] == m["moves"])
    reporting = set(m.get("workloads") or CELLS)
    assert reporting <= set(moved.get("workloads") or CELLS)
    reader = mf.load_module("layer_metrics", metric)
    assert list(inspect.signature(reader.read).parameters) == ["collected"]
    assert reader.__doc__ and m["layer"].split()[0].lower() in reader.__doc__.lower()


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_per_layer_metric(cell):
    e2e = [m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert mf.metrics_for(MANIFEST, "per_layer", cell)
    w = mf.cell(MANIFEST, cell)
    assert w["chips"] == 1
    traffic = mf.load_traffic(w["traffic"])
    assert hasattr(mf.load_module("generators", traffic["generator"]), "drive")
    config = mf.load_config(MANIFEST, w["config"])
    for key in ("engine", "correctness", "source", "model_id", "vocab_size"):
        assert key in config


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_a_configuration_file_cuts_depth_only(config):
    entry = mf.config_entry(MANIFEST, config)
    cfg = mf.load_config(MANIFEST, config)
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    # the published widths of Mistral-7B
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["vocab_size"]) == (4096, 14336, 32, 8, 32000)
    assert cfg["source"] == entry["source"]
    assert cfg["engine"]["num_slots"] == 32 and cfg["engine"]["kv_page_size"] == 128
    # `correct` goes through the extend path too, behind a prefilled prefix
    assert cfg["correctness"]["extend_chunks"] >= 2


def test_open_loop_cells_hold_their_rate_as_a_number():
    for w in MANIFEST["workloads"]:
        traffic = mf.load_traffic(w["traffic"])
        if traffic["generator"] == "open_loop":
            assert isinstance(traffic["rate_per_s"], (int, float))
            assert traffic["rate_per_s"] > 0
            assert traffic["share_of_knee"] <= 0.8


def _mutations():
    def moves_unreported(m):
        # out_tok_per_s is reported by decode-saturated alone, the host's
        # share by every cell
        next(x for x in m["per_layer"]
             if x["name"] == "sched.host_share")["moves"] = "out_tok_per_s"

    def moves_unknown(m):
        m["per_layer"][0]["moves"] = "nothing"

    def bad_name(m):
        m["per_layer"][0]["name"] = "has space"

    def bad_unit(m):
        m["end_to_end"][0]["unit"] = "tokens per second"

    def greek_unit(m):
        m["end_to_end"][0]["unit"] = "µs"

    def extra_key(m):
        m["per_layer"][0]["why"] = "not allowed on a metric"

    def no_setup(m):
        m["end_to_end"] = [x for x in m["end_to_end"] if x["name"] != "setup_s"]

    def four_chips_everywhere(m):
        for w in m["workloads"]:
            w["chips"] = 4

    def loose_bound(m):
        m["end_to_end"][0]["bound"] = 0.2

    def width_reduced(m):
        m["configs"][0]["reduced"].append("hidden_size")

    def same_pair_twice(m):
        m["workloads"].append({**m["workloads"][0], "name": "again"})

    def long_why(m):
        m["workloads"][0]["why"] = "x" * 201

    def run_seconds_too_long(m):
        m["run_seconds"] = 52

    def unknown_cell_listed(m):
        m["per_layer"][0]["workloads"] = ["no-such-cell"]

    def missing_reader(m):
        m["per_layer"].append({**m["per_layer"][-1], "name": "no.such.reader"})

    def command_leaves_repo(m):
        m["command"] = ["python3", "../elsewhere/run.py"]

    return [v for k, v in list(locals().items()) if callable(v)]


@pytest.mark.parametrize("mutate", _mutations(), ids=lambda f: f.__name__)
def test_the_check_catches(mutate):
    m = copy.deepcopy(MANIFEST)
    mutate(m)
    assert mf.check(m), f"{mutate.__name__} went unnoticed"


HARNESS = ("run.py", "launcher.py", "manifest.py", "warmup.py", "procs.py",
           "samples.py", "correctness.py", "routing.py", "check_config.py")


def _code(fn):
    with open(os.path.join(mf.ROOT, "benchmark", fn)) as f:
        code = "\n".join(ln for ln in f.read().splitlines()
                         if not ln.lstrip().startswith("#"))
    return code.split('"""', 2)[-1]  # the module docstring may name some


def test_the_harness_lists_no_cell_metric_or_configuration_in_code():
    names = ([w["name"] for w in MANIFEST["workloads"]]
             + [c["name"] for c in MANIFEST["configs"]]
             + [m["name"] for m in MANIFEST["per_layer"]])
    for fn in HARNESS:
        for n in names:
            assert f'"{n}"' not in _code(fn), f"{fn} names {n}"


def test_no_test_of_the_harness_pins_the_programs_capacity_code():
    """A test under `tests/benchmark/` holds the harness to its contract,
    not the program to what it happens to have: the capacity dispatch, its
    sizing function and the configuration's factor may be deleted by a PR
    that edits the program, so a test names them only where it asks
    whether they are there (`getattr`, `hasattr`). Spelt in pieces, so that
    this file passes its own case."""
    pins = ["_".join(words) for words in (
        ("moe", "dispatch", "combine"), ("default", "capacity"),
        ("capacity", "factor"))]
    here = os.path.dirname(os.path.abspath(__file__))
    found = []
    for path in glob.glob(os.path.join(here, "**", "*.py"), recursive=True):
        with open(path) as f:
            found += [f"{os.path.relpath(path, here)}:{i}: {line.strip()}"
                      for i, line in enumerate(f, 1)
                      if any(pin in line for pin in pins)
                      and "getattr(" not in line and "hasattr(" not in line]
    assert not found, "\n".join(found)


def test_the_harness_names_no_architecture_in_code():
    """Which class a `model_type` gets is the program's to know (the
    launcher asks its `load_config`), and which reference, the
    configuration file's (`correctness.reference`; the table for files that
    name none is in `benchmark/reference/__init__.py`). So no harness file
    compares a `model_type` or holds an architecture's name."""
    from benchmark.reference import REFERENCES

    rehearsal = mf.load(os.path.join(os.path.dirname(__file__), "rehearsal",
                                     "BENCHMARK.json"))
    base = os.path.join(os.path.dirname(__file__), "rehearsal")
    architectures = set(REFERENCES) | {"qwen", "olmoe", "deepseek", "granite"}
    for manifest, root in ((MANIFEST, mf.ROOT), (rehearsal, base)):
        for c in manifest["configs"]:
            architectures.add(mf.load_config(manifest, c["name"], root)["model_type"])
    for fn in HARNESS:
        code = _code(fn).lower()
        assert "model_type" not in code, f"{fn} looks at a model_type"
        for name in architectures:
            assert name not in code, f"{fn} names the architecture {name!r}"


def test_the_manifest_fits_the_size_limit():
    assert os.path.getsize(mf.MANIFEST_PATH) <= 64 * 1024
    assert set(json.load(open(mf.MANIFEST_PATH))) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
